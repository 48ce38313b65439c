"""``device_ms_per_step.trials``: device busy milliseconds per step of the
vmapped scan, one step advancing every lane of the batch."""


def read(m):
    return 1e3 * m.busy_s / m.steps
