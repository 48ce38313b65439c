"""``device_ms_per_step``: device busy milliseconds (union of operation
intervals, mean over the cell's chips) per simulated step of a call."""


def read(m):
    return 1e3 * m.busy_s / m.steps
