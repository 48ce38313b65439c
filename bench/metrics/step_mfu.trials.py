"""``step_mfu.trials``: percent of the chip's peak that the required work
of every lane would take, over the traced window (``bench/work.py``)."""


def read(m):
    return 100.0 * m.least_s / (m.window_s * m.chips)
