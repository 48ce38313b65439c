"""``step_mfu``: percent of the chips' peak that the model's required work
would take, over the traced window (``bench/work.py``)."""


def read(m):
    return 100.0 * m.least_s / (m.window_s * m.chips)
