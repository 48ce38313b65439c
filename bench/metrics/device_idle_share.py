"""``device_idle_share``: percent of the traced window in which no
operation ran on the device (mean over the cell's chips)."""


def read(m):
    return 100.0 * (1.0 - m.busy_s / m.window_s)
