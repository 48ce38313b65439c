"""``device_idle_share.trials``: percent of the traced window of a trial
battery in which no operation ran on the device."""


def read(m):
    return 100.0 * (1.0 - m.busy_s / m.window_s)
