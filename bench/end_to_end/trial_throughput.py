"""``trial_throughput``: biological seconds completed per wall second,
summed over the lanes of every whole call of the window."""


def read(w) -> float:
    return w.lanes * w.calls * w.steps * w.dt_ms * 1e-3 / w.window_s
