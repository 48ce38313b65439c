"""``wall_per_bio_s``: seconds of wall time per second of simulated
biology, over every whole call of the window (entry call until counts and
state are on the host)."""


def read(w) -> float:
    return w.window_s / (w.calls * w.steps * w.dt_ms * 1e-3)
