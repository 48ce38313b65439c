"""``delivery_ms_per_step.trials``: device milliseconds per step of the
vmapped scan (one step advancing every lane) in the delivery layer
(``bench/layers.py``); nothing where no operation ran there."""


def read(m):
    return m.layers.get("delivery") or None
