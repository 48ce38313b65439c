"""``delivery_ms_per_step``: device milliseconds per simulated step in the
step's delivery layer (scope ``delivery`` with its parts: slot owners,
synapse gather and scatter, drop count; mean over the cell's chips;
``bench/layers.py``); nothing where no operation ran there."""


def read(m):
    return m.layers.get("delivery") or None
