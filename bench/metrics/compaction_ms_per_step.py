"""``compaction_ms_per_step``: device milliseconds per simulated step in the
step's compaction layer (scope ``compaction``: the spiking neurons' ids
gathered into the spike budget; mean over the cell's chips;
``bench/layers.py``); nothing where no operation ran there."""


def read(m):
    return m.layers.get("compaction") or None
