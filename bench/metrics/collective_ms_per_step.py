"""``collective_ms_per_step``: device milliseconds in collective operations
(mean over the cell's chips) per simulated step; nothing where the trace
holds no collective."""


def read(m):
    if m.collective_s is None:
        return None
    return 1e3 * m.collective_s / m.steps
