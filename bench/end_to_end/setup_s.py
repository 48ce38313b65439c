"""``setup_s``: process start to the first timed call (connectome made,
simulator state built, one warm-up call of the cell's shape, and, in a
run whose compile cache is cold, compilation)."""


def read(w) -> float:
    return w.setup_s
