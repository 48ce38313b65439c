"""``slot_chunks_per_step``: chunks of synapse slots the delivery's slot
loop ran per simulated step, summed over lanes and chips (the program's
counter ``slot_chunks``); nothing where the program does not count it."""


def read(m):
    return m.counters.get("slot_chunks")
