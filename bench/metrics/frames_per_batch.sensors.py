"""Frames per microbatch: the change of ``ServiceStats.images`` over the
window divided by the change of ``ServiceStats.batches``."""


def read(record):
    c = record.get("counters")
    if not c or not c["batches"]:
        return None
    return c["images"] / c["batches"]
