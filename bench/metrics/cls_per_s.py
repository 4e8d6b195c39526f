"""Frames returned in the window over the window's seconds (host clock)."""


def read(record):
    if record.get("frames_done") is None:
        return None
    return record["frames_done"] / record["seconds"]
