"""The composite cell's whole window as a share of the chip's int8 peak:
the frames the composite step classified in the traced window, times the
counted operations per frame, over the window's seconds and the peak."""

from tracefile import step_events


def read(record):
    if record["kind"] != "engine" or not record.get("trace"):
        return None
    n, _ = step_events(record, record["step_modules"])
    if not n:
        return None
    ops = n * record["frames_per_step_event"] * record["work"]["ops_per_frame"]
    peak = record["chips"] * record["peaks"]["int8_ops_per_s"]
    return 100.0 * ops / (record["trace"]["window_s"] * peak)
