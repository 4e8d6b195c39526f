"""The composite step's share of its roofline: the least time the counted
work of its runs in the traced window could take, over the summed device
time of those runs.  The work is the configuration's ``served_work``,
counted over each specialist's served active clauses
(``ServeStats.active_clauses``); peaks from ``harness.PEAKS``."""

from tracefile import step_events
from work import least_step_s


def read(record):
    if record["kind"] != "engine":
        return None
    n, device_s = step_events(record, record["step_modules"])
    if not n or device_s <= 0:
        return None
    least = n * least_step_s(record["work"], record["peaks"],
                             record["frames_per_step_event"])
    return 100.0 * least / device_s
