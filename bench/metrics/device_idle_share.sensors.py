"""Share of the traced window in which no operation ran on the device."""

from tracefile import idle_share_pct


def read(record):
    return idle_share_pct(record) if record["kind"] == "service" else None
