"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""

from tracefile import idle_share_pct


def read(record):
    return idle_share_pct(record) if record["kind"] == "engine" else None
