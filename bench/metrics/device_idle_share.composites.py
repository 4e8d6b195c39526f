"""Share of the traced window in which no operation ran on the chip, in
the composite's bulk cell."""

from tracefile import idle_share_pct


def read(record):
    return idle_share_pct(record) if record["kind"] == "engine" else None
