"""Share of the traced window in which no operation ran on the device (%)."""

from bench.trace import idle_share_pct


def read(rec):
    return idle_share_pct(rec.trace)
