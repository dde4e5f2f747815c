"""The share of the traced window in which the chip ran no operation,
in % (``trace.idle_pct``)."""

from bench.trace import idle_pct


def read(rec):
    return idle_pct(rec["trace"])
