"""Device: share of the traced window in which no operation ran, %."""
from bench.layer import idle_pct


def read(r):
    return idle_pct(r)
