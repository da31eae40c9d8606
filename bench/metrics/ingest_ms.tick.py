"""Serving ingest: seconds under the span `bench.ingest` per tick, ms."""
from bench.layer import per_tick


def read(r):
    s = r.span_s("bench.ingest")
    return per_tick(r, None if s is None else s * 1e3)
