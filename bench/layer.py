"""Arithmetic shared by the per-layer readers of ``bench/metrics``.

A reader returns ``None`` where its window holds nothing to read; the run
then leaves the metric out of its line.
"""

from __future__ import annotations


def per_tick(r, value: float | None, counter: str = "ticks") -> float | None:
    n = r.counters.get(counter, 0)
    if value is None or not n:
        return None
    return value / n


def device_ms_per(r, counter: str) -> float | None:
    if r.trace["busy_s"] <= 0:
        return None
    return per_tick(r, r.trace["busy_s"] * 1e3, counter)


def idle_pct(r) -> float | None:
    window = r.trace["window_s"]
    if window <= 0 or r.trace["chips_traced"] == 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / window)
