"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device and span numbers.

* Device busy: the union of the intervals of the operations on each
  accelerator plane (``/device:TPU:<n>``; the line ``XLA Ops``), clipped to
  the traced window, averaged over the chips used.
* Host spans: the benchmark's own ``TraceAnnotation`` spans (names starting
  ``bench.``), as count and total seconds inside the window.
* Breakdown: the device operations that took most time, and the longest
  idle gaps of chip 0, each named by the innermost ``bench.`` span that was
  open on the host when the gap began.

The window is the span ``bench.window``; without it, the whole trace.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return paths[0]


def _union(intervals: list[tuple[float, float]], lo: float, hi: float
           ) -> tuple[float, list[tuple[float, float]]]:
    """Covered length of ``intervals`` inside [lo, hi], and the merged
    intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def reduce_planes(planes, n_chips: int) -> dict:
    """Reduce parsed planes: an iterable of ``(plane name, [(line name,
    [(event name, start_ns, duration_ns), ...]), ...])``.

    Returns ``window_s``, ``busy_s`` (mean over the first ``n_chips``
    device planes), ``busy_s_per_chip``, ``spans`` {name: [count, seconds]},
    ``device_ops`` and ``idle_gaps`` (top 10 each, seconds).
    """
    host_spans: list[tuple[str, float, float]] = []
    devices: dict[int, list[tuple[str, float, float]]] = {}
    for pname, lines in planes:
        if pname.startswith("/device:TPU:") and pname[12:].isdigit():
            ops = [ev for lname, evs in lines if lname == OPS_LINE
                   for ev in evs]
            devices[int(pname[12:])] = [(n, s, s + d) for n, s, d in ops]
        elif pname.startswith("/host:"):
            for _, evs in lines:
                host_spans.extend((n, s, s + d) for n, s, d in evs
                                  if n.startswith(SPAN_PREFIX))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        ends = [(s, e) for evs in devices.values() for _, s, e in evs]
        ends += [(s, e) for _, s, e in host_spans]
        if not ends:
            raise ValueError("trace holds neither device operations nor "
                             "benchmark spans")
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    chips = sorted(devices)[:n_chips]
    busy, merged0 = [], []
    op_time: collections.Counter = collections.Counter()
    for k, chip in enumerate(chips):
        ivs = [(s, e) for _, s, e in devices[chip]]
        covered, merged = _union(ivs, lo, hi)
        busy.append(covered * 1e-9)
        if k == 0:
            merged0 = merged
            for name, s, e in devices[chip]:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    op_time[name] += d * 1e-9
    spans: dict[str, list[float]] = {}
    inner = sorted(((s, e, n) for n, s, e in host_spans
                    if n != WINDOW_SPAN and s < hi and e > lo))
    for s, e, n in inner:
        agg = spans.setdefault(n, [0, 0.0])
        agg[0] += 1
        agg[1] += (min(e, hi) - max(s, lo)) * 1e-9
    gaps = []
    edges = [lo] + [x for iv in merged0 for x in iv] + [hi]
    starts = [s for s, _, _ in inner]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 > g0:
            gaps.append((g1 - g0, g0))
    gaps.sort(reverse=True)
    idle = collections.Counter()
    named = []
    for length, g0 in gaps:
        label = "no span"
        i = bisect.bisect_right(starts, g0)
        for s, e, n in reversed(inner[max(0, i - 8):i]):
            if s <= g0 < e:
                label = n
                break
        idle[label] += length * 1e-9
        if len(named) < 10:
            named.append([label, length * 1e-9])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "busy_s_per_chip": busy,
        "chips_traced": len(chips),
        "spans": spans,
        "device_ops": [[n, t] for n, t in op_time.most_common(10)],
        "idle_gaps": named,
        "idle_by_span": dict(idle.most_common(10)),
    }


def read_planes(path: str):
    """Parse an ``.xplane.pb`` into the plain form :func:`reduce_planes`
    takes, keeping device operation lines and ``bench.`` spans only."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                   if device or e.name.startswith(SPAN_PREFIX)]
            lines.append((line.name, evs))
        out.append((plane.name, lines))
    return out


def reduce_trace(log_dir: str, n_chips: int) -> dict:
    return reduce_planes(read_planes(find_xplane(log_dir)), n_chips)
