"""Phases of the device solve, read from a profiler trace that holds the
program's own spans (``repro.*``, see ``src/repro/trace.py``).

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>
                            [--untraced <s>] [--keep <file>]

Runs a sweep cell as ``run.py`` sets it up, with every garbage collection
recorded as a span, then (optionally) an untraced window of ``--untraced``
seconds and a traced window of ``--seconds`` (at most the harness's trace
cap). Prints one JSON line: solves per second in both windows, the slowest
solves and the garbage collections of each window, rounds per solve, and
the reduction below. ``--keep`` copies the trace's
``.xplane.pb`` to a file. Not part of a benchmark run: the result line of
``run.py`` does not read it.

The reduction adds to ``bench/trace.py``'s, which it returns unchanged
(``busy_s``, ``window_s``, ``device_ops``, the ``bench.`` spans):

* ``programs``: executions of each device program (the ``XLA Modules``
  line, the id dropped) inside the window, count and seconds, mean over
  the chips used;
* ``clock_offset_us``, per chip: each execution of a program is paired,
  in order per program, with the ``repro.solve.launch`` span that launched
  it (its ``program`` argument names it), and launches with their
  ``repro.solve.wait`` in order. A device cannot start before its launch
  span began, ``lo = max(launch start - device start)``, and the host
  cannot leave the wait before the device ended,
  ``hi = min(wait end - device end)``. The midpoint is added to the
  chip's device times; with no pair, or ``lo > hi``, ``applied`` is null,
  the chip keeps its own clock and the phase readings are ``None``;
* ``idle_by_span``: the idle time of the aligned device, cut at every host
  span boundary, each piece charged to the innermost ``repro.`` or
  ``bench.`` span open over it, or to ``"no span"``; the labels sum to
  ``window_s - busy_s_aligned``;
* ``idle_gaps``: the ten longest gaps on the aligned clock of chip 0, each
  named by the innermost span open at its start.
"""

from __future__ import annotations

import bisect
import collections
import gc
import json
import pathlib
import shutil
import sys
import time

T0 = time.perf_counter()

if __name__ == "__main__":
    sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent.parent)]

from bench import trace  # noqa: E402

SPAN_PREFIXES = ("repro.", "bench.")
MODULES_LINE = "XLA Modules"
LAUNCH, WAIT = "repro.solve.launch", "repro.solve.wait"
SOLVE_PROGRAM = "jit__serve_batch"


def read_planes(path: str):
    """Parse an ``.xplane.pb`` into ``reduce_phases``' form: planes of lines
    of ``(name, start_ns, duration_ns, args)``, keeping device operations
    and programs and the ``repro.``/``bench.`` host spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (trace.OPS_LINE, MODULES_LINE):
                continue
            if device:
                evs = [(e.name, e.start_ns, e.duration_ns, {})
                       for e in line.events]
            else:
                evs = [(e.name, e.start_ns, e.duration_ns,
                        dict(e.stats) if e.name == LAUNCH else {})
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIXES)]
            lines.append((line.name, evs))
        out.append((plane.name, lines))
    return out


def _device_planes(planes) -> dict[int, dict[str, list]]:
    devices = {}
    for pname, lines in planes:
        if pname.startswith("/device:TPU:") and pname[12:].isdigit():
            devices[int(pname[12:])] = {
                lname: [(n, s, s + d) for n, s, d, *_ in evs]
                for lname, evs in lines}
    return devices


def _host_spans(planes) -> list[tuple[str, float, float, dict]]:
    return [(n, s, s + d, (rest or [{}])[0])
            for pname, lines in planes if pname.startswith("/host:")
            for _, evs in lines for n, s, d, *rest in evs
            if n.startswith(SPAN_PREFIXES)]


def clock_offset(modules, spans) -> dict:
    """Causal bounds on the device clock's offset from the host's, in us
    (added to device times), from the solves that the spans launched and
    waited for; see the module docstring."""
    launches = sorted((s for s in spans if s[0] == LAUNCH),
                      key=lambda x: x[1])
    waits = sorted((s for s in spans if s[0] == WAIT), key=lambda x: x[1])
    if launches:       # a wait begun before any launch awaits an older one
        waits = [w for w in waits if w[1] >= launches[0][1]]
    by_program = collections.defaultdict(list)
    for launch, wait in zip(launches, waits):
        by_program[launch[3].get("program")].append((launch, wait))
    execs = collections.defaultdict(list)
    for name, s, e in sorted(modules, key=lambda x: x[1]):
        execs[name.split("(")[0]].append((s, e))
    lo, hi, pairs = -float("inf"), float("inf"), 0
    for program, issued in by_program.items():
        ran = execs.get(program, [])
        # executions beyond the launches traced were launched before the
        # trace began; launches beyond the executions ran after it ended
        ran = ran[max(0, len(ran) - len(issued)):]
        for (launch, wait), (s, e) in zip(issued, ran):
            lo = max(lo, launch[1] - s)
            hi = min(hi, wait[2] - e)
            pairs += 1
    if not pairs:
        return {"lo": None, "hi": None, "applied": None, "pairs": 0}
    applied = (lo + hi) / 2 if lo <= hi else None
    return {"lo": lo * 1e-3, "hi": hi * 1e-3,
            "applied": None if applied is None else applied * 1e-3,
            "pairs": pairs}


def _segments(spans, lo: float, hi: float):
    """Cut [lo, hi] at every span boundary: the cut points, and the label
    of each piece, the innermost span open over it (latest start)."""
    points = sorted({lo, hi} | {x for _, s, e, _ in spans for x in (s, e)
                                if lo < x < hi})
    order = sorted(spans, key=lambda x: x[1])
    active, j, labels = [], 0, []
    for a in points[:-1]:
        while j < len(order) and order[j][1] <= a:
            active.append(order[j])
            j += 1
        active = [sp for sp in active if sp[2] > a]
        labels.append(max(active, key=lambda sp: (sp[1], -sp[2]))[0]
                      if active else "no span")
    return points, labels


def _charge(gaps, points, labels, idle: collections.Counter,
            scale: float) -> None:
    """Add each piece of the sorted ``gaps`` between two cut points to the
    label of that piece, times ``scale``."""
    i = 0
    for g0, g1 in gaps:
        while points[i + 1] <= g0:
            i += 1
        j = i
        while j < len(labels) and points[j] < g1:
            piece = min(g1, points[j + 1]) - max(g0, points[j])
            if piece > 0:
                idle[labels[j]] += piece * scale
            j += 1


def reduce_phases(planes, n_chips: int) -> dict:
    """``bench/trace.py``'s reduction of ``planes`` plus the keys of the
    module docstring."""
    out = trace.reduce_planes(
        [(p, [(ln, [ev[:3] for ev in evs]) for ln, evs in lines])
         for p, lines in planes], n_chips)
    spans = _host_spans(planes)
    devices = _device_planes(planes)
    windows = [(s, e) for n, s, e, _ in spans if n == trace.WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:               # as bench/trace.py: the whole trace
        ends = [(s, e) for lines in devices.values()
                for _, s, e in lines.get(trace.OPS_LINE, [])]
        ends += [(s, e) for n, s, e, _ in spans
                 if n.startswith(trace.SPAN_PREFIX)]
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    inner = [sp for sp in spans if sp[0] != trace.WINDOW_SPAN
             and sp[1] < hi and sp[2] > lo]
    for n, s, e, _ in inner:
        if n.startswith("repro."):
            agg = out["spans"].setdefault(n, [0, 0.0])
            agg[0] += 1
            agg[1] += (min(e, hi) - max(s, lo)) * 1e-9
    points, labels = _segments(inner, lo, hi)
    chips = sorted(devices)[:n_chips]
    offsets, busy, gaps0 = [], [], []
    programs: dict[str, list[float]] = {}
    idle: collections.Counter = collections.Counter()
    for k, chip in enumerate(chips):
        lines = devices[chip]
        off = clock_offset(lines.get(MODULES_LINE, []), spans)
        offsets.append(off)
        shift = (off["applied"] or 0.0) * 1e3
        for name, s, e in lines.get(MODULES_LINE, []):
            s, e = max(s + shift, lo), min(e + shift, hi)
            if e > s:
                agg = programs.setdefault(name.split("(")[0], [0, 0.0])
                agg[0] += 1 / len(chips)
                agg[1] += (e - s) * 1e-9 / len(chips)
        covered, merged = trace._union(
            [(s + shift, e + shift)
             for _, s, e in lines.get(trace.OPS_LINE, [])],
            lo, hi)
        busy.append(covered * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        _charge(gaps, points, labels, idle, 1e-9 / len(chips))
        if k == 0:
            gaps0 = gaps
    named = []
    for g0, g1 in sorted(gaps0, key=lambda g: g[0] - g[1])[:10]:
        at = min(bisect.bisect_right(points, g0) - 1, len(labels) - 1)
        named.append([labels[at], (g1 - g0) * 1e-9])
    out.update(programs=programs, clock_offset_us=offsets,
               busy_s_aligned=sum(busy) / len(busy) if busy else 0.0,
               idle_by_span=dict(idle.most_common()), idle_gaps=named)
    return out


def phase_metrics(tr: dict, counters: dict) -> dict:
    """The per-solve readings of the solve's phases: device time per
    admission round, idle per solve under launch, readback (wait and
    fetch) and unpack, and the share of the window idle under a garbage
    collection. ``None`` where the window holds nothing to read."""
    solves, rounds = counters.get("solves", 0), counters.get("rounds", 0)
    prog = tr.get("programs", {}).get(SOLVE_PROGRAM)
    out = {"round_us": prog[1] / rounds * 1e6 if prog and rounds else None}
    aligned = tr.get("clock_offset_us") and all(
        o["applied"] is not None for o in tr["clock_offset_us"])
    idle = tr.get("idle_by_span", {})
    for key, labels in (("launch_idle_ms", ["repro.solve.launch"]),
                        ("readback_idle_ms", ["repro.solve.wait",
                                              "repro.solve.fetch"]),
                        ("unpack_idle_ms", ["repro.solve.unpack"])):
        out[key] = (sum(idle.get(n, 0.0) for n in labels) / solves * 1e3
                    if aligned and solves else None)
    gc_s = sum(v for n, v in idle.items() if n.startswith("repro.gc."))
    out["gc_idle_pct"] = (100.0 * gc_s / tr["window_s"]
                          if aligned and tr["window_s"] > 0 else None)
    return out


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--untraced", type=float, default=0.0)
    p.add_argument("--keep", default=None)
    args = p.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root / "src")]
    from repro.trace import install_gc_spans

    from bench import deploy, generator, harness

    install_gc_spans()
    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "sweep":
        p.error("only sweep cells are read here")
    harness.configure_jax(root / ".jax_cache")
    devices, why = harness.accelerators(cell.chips)
    if devices is None:
        print(why, file=sys.stderr)
        return 1
    from repro.core import stack_instances
    from repro.core.greedy import solve_device_batch
    from repro.core.sfesp import device_stack

    mix, pool_cfg = cell.traffic, cell.config["pool"]
    devs = [device_stack(stack_instances(
        [deploy.program_instance(pool_cfg, t)
         for t in deploy.sweep_batch(mix, s)]))
        for s in generator.sweep_seeds(mix, args.seed)]
    rounds = [solve_device_batch(d)["rounds"] for d in devs]
    setup_s = time.perf_counter() - T0
    result = {"workload": cell.name, "seed": args.seed, "setup_s": setup_s,
              "device": harness.device_entry(devices, 0),
              "rounds_per_solve": rounds}

    def window(seconds: float, prof) -> tuple[harness.Window, dict]:
        gc0 = [g["collections"] for g in gc.get_stats()]
        win, _, _ = harness.sweep_loop(devs, seconds,
                                       harness.Spans(prof.on), args.seed,
                                       prof)
        return win, {
            "solves_per_s": win.counters["solves"] / win.seconds,
            "slowest_ms": [t * 1e3 for t in sorted(win.ticks)[-5:]],
            "gc_collections": [g["collections"] - n for g, n in
                               zip(gc.get_stats(), gc0)]}

    if args.untraced > 0:
        result["untraced"] = window(args.untraced, harness.Profiler(False))[1]
    prof = harness.Profiler(True)
    win, result["traced"] = window(min(args.seconds, harness.TRACE_CAP_S),
                                   prof)
    solves = win.counters["solves"]
    counters = {"solves": solves,
                "rounds": sum(rounds[k % len(devs)] for k in range(solves))}
    try:
        path = trace.find_xplane(prof.dir)
        if args.keep:
            shutil.copyfile(path, args.keep)
        tr = reduce_phases(read_planes(path), cell.chips)
    finally:
        shutil.rmtree(prof.dir, ignore_errors=True)
    result.update(counters=counters, metrics=phase_metrics(tr, counters),
                  idle_ms_per_solve={n: v / solves * 1e3
                                     for n, v in tr["idle_by_span"].items()},
                  trace={k: tr[k] for k in (
                      "window_s", "busy_s", "busy_s_aligned",
                      "clock_offset_us", "programs", "idle_gaps", "spans")})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
