"""One run of one cell: set-up, measured window, comparison, result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is data found by name: ``BENCHMARK.json`` names the cell's
configuration file and mix (``bench/traffic/<mix>.json``), and each
per-layer metric is read by ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import check, deploy, generator, roofline
from bench import trace as trace_mod

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_CAP_S = 4.0          # longest window a --trace 1 run records
GRACE_S = 60.0             # longest wait for answers due in the window
CHECK_CELL_TICKS = 3072    # serving cell-ticks compared (at least 3 ticks)
CHECK_READBACKS = 4        # sweep readbacks compared (besides each last)
CHECK_INSTANCES = 160      # sweep instances compared per batch
PREFILL_STEPS = (0.25, 0.5, 1.0, 2.0, 4.0, 8.0)   # x prefill_step_s


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


class CompileClock:
    """Counts JAX backend compiles (seconds and number) in this process."""

    def __init__(self):
        import jax.monitoring

        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1


def configure_jax(cache_dir: pathlib.Path) -> None:
    """Keep every compiled program in the checkout's persistent cache,
    small ones included, so only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerators(chips: int):
    """The devices of the run, or an error message: a TPU, at least
    ``chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, (f"JAX found no TPU (platform {devs[0].platform}); "
                      "this benchmark never runs on another backend")
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devs)}"
    return devs[:chips], ""


class Spans:
    """Host spans around the program's public calls, written into the
    profiler's trace; free when the run is not traced."""

    def __init__(self, on: bool):
        self.on = on
        self._null = contextlib.nullcontext()

    def __call__(self, name: str):
        if not self.on:
            return self._null
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)


class Profiler:
    """A profiler trace of the window, into a temporary directory."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self):
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def reduce(self, chips: int) -> dict:
        try:
            return trace_mod.reduce_trace(self.dir, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Reservoir:
    """A uniform sample of ``k`` items of a stream of unknown length, drawn
    from a seeded generator."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.n = k, rng, 0
        self.items: list = []

    def offer(self, make) -> None:
        self.n += 1
        if len(self.items) < self.k:
            self.items.append(make())
            return
        j = int(self.rng.integers(self.n))
        if j < self.k:
            self.items[j] = make()


@dataclasses.dataclass
class Window:
    start: float                 # host clock at the window's start
    seconds: float
    ticks: list[float]
    counters: dict
    checks: dict
    attempted: int
    failed: int
    extra: dict


# ------------------------------------------------------------------ serving
def closed_loop(eng, gen, dep, seconds: float, spans: Spans, seed: int,
                prof: Profiler) -> Window:
    """Set-up (seeding and warm ticks) is done; measure ``seconds`` of
    ingest -> dispatch -> commit ticks, next events drawn after commit."""
    from repro.core.events import Arrival, Departure

    sample = Reservoir(check_ticks(dep), np.random.default_rng([seed, 1]))
    withdrawn: dict[int, int] = {}
    ticks: list[float] = []
    events_in = 0
    rows0 = eng.sesm.delta_rows
    n = 0
    prof.start()
    t_start = time.perf_counter()
    deadline = t_start + seconds
    with spans("bench.window"):
        events = gen.tick(eng)
        while True:
            for ev in events:
                if type(ev) is Departure:
                    withdrawn[ev.request_id] = n
            t0 = time.perf_counter()
            with spans("bench.ingest"):
                eng.ingest(events)
            with spans("bench.dispatch"):
                handle = eng.reslice_dispatch()
            with spans("bench.commit"):
                decisions = eng.reslice_commit(handle)
            t1 = time.perf_counter()
            ticks.append(t1 - t0)
            events_in += len(events)
            fresh = [(ev.request.request_id, ev.cell) for ev in events
                     if type(ev) is Arrival]
            sample.offer(lambda: (n, decisions, fresh))
            n += 1
            if t1 >= deadline:
                break
            events = gen.tick(eng)
    window = t1 - t_start
    prof.stop()
    counters = dict(ticks=n, events=events_in,
                    delta_rows=eng.sesm.delta_rows - rows0)
    return Window(t_start, window, ticks, counters, {},
                  attempted=events_in, failed=0,
                  extra=dict(kept=sample.items, sent=gen.sent,
                             withdrawn=withdrawn))


def check_ticks(dep) -> int:
    return max(3, CHECK_CELL_TICKS // dep.n_cells)


def merge_ties(into: dict, ties: dict, prefix: str = "") -> dict:
    """Add one comparison's tie readings to a run's: ties followed summed,
    the largest gap kept."""
    k, g = f"{prefix}ties_followed", f"{prefix}tie_gap_max"
    into[k] = into.get(k, 0) + ties["ties_followed"]
    into[g] = max(into.get(g, 0.0), ties["tie_gap_max"])
    return into


def serving_checks(dep, kept, sent, withdrawn) -> tuple[dict, dict]:
    """The compared numbers with their limits, and the readings of the
    comparison (``bench/check.py``): its ties, and the share of sampled
    ticks and of sampled (tick, coupling group) pairs in which the link
    budgets decided something."""
    mism = stale = missing = 0
    bound = bound_ticks = 0
    readings = merge_ties({}, {"ties_followed": 0, "tie_gap_max": 0.0})
    for tick, decisions, fresh in kept:
        m, t, expected = check.compare_tick(dep, decisions)
        mism += m
        merge_ties(readings, t)
        g = check.groups_bound(dep, decisions, expected)
        bound += g
        bound_ticks += g > 0
        s, m = check.membership(decisions, tick, sent, withdrawn, fresh)
        stale += s
        missing += m
    n = max(len(kept), 1)
    readings["links_bound_ticks"] = bound_ticks / n
    readings["links_bound_groups"] = bound / (n * len(dep.groups()))
    return ({"mismatched_decisions": [mism, 0],
             "stale_decisions": [stale, 0], "missing_arrivals": [missing, 0],
             "ticks_compared": [len(kept), ">=1"]},
            readings)


def serving_controls(dep, kept) -> dict:
    """Mismatches of the controls put in the program's place, on the
    sampled ticks' candidate lists."""
    import ml_dtypes

    out = {"bfloat16": 0, "budgets_left_out": 0}
    for _, decisions, _ in kept:
        for key, ctl in (
                ("bfloat16", check.reference_tick(dep, decisions,
                                                  ml_dtypes.bfloat16)[0]),
                ("budgets_left_out", check.reference_tick(
                    dep, decisions, coupled=False)[0])):
            m, t, _ = check.compare_tick(dep, decisions, ctl)
            out[key] += m
            merge_ties(out, t, f"{key}.")
    return out


def open_warmup(eng, gen) -> float:
    """High-water burst, first solve, then ``prefill_s`` of traffic replayed
    in simulated time. Returns the simulated time reached."""
    eng.ingest(gen.high_water())
    eng.reslice()
    sim = 0.0
    k = 0
    while sim < gen.prefill_s:
        sim += gen.prefill_step_s * PREFILL_STEPS[k % len(PREFILL_STEPS)]
        k += 1
        handle = eng.reslice_dispatch()
        eng.ingest(gen.due(sim)[0])
        eng.reslice_commit(handle)
    return sim


def open_loop(eng, gen, dep, seconds: float, spans: Spans, seed: int,
              prof: Profiler, sim: float) -> Window:
    """The double-buffered open loop: dispatch, ingest every event now due,
    commit. ``warm_s`` runs on the clock first. An arrival ingested in tick
    m is seated by dispatch m+1 and decided by commit m+1, unless it left in
    the same ingest (withdrawn, never due a decision). Its latency is that
    commit's end minus its due time. After the window, ticks go on until
    every arrival due in the window is decided, for at most ``GRACE_S``."""
    origin = time.perf_counter() - sim
    state = {"n": 0}
    left_at: dict[int, int] = {}      # rid -> tick that ingested its leave

    def tick():
        t0 = time.perf_counter()
        with spans("bench.dispatch"):
            handle = eng.reslice_dispatch()
        events, arrivals, gone = gen.due(time.perf_counter() - origin)
        with spans("bench.ingest"):
            eng.ingest(events)
        with spans("bench.commit"):
            decisions = eng.reslice_commit(handle)
        t1 = time.perf_counter()
        state["n"] += 1
        for rid in gone:
            left_at[rid] = state["n"]
        return t0, t1, decisions, len(events), arrivals

    while time.perf_counter() - origin < sim + gen.warm_s:
        tick()

    carry: list[tuple[int, float, int, int]] = []   # rid, due, cell, tick
    lat: list[float] = []
    withdrawn_n = 0

    def settle(decisions, t1):
        nonlocal carry, withdrawn_n
        ids: dict[int, set] = {}
        keep = []
        for item in carry:
            rid, due, cell, m = item
            if left_at.get(rid) == m:
                withdrawn_n += 1
                continue
            got = ids.get(cell)
            if got is None:
                got = ids[cell] = {d.request.request_id
                                   for d in decisions[cell]}
            if rid in got:
                lat.append(t1 - origin - due)
            else:
                keep.append(item)
        carry = keep

    sample = Reservoir(check_ticks(dep), np.random.default_rng([seed, 1]))
    ticks: list[float] = []
    events_in = attempted = 0
    rows0 = eng.sesm.delta_rows
    prev: list[tuple[int, int]] = []
    prof.start()
    t_start = time.perf_counter()
    lo = t_start - origin
    hi = lo + seconds
    with spans("bench.window"):
        while True:
            t0, t1, decisions, n_events, arrivals = tick()
            settle(decisions, t1)
            n = state["n"]
            for rid, due, cell in arrivals:
                if lo <= due <= hi:
                    carry.append((rid, due, cell, n))
                    attempted += 1
            ticks.append(t1 - t0)
            events_in += n_events
            fresh = [f for f in prev if left_at.get(f[0]) != n - 1]
            sample.offer(lambda: (n, decisions, fresh))
            prev = [(rid, cell) for rid, _, cell in arrivals]
            if t1 - t_start >= seconds:
                break
    window = t1 - t_start
    prof.stop()
    counters = dict(ticks=len(ticks), events=events_in,
                    delta_rows=eng.sesm.delta_rows - rows0)
    close = time.perf_counter()
    while carry and time.perf_counter() - close < GRACE_S:
        _, t1, decisions, _, arrivals = tick()
        settle(decisions, t1)
        for rid, due, cell in arrivals:
            if lo <= due <= hi:
                carry.append((rid, due, cell, state["n"]))
                attempted += 1
    failed = len(carry)
    withdrawn = {rid: m + 1 for rid, m in left_at.items()}
    return Window(t_start, window, ticks, counters, {},
                  attempted=attempted - withdrawn_n, failed=failed,
                  extra=dict(latencies=lat, withdrawn=withdrawn_n,
                             undecided_wait_s=GRACE_S if failed else 0.0,
                             kept=sample.items, sent=gen.sent,
                             withdrawn_at=withdrawn))


# -------------------------------------------------------------------- sweep
def sweep_loop(devs, seconds: float, spans: Spans, seed: int,
               prof: Profiler) -> tuple[Window, dict, dict]:
    """``solve_device_batch`` back to back, alternating over the staged
    batches, each result read back to the host."""
    from repro.core.greedy import solve_device_batch

    sample = Reservoir(CHECK_READBACKS, np.random.default_rng([seed, 1]))
    last: dict[int, dict] = {}
    ticks: list[float] = []
    solves = 0
    prof.start()
    t_start = time.perf_counter()
    with spans("bench.window"):
        while True:
            b = solves % len(devs)
            t0 = time.perf_counter()
            with spans("bench.solve"):
                res = solve_device_batch(devs[b])
            t1 = time.perf_counter()
            ticks.append(t1 - t0)
            last[b] = res
            sample.offer(lambda: (b, res))  # noqa: B023 (called at once)
            solves += 1
            if t1 - t_start >= seconds:
                break
    window = t1 - t_start
    prof.stop()
    return (Window(t_start, window, ticks,
                   dict(ticks=solves, solves=solves), {}, attempted=0,
                   failed=0, extra={}), last, sample.items)


# ------------------------------------------------------------------ results
def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def load_reader(name: str, root: pathlib.Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Reading:
    """What a per-layer reader sees: the traced window's spans and device
    numbers, the run's counters, the device's peaks."""

    cell: str
    trace: dict
    counters: dict
    peaks: dict

    def span_s(self, name: str) -> float | None:
        got = self.trace["spans"].get(name)
        return None if got is None else got[1]


def run(cell: Cell, seed: int, seconds: float, traced: bool, t0: float,
        devices, log, control: bool = False) -> dict:
    """One run; returns the result line's object. ``control`` adds the
    controls' readings under ``control`` (``bench/control.py``)."""
    clock = CompileClock()
    loop = cell.traffic["loop"]
    spans = Spans(traced)
    prof = Profiler(traced)
    if traced:
        seconds = min(seconds, TRACE_CAP_S)
    mark = time.perf_counter()
    parts: dict[str, float] = {"init_s": mark - t0}
    if loop in ("closed", "open"):
        dep = deploy.metro(cell.config)
        eng = deploy.engine(dep, cell.chips)
        parts["build_s"] = time.perf_counter() - mark
        mark = time.perf_counter()
        if loop == "closed":
            gen = generator.Closed(cell.traffic, dep, seed)
            eng.ingest(gen.initial())
            eng.reslice()
            for _ in range(gen.warm_ticks):
                eng.ingest(gen.tick(eng))
                eng.reslice_commit(eng.reslice_dispatch())
            sim = None
        else:
            gen = generator.Open(cell.traffic, dep, seed)
            sim = open_warmup(eng, gen)
        parts["warm_s"] = time.perf_counter() - mark
        compiles0 = clock.count
        parts["compile_s"] = clock.secs
        if loop == "closed":
            win = closed_loop(eng, gen, dep, seconds, spans, seed, prof)
        else:
            # the clock warm-up before the window belongs to set-up too
            win = open_loop(eng, gen, dep, seconds, spans, seed, prof, sim)
            parts["warm_s"] = win.start - mark
        compiles = clock.count - compiles0
        counters = dict(win.counters)
    else:
        from repro.core import stack_instances
        from repro.core.sfesp import device_stack
        from repro.core.greedy import solve_device_batch

        mix, pool_cfg = cell.traffic, cell.config["pool"]
        batches = [deploy.sweep_batch(mix, s)
                   for s in generator.sweep_seeds(mix, seed)]
        insts = [[deploy.program_instance(pool_cfg, t) for t in b]
                 for b in batches]
        parts["build_s"] = time.perf_counter() - mark
        mark = time.perf_counter()
        stacked = [stack_instances(i) for i in insts]
        devs = [device_stack(s) for s in stacked]
        for d in devs:
            solve_device_batch(d)
        parts["warm_s"] = time.perf_counter() - mark
        parts["compile_s"] = clock.secs
        compiles0 = clock.count
        win, last, kept = sweep_loop(devs, seconds, spans, seed, prof)
        compiles = clock.count - compiles0
        counters = dict(win.counters)
        B = stacked[0].batch_size
        counters["instances"] = counters["solves"] * B
        win.attempted = counters["instances"]
        counters["work"] = roofline.sweep_work(
            stacked[0], [last[b]["admitted"] for b in sorted(last)])
    setup_s = win.start - t0
    peak = memory_peak(devices) if devices is not None else 0
    controls = None
    mark = time.perf_counter()
    if loop == "sweep":
        del devs, stacked, insts
        win.checks, ties, controls = sweep_checks(cell, batches, last, kept,
                                                  seed, control)
    else:
        del eng
        x = win.extra
        withdrawn = x["withdrawn_at"] if loop == "open" else x["withdrawn"]
        win.checks, ties = serving_checks(dep, x["kept"], x["sent"],
                                          withdrawn)
        if control:
            controls = serving_controls(dep, x["kept"])
    check_s = time.perf_counter() - mark     # controls included, if asked
    device = device_entry(devices, peak)
    metrics: dict[str, dict] = {}
    breakdown = None
    if traced:
        tr = prof.reduce(cell.chips)
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        peaks = load_peaks(devices)
        reading = Reading(cell.name, tr, counters, peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": [[n.split(" ")[0], t]
                                    for n, t in tr["device_ops"]],
                     "idle_gaps": tr["idle_gaps"]}
    else:
        values = e2e_values(loop, win, setup_s, counters)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    checks = win.checks
    correct = all(_within(v, lim) for v, lim in checks.values())
    out = {"correct": correct, "attempted": int(win.attempted),
           "failed": int(win.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    info = dict(setup_parts=parts, compiles_in_window=compiles,
                window_s=win.seconds, check_s=check_s, **counters, **ties)
    if loop == "open":
        info["withdrawn"] = win.extra["withdrawn"]
    out["info"] = info
    if controls is not None:
        out["control"] = controls
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    log(f"setup parts: {json.dumps(parts)}; compiles in window: {compiles}")
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    return out


def _within(value, limit) -> bool:
    if isinstance(limit, str) and limit.startswith(">="):
        return value >= float(limit[2:])
    return value <= limit


def e2e_values(loop: str, win: Window, setup_s: float,
               counters: dict) -> dict:
    vals = {"setup_s": setup_s}
    if loop == "closed":
        vals["tick_ms"] = win.seconds / counters["ticks"] * 1e3
        vals["tick_p50_ms"] = percentile(win.ticks, 50) * 1e3
        vals["tick_p99_ms"] = percentile(win.ticks, 99) * 1e3
    elif loop == "open":
        lat = list(win.extra["latencies"])
        # an arrival never decided missed every limit: it counts at the
        # longest wait the run allowed it
        lat += [win.extra["undecided_wait_s"]] * win.failed
        vals["decision_p95_ms"] = percentile(lat, 95) * 1e3 if lat \
            else float("nan")
    else:
        vals["sweep_inst_per_s"] = counters["instances"] / win.seconds
    return vals


def sweep_checks(cell: Cell, batches, last: dict, kept: list, seed: int,
                 control: bool) -> tuple[dict, dict, dict | None]:
    """Compare a seeded sample of each batch's instances in every kept
    readback of it; with ``control``, also the controls' readings."""
    import ml_dtypes

    pool_cfg = cell.config["pool"]
    rng = np.random.default_rng([seed, 2])
    mism = compared = 0
    ties = merge_ties({}, {"ties_followed": 0, "tie_gap_max": 0.0})
    ctl = {"bfloat16": 0, "capacity_not_charged": 0}

    def compare(got, chosen) -> tuple[int, dict]:
        expected, t = check.sweep_reference(pool_cfg, chosen,
                                            hint=check.hints(got))
        return check.mismatches(got, expected), t

    for b, batch in enumerate(batches):
        rows = np.sort(rng.choice(len(batch), min(CHECK_INSTANCES,
                                                  len(batch)), replace=False))
        chosen = [batch[k] for k in rows]
        reads = [last[b]] if b in last else []
        reads += [r for i, r in kept if i == b]
        for r in reads:
            m, t = compare(check.readback_rows(r, rows, chosen), chosen)
            mism += m
            merge_ties(ties, t)
            compared += 1
        if control:
            for key, kw in (("bfloat16", {"dtype": ml_dtypes.bfloat16}),
                            ("capacity_not_charged", {"charge": False})):
                m, t = compare(check.sweep_reference(pool_cfg, chosen,
                                                     **kw)[0], chosen)
                ctl[key] += m
                merge_ties(ctl, t, f"{key}.")
    return ({"mismatched_decisions": [mism, 0],
             "readbacks_compared": [compared, ">=1"]},
            ties, ctl if control else None)


def device_entry(devices, peak: int) -> dict:
    if devices is None:
        return {"platform": "none", "kind": "none", "count": 0,
                "memory_peak_bytes": 0}
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def load_peaks(devices) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind if devices is not None else "none"
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table["devices"][kind]


def emit(out: dict) -> None:
    """The result as the last line of standard output (the compared numbers
    were the last lines ``run`` wrote to standard error)."""
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
