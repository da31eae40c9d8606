"""Smoke run of the metro re-slice loop on TPU chips (a smoke run, not a
benchmark: its timings are one sample each, taken on the host clock).

Usage, from the root of a checkout::

    python chip_smoke.py             # one chip: the default phase
    python chip_smoke.py --chips 4   # four chips: only the sharded metro phase

Default phase — the 256-cell / 32-domain metro deployment of the
``serving/metro_reslice_256cell`` benchmark (``multi_cell_pools(256,
seed=1)``, one backhaul link per domain at 1.2 x domain size, the 4-app mix
on every cell, 1024 requests) served by ``MultiCellEngine`` on a "cells"
mesh:

1. the warm tick and three churned ticks (arrivals and departures through
   ``ingest``) bit-match the coupled numpy oracle in all 32 domains;
2. after warm-up the session is steady: one fresh stack, no session rebuild,
   one shard plan, no recompile of the sharded serve program;
3. one data-plane tick (``process``) runs the admitted vision jobs through
   the compiled resize kernel; one batch is checked against ``resize_ref``;
4. the gathered instances solved with ``inner="pallas"`` (the compiled
   ``batch_round`` kernel) equal the jnp round and the oracle.

``--chips 4`` phase — 1024 cells / 128 domains on a 4-device "cells" mesh:
decisions equal a meshless single-device engine on every cell and the oracle
on sampled domains, and each device holds its own block of coupling groups.

Nothing here runs off the chip: when JAX's first device is not a TPU the
script exits non-zero without printing the result line. On success the last
stdout line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import statistics
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

# (app class, min accuracy, jobs/s) — the benchmark's per-cell request mix
MIX = [("coco_bags", 0.35, 8.0), ("coco_animals", 0.50, 6.0),
       ("cityscapes_flat", 0.35, 5.0), ("coco_person", 0.20, 5.0)]
# compiled resize kernel vs the matrix-form oracle at fp32 precision
RESIZE_ATOL = 1e-4


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile events (seconds and count) in-process."""

    def __init__(self):
        import jax.monitoring

        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def since(self, mark: tuple[float, int]) -> str:
        return f"{self.secs - mark[0]:.2f} s in {self.count - mark[1]} compiles"

    def mark(self) -> tuple[float, int]:
        return self.secs, self.count


def request(app: str, acc: float, fps: float):
    from repro.serving import SliceRequest

    return SliceRequest("object-recognition", "yolox", app,
                        max_latency_s=0.7, min_accuracy=acc,
                        jobs_per_sec=fps)


def metro_engine(n_cells: int, n_domains: int, mesh):
    """The metro deployment: one backhaul link per contiguous domain at
    1.2 x its cell count, the 4-app mix submitted to every cell."""
    from repro.core import CouplingSpec, scenarios
    from repro.serving import MultiCellEngine

    pools = scenarios.multi_cell_pools(n_cells, seed=1)
    domain = (np.arange(n_cells) * n_domains) // n_cells
    inc = np.zeros((n_cells, n_domains), bool)
    inc[np.arange(n_cells), domain] = True
    spec = CouplingSpec(np.bincount(domain, minlength=n_domains) * 1.2, inc)
    eng = MultiCellEngine(pools, coupling=spec, mesh=mesh, max_retries=3)
    for c in range(n_cells):
        for app, acc, fps in MIX:
            eng.submit(request(app, acc, fps), c)
    return eng, domain


def coupled_instances(eng, sets, cells=None):
    """The solver instances of ``sets[k]`` in cell ``cells[k]`` (default:
    cell k), each carrying its cell's row of the engine's coupling."""
    cells = range(len(sets)) if cells is None else cells
    return [dataclasses.replace(eng.sdla.build_instance(rs, eng.pools[c]),
                                coupling=eng.coupling.row(c))
            for c, rs in zip(cells, sets)]


def oracle(eng, domain, domains) -> dict:
    """``solve_coupled_ref`` on the engine's gathered candidate sets, one
    closed domain at a time: cell -> (admitted flags, allocation rows)."""
    from repro.core import solve_coupled_ref

    sets = eng.gather()
    out = {}
    for d in domains:
        idxs = [i for i in np.flatnonzero(domain == d) if sets[i]]
        insts = coupled_instances(eng, [sets[i] for i in idxs], idxs)
        for i, ref in zip(idxs, solve_coupled_ref(insts)):
            out[int(i)] = (ref.admitted.tolist(), ref.alloc)
    return out


def assert_matches(decisions, expected: dict, what: str) -> None:
    """Admission flags and admitted allocations equal, cell by cell."""
    for cell, (adm, alloc) in expected.items():
        got = decisions[cell]
        assert [d.admitted for d in got] == adm, f"{what}: cell {cell}"
        for t, d in enumerate(got):
            if d.admitted:
                assert np.array_equal(list(d.alloc.values()), alloc[t]), \
                    f"{what}: cell {cell} task {t} allocation"


def same_decisions(a, b, what: str) -> None:
    for cell, (da, db) in enumerate(zip(a, b)):
        assert [(d.admitted, d.z, d.alloc) for d in da] == \
            [(d.admitted, d.z, d.alloc) for d in db], f"{what}: cell {cell}"


def churn(engines, rng, n_cells: int, k: int) -> None:
    """``k`` cells each lose their first live request and gain a new one —
    the same events for every engine, through ``ingest``. A departure frees
    a slot the arrival then takes, so the Tmax bucket holds."""
    from repro.serving import Arrival, Departure

    cells = rng.choice(n_cells, size=k, replace=False)
    apps = rng.integers(0, len(MIX), size=k)
    for eng in engines:
        events = []
        for c, a in zip(cells, apps):
            live = eng.cells[c].live_ids()
            if live:
                events.append(Departure(live[0], int(c)))
            events.append(Arrival(request(*MIX[a]), int(c)))
        summary = eng.ingest(events)
        assert summary["placed"] == k, summary


def default_phase(mesh, clock: CompileClock, *, n_cells: int = 256,
                  n_domains: int = 32, churn_ticks: int = 3,
                  steady_ticks: int = 5) -> None:
    import jax

    from repro.core import solve_greedy_batch
    from repro.core.greedy import _sharded_serve_fn
    from repro.kernels.resize import ops as resize_ops

    t0 = time.perf_counter()
    eng, domain = metro_engine(n_cells, n_domains, mesh)
    all_domains = range(n_domains)
    log(f"metro deployment: {n_cells} cells, {n_domains} domains, "
        f"{n_cells * len(MIX)} requests, mesh {dict(mesh.shape)} "
        f"({time.perf_counter() - t0:.2f} s to build)")

    # 1. warm tick: session build + compile + solve, oracle-checked
    expect = oracle(eng, domain, all_domains)
    mark = clock.mark()
    t0 = time.perf_counter()
    decs = eng.reslice()
    warm_s = time.perf_counter() - t0
    assert_matches(decs, expect, "warm tick vs oracle")
    admitted = sum(d.admitted for ds in decs for d in ds)
    log(f"warm tick: {warm_s:.2f} s wall, compile {clock.since(mark)}; "
        f"{admitted} admitted; oracle bit-match in all {n_domains} domains")
    serve_fn = _sharded_serve_fn(mesh, "cells", True, eng.sesm.inner)
    serve_compiles = serve_fn._cache_size()

    # 2. churned ticks through ingest, oracle-checked in every domain
    rng = np.random.default_rng(0)
    for tick in range(churn_ticks):
        churn([eng], rng, n_cells, k=max(1, n_cells // 16))
        expect = oracle(eng, domain, all_domains)
        decs = eng.reslice()
        assert_matches(decs, expect, f"churn tick {tick} vs oracle")
    log(f"{churn_ticks} churned ticks ({max(1, n_cells // 16)} departures + "
        f"arrivals each): oracle bit-match in all {n_domains} domains")

    # steady ticks: the mesh-resident session contract
    walls = []
    for _ in range(steady_ticks):
        t0 = time.perf_counter()
        eng.reslice()                  # decisions are read back to the host
        walls.append(time.perf_counter() - t0)
    sesm = eng.sesm
    recompiles = serve_fn._cache_size() - serve_compiles
    assert sesm.fresh_stacks == 1, sesm.fresh_stacks
    assert sesm.session_rebuilds == 0, sesm.session_rebuilds
    assert sesm.shard_replans == 1, sesm.shard_replans
    assert recompiles == 0, recompiles
    log(f"steady contract: fresh_stacks={sesm.fresh_stacks} "
        f"session_rebuilds={sesm.session_rebuilds} "
        f"shard_replans={sesm.shard_replans} serve recompiles={recompiles}")
    log(f"steady tick wall (smoke run, not a benchmark): median "
        f"{statistics.median(walls) * 1e3:.2f} ms over {steady_ticks} ticks")

    # 3. data plane: admitted vision jobs through the compiled resize kernel
    jobs = lambda: sum(rt.jobs_done for cell in eng.cells
                       for rt in cell.tasks.values())
    before = jobs()
    mark = clock.mark()
    t0 = time.perf_counter()
    eng.process(wall_dt=1.0)
    log(f"process tick: {jobs() - before} vision jobs in "
        f"{time.perf_counter() - t0:.2f} s wall, compile {clock.since(mark)}")
    cell = next(c for c in eng.cells if c.tasks)
    rt = next(iter(cell.tasks.values()))
    req = rt.decision.request
    batch = min(cell.max_batch,
                max(1, int(round(req.jobs_per_sec * req.n_ues))))
    frames = jax.numpy.asarray(cell.frames.frames(cell.step, batch))
    z = max(rt.decision.z, 0.02)
    got = np.asarray(resize_ops.compress_frames(frames, z, use_kernel=True))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(resize_ops.compress_frames(frames, z,
                                                    use_kernel=False))
    err = float(np.abs(got - ref).max())
    assert got.shape == ref.shape and np.isfinite(got).all()
    assert err <= RESIZE_ATOL, err
    log(f"resize kernel: batch {tuple(frames.shape)} at z={z:.3f} -> "
        f"{got.shape}, max |kernel - resize_ref| = {err:.3g} "
        f"(tolerance {RESIZE_ATOL})")

    # 4. compiled batch_round vs the jnp round and the oracle
    expect = oracle(eng, domain, all_domains)
    insts = coupled_instances(eng, eng.gather())
    mark = clock.mark()
    t0 = time.perf_counter()
    pallas = solve_greedy_batch(insts, inner="pallas")
    pallas_s = time.perf_counter() - t0
    pallas_compile = clock.since(mark)
    jnp_sols = solve_greedy_batch(insts, inner="jnp")
    for i, (p, j) in enumerate(zip(pallas, jnp_sols)):
        assert np.array_equal(p.admitted, j.admitted), f"pallas/jnp cell {i}"
        assert np.array_equal(p.alloc, j.alloc), f"pallas/jnp alloc cell {i}"
        if i in expect:
            adm, alloc = expect[i]
            assert p.admitted.tolist() == adm, f"pallas/oracle cell {i}"
            assert np.array_equal(p.alloc[p.admitted],
                                  alloc[p.admitted]), f"alloc cell {i}"
    log(f"batch_round (inner='pallas') on {len(insts)} gathered instances: "
        f"== jnp round == oracle; first solve {pallas_s:.2f} s wall, "
        f"compile {pallas_compile}")


def four_chip_phase(mesh, clock: CompileClock, *, n_cells: int = 1024,
                    n_domains: int = 128, churn_ticks: int = 2) -> None:
    from repro.core.sfesp import ShardedStack

    n_dev = int(mesh.shape["cells"])
    metro, domain = metro_engine(n_cells, n_domains, mesh)
    plain, _ = metro_engine(n_cells, n_domains, None)
    sample = sorted({0, 1, n_domains // 3, n_domains // 2,
                     2 * n_domains // 3, n_domains - 1})
    log(f"sharded metro: {n_cells} cells, {n_domains} domains on "
        f"{n_dev} devices vs a meshless engine; oracle on domains {sample}")
    rng = np.random.default_rng(0)
    for tick in range(churn_ticks + 1):
        if tick:
            churn([metro, plain], rng, n_cells, k=n_cells // 16)
        expect = oracle(metro, domain, sample)
        mark = clock.mark()
        t0 = time.perf_counter()
        md = metro.reslice()
        wall = time.perf_counter() - t0
        compiled = clock.since(mark)
        pd = plain.reslice()
        same_decisions(md, pd, f"tick {tick}: mesh vs meshless")
        assert_matches(md, expect, f"tick {tick}: mesh vs oracle")
        log(f"tick {tick}: {sum(d.admitted for ds in md for d in ds)} "
            f"admitted, mesh == meshless on all {n_cells} cells, == oracle "
            f"on sampled domains (mesh tick {wall:.2f} s wall, compile "
            f"{compiled})")

    # the session's buffers are split: block s of every per-cell table on
    # mesh device s, each block holding whole coupling groups of its own
    shd = metro.sesm._serve_session.dev
    assert isinstance(shd, ShardedStack) and shd.num_shards == n_dev
    devices = list(mesh.devices.flat)
    rows = shd.shard_rows
    for name in ("lat_ok", "alive0", "link_load", "price", "capacity",
                 "incidence", "group"):
        arr = getattr(shd, name)
        full = np.asarray(arr)
        shards = arr.addressable_shards
        assert len(shards) == n_dev, (name, len(shards))
        for sh in shards:
            s = devices.index(sh.device)
            start, stop, _ = sh.index[0].indices(full.shape[0])
            assert (start, stop) == (s * rows, (s + 1) * rows), (name, s)
            assert np.array_equal(np.asarray(sh.data), full[start:stop])
    owner = {}
    for s in range(n_dev):
        block = shd.row_of[s * rows:(s + 1) * rows]
        doms = set(domain[block[block >= 0]].tolist())
        assert doms, f"device {s} holds no coupling group"
        for d in doms:
            assert owner.setdefault(d, s) == s, f"domain {d} split"
    log(f"shards: {n_dev} devices x {rows} rows; groups per device "
        f"{shd.groups_per_shard.tolist()}; every domain on exactly one "
        "device")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: default phase; 4: only the sharded phase")
    args = parser.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this smoke run never falls back "
              "to another backend", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    try:
        from repro.kernels import resolve_interpret
        from repro.launch.compile_cache import configure_compile_cache
        from repro.launch.mesh import make_cells_mesh
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from {SRC}: {e}",
              file=sys.stderr)
        return 1
    if resolve_interpret(None):
        print("chip_smoke: kernels would run interpreted", file=sys.stderr)
        return 1
    log(f"compile cache: {configure_compile_cache()}")

    clock = CompileClock()
    t0 = time.perf_counter()
    mesh = make_cells_mesh(args.chips)
    phase = four_chip_phase if args.chips == 4 else default_phase
    phase(mesh, clock)
    log(f"phase done in {time.perf_counter() - t0:.2f} s wall, compile "
        f"{clock.secs:.2f} s in {clock.count} compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
