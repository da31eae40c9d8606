"""The phase reduction (``bench/phases.py``) on hand-made planes and on two
traces recorded on a TPU v5e:

* ``data/sweep_trace.xplane.pb``: a few solves of ``paper4res.sweep`` with
  the program's spans, recorded by
  ``python3 bench/phases.py --workload paper4res.sweep --seed 2900000011
  --seconds 0.04 --keep bench/tests/data/sweep_trace.xplane.pb``;
* ``data/small_trace.xplane.pb``: the serving loop before the program had
  spans, so its clocks cannot be aligned.
"""

import pathlib

import pytest

from bench import phases, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
SOLVE = "jit__serve_batch"


def _planes(modules, ops, spans):
    """One chip and one host thread; spans are (name, start, end[, args])."""
    host = [(n, s, e - s, *rest) for n, s, e, *rest in spans]
    return [("/device:TPU:0",
             [("XLA Modules", [(n, s, e - s) for n, s, e in modules]),
              ("XLA Ops", [(n, s, e - s) for n, s, e in ops])]),
            ("/host:CPU", [("main", host)])]


def _solve(launch, wait_end, dev, fetch=50, unpack=40, end=None):
    """Host spans of one solve launched at ``launch``, its wait ending at
    ``wait_end``, and its device execution ``dev`` = (start, end)."""
    spans = [("repro.solve.launch", launch, launch + 50, {"program": SOLVE}),
             ("repro.solve.wait", launch + 50, wait_end),
             ("repro.solve.fetch", wait_end, wait_end + fetch),
             ("repro.solve.unpack", wait_end + fetch,
              wait_end + fetch + unpack),
             ("bench.solve", launch, end or wait_end + fetch + unpack)]
    return spans, (f"{SOLVE}(42)", *dev)


def test_known_device_offset_is_recovered_within_bounds():
    true = 600                 # the device's clock runs 600 ns early
    spans, modules = [("bench.window", 0, 40000)], []
    for k, (j0, j1) in enumerate([(30, 40), (12, 95), (55, 20), (8, 61)]):
        t = 10000 * k
        dev = (t + j0 - true, t + 5000 + j0 - true)      # device clock
        sp, mod = _solve(t, t + 5000 + j0 + j1, dev)
        spans += sp
        modules.append(mod)
    ops = [(n, s, e) for n, s, e in modules]
    r = phases.reduce_phases(_planes(modules, ops, spans), 1)
    (off,) = r["clock_offset_us"]
    assert off["pairs"] == 4
    assert off["lo"] * 1e3 <= true <= off["hi"] * 1e3
    # bounds: the earliest device start and the tightest wait end
    assert off["lo"] * 1e3 == pytest.approx(true - 8)
    assert off["hi"] * 1e3 == pytest.approx(true + 20)
    assert off["applied"] * 1e3 == pytest.approx(true + 6)


def test_a_gap_is_split_piecewise_across_phases():
    spans = [("bench.window", 0, 1000)]
    sp1, m1 = _solve(0, 300, (20, 280), end=500)
    sp2, m2 = _solve(500, 900, (520, 880), unpack=40, end=1000)
    planes = _planes([m1, m2], [m1, m2], spans + sp1 + sp2)
    r = phases.reduce_phases(planes, 1)
    assert r["clock_offset_us"][0]["applied"] == pytest.approx(0.0)
    ns = {k: round(v * 1e9) for k, v in r["idle_by_span"].items()}
    # [0,20) launch; [280,520): wait 20, fetch 50, unpack 40, solve 110,
    # launch 20; [880,1000): wait 20, fetch 50, unpack 40, solve 10
    assert ns == {"repro.solve.launch": 40, "repro.solve.wait": 40,
                  "repro.solve.fetch": 100, "repro.solve.unpack": 80,
                  "bench.solve": 120}
    assert r["idle_gaps"][0] == ["repro.solve.wait", pytest.approx(240e-9)]
    assert r["programs"][SOLVE] == [2, pytest.approx(620e-9)]
    m = phases.phase_metrics(r, {"solves": 2, "rounds": 32})
    assert m["readback_idle_ms"] == pytest.approx(140e-9 / 2 * 1e3)
    assert m["launch_idle_ms"] == pytest.approx(20e-9 * 1e3)
    assert m["unpack_idle_ms"] == pytest.approx(40e-9 * 1e3)
    assert m["round_us"] == pytest.approx(620e-9 / 32 * 1e6)
    assert m["gc_idle_pct"] == 0.0


def test_idle_sums_to_window_less_aligned_busy():
    spans = [("bench.window", 0, 30000), ("repro.gc.gen2", 9000, 9400)]
    modules, ops = [], []
    for k in range(3):
        t = 10000 * k + 100
        dev = (t + 40 - 700, t + 7000 - 700)
        sp, mod = _solve(t, t + 7100, dev)
        spans += sp
        modules.append(mod)
        ops += [("op", dev[0], dev[0] + 3000), ("op", dev[0] + 3100, dev[1])]
    r = phases.reduce_phases(_planes(modules, ops, spans), 1)
    total = sum(r["idle_by_span"].values())
    assert total == pytest.approx(r["window_s"] - r["busy_s_aligned"],
                                  rel=1e-9)
    assert r["idle_by_span"]["repro.gc.gen2"] == pytest.approx(400e-9)
    assert phases.phase_metrics(r, {"solves": 3})["gc_idle_pct"] == \
        pytest.approx(100 * 400 / 30000)


def test_contradicting_clocks_publish_no_phase():
    spans = [("bench.window", -100, 1000)]
    sp, mod = _solve(0, 300, (-10, 400))      # started before its launch
    r = phases.reduce_phases(_planes([mod], [mod], spans + sp), 1)
    off = r["clock_offset_us"][0]
    assert off["lo"] > off["hi"] and off["applied"] is None
    m = phases.phase_metrics(r, {"solves": 1, "rounds": 16})
    assert m["launch_idle_ms"] is None and m["readback_idle_ms"] is None
    assert m["unpack_idle_ms"] is None and m["gc_idle_pct"] is None
    assert m["round_us"] is not None          # device time needs no host


def test_recorded_sweep_trace_aligns():
    planes = phases.read_planes(str(DATA / "sweep_trace.xplane.pb"))
    r = phases.reduce_phases(planes, 1)
    (off,) = r["clock_offset_us"]
    assert off["pairs"] >= 3 and off["lo"] <= off["hi"]
    shift = off["applied"] * 1e3
    spans = phases._host_spans(planes)
    launches = sorted((s for s in spans if s[0] == "repro.solve.launch"),
                      key=lambda s: s[1])
    waits = sorted((s for s in spans if s[0] == "repro.solve.wait"),
                   key=lambda s: s[1])
    execs = sorted((s, e) for n, s, e in
                   phases._device_planes(planes)[0]["XLA Modules"]
                   if n.startswith(SOLVE + "("))
    assert len(execs) == len(launches) == len(waits)
    for (s, e), launch, wait in zip(execs, launches, waits):
        assert launch[1] <= s + shift and e + shift <= wait[2]
    total = sum(r["idle_by_span"].values())
    assert total == pytest.approx(r["window_s"] - r["busy_s_aligned"],
                                  rel=1e-9)
    assert abs(r["busy_s_aligned"] - r["busy_s"]) <= 2 * abs(shift) * 1e-9
    assert any(n.startswith("repro.solve.") for n, _ in r["idle_gaps"])
    assert r["programs"][SOLVE][0] == len(execs)


def test_recorded_serving_trace_has_no_alignment():
    planes = phases.read_planes(str(DATA / "small_trace.xplane.pb"))
    r = phases.reduce_phases(planes, 1)
    assert r["clock_offset_us"] == [{"lo": None, "hi": None,
                                     "applied": None, "pairs": 0}]
    old = trace.reduce_planes(trace.read_planes(
        str(DATA / "small_trace.xplane.pb")), 1)
    for key in ("window_s", "busy_s", "busy_s_per_chip", "chips_traced",
                "device_ops", "spans"):
        assert r[key] == old[key], key
    assert {"jit__scatter_rows", "jit_body"} <= set(r["programs"])
    assert phases.phase_metrics(r, {"solves": 11})["readback_idle_ms"] \
        is None
