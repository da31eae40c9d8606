"""The trace reduction, on hand-made planes and on a small trace recorded on
a TPU v5e (a few ticks of the 256-cell serving loop,
``data/small_trace.xplane.pb``)."""

import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _planes(ops, spans):
    return [("/device:TPU:0", [("XLA Ops", ops), ("Steps", [("s", 0, 99)])]),
            ("/host:CPU", [("main", spans)])]


def test_busy_is_the_union_clipped_to_the_window():
    ops = [("a", 10, 20), ("b", 20, 10), ("c", 100, 50), ("d", 190, 20)]
    spans = [("bench.window", 0, 200), ("bench.dispatch", 0, 60),
             ("bench.commit", 60, 140)]
    r = trace.reduce_planes(_planes(ops, spans), 1)
    assert r["window_s"] == pytest.approx(200e-9)
    # [10, 30) and [100, 150) and [190, 200): 20 + 50 + 10
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["spans"]["bench.dispatch"] == [1, pytest.approx(60e-9)]
    assert "bench.window" not in r["spans"]
    gaps = dict((round(s * 1e9), n) for n, s in r["idle_gaps"])
    assert gaps == {70: "bench.dispatch", 40: "bench.commit",
                    10: "bench.dispatch"}
    assert r["device_ops"][0][0] == "c"


def test_busy_averages_over_the_chips_used():
    planes = _planes([("a", 0, 100)], [("bench.window", 0, 100)])
    planes.append(("/device:TPU:1", [("XLA Ops", [("a", 0, 50)])]))
    assert trace.reduce_planes(planes, 2)["busy_s"] == pytest.approx(75e-9)
    assert trace.reduce_planes(planes, 1)["busy_s"] == pytest.approx(100e-9)


@pytest.mark.skipif(not (DATA / "small_trace.xplane.pb").exists(),
                    reason="no recorded trace")
def test_recorded_trace():
    planes = trace.read_planes(str(DATA / "small_trace.xplane.pb"))
    r = trace.reduce_planes(planes, 1)
    assert r["chips_traced"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    for span in ("bench.ingest", "bench.dispatch", "bench.commit"):
        assert r["spans"][span][0] > 0
    assert r["device_ops"] and r["idle_gaps"]
