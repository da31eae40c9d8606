"""The trace reduction and the serving tick's readers, on hand-made planes
and on a small trace recorded on a TPU v5e (a few ticks of the 256-cell
serving loop, ``data/small_trace.xplane.pb``)."""

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


TICK_READERS = ["ingest_ms.tick", "dispatch_ms.tick", "commit_ms.tick",
                "device_ms.tick", "device_idle_pct.tick"]


@pytest.mark.parametrize("name", TICK_READERS)
def test_tick_readers_on_recorded_trace(name):
    from bench import harness

    r = trace.reduce_planes(trace.read_planes(
        str(DATA / "small_trace.xplane.pb")), 1)
    ticks = r["spans"]["bench.dispatch"][0]
    reading = harness.Reading("serving", r, {"ticks": ticks}, {})
    value = harness.load_reader(name)(reading)
    if name.startswith("device_idle"):
        assert value == pytest.approx(100 * (1 - r["busy_s"] / r["window_s"]))
    elif name.startswith("device_ms"):
        assert value == pytest.approx(r["busy_s"] * 1e3 / ticks)
    else:
        span = "bench." + name.split("_")[0]
        assert value == pytest.approx(r["spans"][span][1] * 1e3 / ticks)
    assert 0 < value < 100
    # a window without the span, or without ticks, reads nothing per tick
    empty = harness.Reading("serving", dict(r, spans={}), {"ticks": 0}, {})
    if not name.startswith("device_idle"):
        assert harness.load_reader(name)(empty) is None
