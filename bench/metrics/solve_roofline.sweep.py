"""Device solve (kernel): the least time of one batch solve on this chip
(bench/roofline.py) over the measured device time per solve, %."""
from bench import roofline
from bench.layer import device_ms_per


def read(r):
    ms = device_ms_per(r, "solves")
    if ms is None:
        return None
    floor, _ = roofline.floor_s(r.counters["work"], r.peaks)
    return 100.0 * floor / (ms * 1e-3)
