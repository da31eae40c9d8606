"""Device solve: device busy time in the trace per solved batch, ms."""
from bench.layer import device_ms_per


def read(r):
    return device_ms_per(r, "solves")
