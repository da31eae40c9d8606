"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine's own TPU chips. It builds
the cell's deployment, warms up the shapes the cell's traffic uses (set-up),
measures ``--seconds`` of traffic, compares the decisions the window produced
with the plain reference (``bench/reference.py``), and prints the result as
the last line of standard output: the end-to-end metrics with ``--trace 0``,
the per-layer metrics read from a profiler trace with ``--trace 1``. The
numbers compared are the last lines of standard error and the result's last
key, ``checks``. Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        p.error("--seed must be >= 0 and --seconds > 0")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  (the system under test)
        from bench import harness
    except ImportError as e:
        log(f"cannot import the system under test or the harness: {e}")
        return 1
    cell = harness.load_cell(args.workload)
    harness.configure_jax(CACHE_DIR)
    devices, why = harness.accelerators(cell.chips)
    if devices is None:
        log(why)
        return 1
    from repro.kernels import resolve_interpret
    if resolve_interpret(None):
        log("the Pallas kernels would run interpreted on this machine")
        return 1
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace), T0,
                      devices, log)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
