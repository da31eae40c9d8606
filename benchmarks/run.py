"""Benchmark harness entry: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmark contract) and writes
every row to ``BENCH_sweep.json`` at the REPO ROOT (per-benchmark µs + typed
extras such as speedups and B/Tmax/A) so the perf trajectory is tracked
across PRs instead of lost in stdout — anchoring to the repo root rather
than the cwd keeps the CI artifact upload (and the regression gate's
baseline diff) working for out-of-tree invocations.
Run: PYTHONPATH=src python -m benchmarks.run [section ...]
"""

import pathlib
import sys

from repro.launch.compile_cache import configure_compile_cache

from . import (common, fig2_accuracy, fig2_latency, fig6_numerical,
               fig7_colosseum, kernel_perf, roofline, solver_perf, sweep_perf)

SECTIONS = {
    "fig2_accuracy": fig2_accuracy.main,     # paper Fig. 2-left
    "fig2_latency": fig2_latency.main,       # paper Fig. 2-right
    "fig6": fig6_numerical.main,             # paper Fig. 6(a)(b)
    "fig7": fig7_colosseum.main,             # paper Fig. 7
    "solver": solver_perf.main,              # beyond-paper solver scaling
    "sweep": sweep_perf.main,                # batched sweep engine vs seq
    "kernels": kernel_perf.main,             # Pallas kernel micro-bench
    "roofline": roofline.main,               # §Roofline table from dry-run
}


def main() -> None:
    picks = sys.argv[1:] or list(SECTIONS)
    configure_compile_cache()
    print("name,us_per_call,derived")
    for name in picks:
        SECTIONS[name]()
    out = pathlib.Path(__file__).resolve().parent.parent / "BENCH_sweep.json"
    common.dump_results(str(out))
    print(f"# wrote {out} ({len(common.RESULTS)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main()
