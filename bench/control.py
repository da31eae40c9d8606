"""Readings that set the limits of ``correct``; not part of a benchmark run.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: one run of the cell as ``run.py`` makes it,
printing the program's compared numbers, and the readings of the controls
put in the program's place on the same sampled inputs (``bench/check.py``):
the reference with its gradient in bfloat16, and the reference with one of
the configuration's guarantees broken. One JSON line per seed.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    log = lambda msg: print(f"[control] {msg}", file=sys.stderr,  # noqa: E731
                            flush=True)
    cell = harness.load_cell(args.workload)
    harness.configure_jax(ROOT / ".jax_cache")
    devices, why = harness.accelerators(cell.chips)
    if devices is None:
        log(why)
        return 1
    t0 = T0
    for seed in args.seeds:
        out = harness.run(cell, seed, args.seconds, False, t0, devices, log,
                          control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "ties": {k: out["info"][k] for k in
                                   ("ties_followed", "tie_gap_max")},
                          "control": out["control"],
                          "metrics": out["metrics"]}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
