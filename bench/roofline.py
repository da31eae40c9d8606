"""Least work of the sweep solve, whatever implements it.

One ``solve_device_batch`` call on a stacked batch of B instances, T task
rows each, A allocations of m resources:

* bytes: the inputs read once in their smallest form (each task's
  latency-feasible allocations as packed bits, B x T x ceil(A/32) words;
  the candidate flags, one bit per task; prices and capacities; the grid),
  plus the outputs written once (the packed decision rows and the residual
  capacities);
* operations: every admission round scores every allocation of every
  instance (capacity check, primal gradient, masked maximum: 7m + 2
  operations) and scans every task's packed row once. The rounds are the
  largest admitted count plus one, the round that finds nothing left.

The floor is the larger of operations over the chip's peak operation rate
and bytes over its memory bandwidth. The peak rate taken is the bf16 matrix
peak, above anything the solve's float32 vector work can reach, so the
operations bound is never too high and the share cannot pass 100 %.
"""

from __future__ import annotations

import numpy as np


def ops_per_allocation(m: int) -> int:
    return 7 * m + 2


def sweep_work(stacked, admitted_by_batch) -> dict:
    """Bytes and operations of one solve, averaged over the run's batches
    (all stacked alike); ``admitted_by_batch`` are the (B, T) decisions."""
    B, T = stacked.batch_size, stacked.max_tasks
    A, m = stacked.grid.shape
    W, WT = -(-A // 32), -(-T // 32)
    bytes_in = B * T * W * 4 + -(-B * T // 8) + 2 * B * m * 4 + A * m * 4
    bytes_out = B * (WT + T) * 4 + B * m * 4
    rounds = float(np.mean([int(a.sum(axis=1).max()) + 1
                            for a in admitted_by_batch]))
    ops = rounds * B * (A * ops_per_allocation(m) + T * W)
    return {"bytes": float(bytes_in + bytes_out), "ops": float(ops),
            "rounds": rounds, "B": B, "T": T, "A": A, "m": m}


def floor_s(work: dict, peaks: dict) -> tuple[float, str]:
    """Least time of one solve on the chip, and which bound sets it."""
    t_ops = work["ops"] / peaks["flops_bf16"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
