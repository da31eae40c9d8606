"""Pallas TPU kernels: fused feasibility-masked reductions for the greedy.

The SF-ESP greedy re-evaluates, every admission round, the best allocation per
candidate task over the enumerated grid — a (T × A) masked argmax against a
shared per-allocation score vector. At production scale (T = 4096 tasks,
A = 16k allocations) the score matrix is 256 MB/round in f32; materializing it
in HBM each of up to T rounds is the solver's dominant memory-bandwidth cost.

Two kernels:

* :func:`masked_argmax` — the single-instance inner step (per-task row
  max/argmax) used by ``solve_greedy_jax(inner="pallas")``.
* :func:`batch_round` — ONE fused round of the batched sweep engine
  (``solve_greedy_batch(inner="pallas")``): cap-feasibility, primal-gradient
  scoring, the global-max ``V`` reduction and the ``tau``/``best_a`` selection
  over bit-packed (B, T, A) tiles, so no per-round (T, A)-sized intermediate
  ever leaves VMEM.

TPU adaptation (vs. a CUDA warp-shuffle argmax): tile (T, A) into
(BT × BA) VMEM blocks with BA a multiple of 128 lanes, keep a running
(max, argmax) carry in the output block across the innermost grid dimension,
and do block-local VPU reductions. Nothing but the inputs and small
per-row / per-instance outputs ever touch HBM.

Mosaic layout rules the kernels follow (checked by compiling for a v5e chip
in ``tests/test_tpu_compile.py``):

* every block's last two dims are (8, 128)-divisible or span the array, so
  per-row vectors travel as (rows, 1) columns and per-instance scalars as
  (1, m, 1) slabs of a (B, m, 1) view — never as rank-1 blocks;
* outputs are lane-dense 2-D blocks: each result is broadcast across a
  128-lane row and the wrapper reads lane 0;
* argmaxes are "smallest index attaining the max" min-reductions over an
  iota (first-max ordering, like ``jnp.argmax``);
* the carried grid axis is declared ``"arbitrary"`` (sequential) and the
  other one ``"parallel"``, so the accumulation revisits its output block in
  order on every backend that honours the semantics.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

__all__ = ["masked_argmax", "batch_round"]

NEG_INF = float("-inf")
# mirrors repro.core.greedy._EPS_DEN (primal-gradient denominator clamp)
_EPS_DEN = 1e-9
_BIG = 2**31 - 1          # int32 sentinel for min-index reductions
_LANES = 128
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _first_max(score, idx, axis):
    """(max, smallest ``idx`` attaining it) along ``axis``, keepdims.

    All -inf rows give the smallest index, like ``jnp.argmax``."""
    best = score.max(axis=axis, keepdims=True)
    arg = jnp.where(score == best, idx, _BIG).min(axis=axis, keepdims=True)
    return best, arg


def _kernel(sel_ref, lat_ref, alive_ref, g_ref, idx_ref, *, ba: int):
    ai = pl.program_id(1)

    @pl.when(ai == 0)
    def _init():
        g_ref[...] = jnp.full_like(g_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    # sel carries -inf where the allocation does not fit (folded by the
    # wrapper); alive is a (BT, 1) column of 0 / -inf penalties
    lat = lat_ref[...].astype(jnp.int32) != 0                    # (BT, BA)
    score = jnp.where(lat, sel_ref[...], NEG_INF)
    score = score + alive_ref[...]
    cols = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1) + ai * ba
    loc_max, loc_arg = _first_max(score, cols, axis=1)            # (BT, 1)

    # strict > keeps the FIRST global maximum, matching jnp.argmax ordering.
    better = loc_max > g_ref[...]                                 # (BT, 128)
    g_ref[...] = jnp.where(better, loc_max, g_ref[...])
    idx_ref[...] = jnp.where(better, loc_arg, idx_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_a", "interpret"))
def masked_argmax(sel, lat_ok, cap_ok, alive, *, block_t: int = 256,
                  block_a: int = 512, interpret: bool | None = None):
    """Fused masked row max/argmax. See ``ref.masked_argmax_ref`` for
    semantics. The latency mask is int8 (0/1) on the wire.

    Args:
      sel: (A,) f32 — shared per-allocation score (PG or -cost).
      lat_ok: (T, A) bool/int8 — per-task latency feasibility (static).
      cap_ok: (A,) bool/int8 — allocation fits remaining capacity (per round).
      alive: (T,) bool/int8 — candidate mask (per round).
      block_t, block_a: tile sizes; compiled (TPU) mode needs ``block_t`` a
        multiple of 32 (int8 sublane tile) or >= T, and ``block_a`` a
        multiple of 128 or >= A.
      interpret: None → interpreter unless the default backend is the TPU;
        explicit bools force a mode.
    """
    interpret = resolve_interpret(interpret)
    t, a = lat_ok.shape
    bt = min(block_t, max(t, 1))
    ba = min(block_a, max(a, 1))
    tp = -(-t // bt) * bt
    ap = -(-a // ba) * ba

    sel_p = jnp.full((1, ap), NEG_INF, jnp.float32).at[0, :a].set(
        jnp.where(cap_ok, sel.astype(jnp.float32), NEG_INF))
    lat_p = jnp.zeros((tp, ap), jnp.int8).at[:t, :a].set(
        lat_ok.astype(jnp.int8))
    alive_p = jnp.full((tp, 1), NEG_INF, jnp.float32).at[:t, 0].set(
        jnp.where(alive, 0.0, NEG_INF))

    g, idx = pl.pallas_call(
        functools.partial(_kernel, ba=ba),
        grid=(tp // bt, ap // ba),
        in_specs=[
            pl.BlockSpec((1, ba), lambda ti, ai: (0, ai)),
            pl.BlockSpec((bt, ba), lambda ti, ai: (ti, ai)),
            pl.BlockSpec((bt, 1), lambda ti, ai: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, _LANES), lambda ti, ai: (ti, 0)),
            pl.BlockSpec((bt, _LANES), lambda ti, ai: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((tp, _LANES), jnp.int32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(sel_p, lat_p, alive_p)
    return g[:t, 0], idx[:t, 0]


# ---------------------------------------------------------------------------
# Fused batched admission round (sweep engine inner step)
# ---------------------------------------------------------------------------

def _round_kernel(bits_ref, alive_ref, grid_ref, price_ref, cap_ref, occ_ref,
                  v_ref, tau_ref, a_ref, *, bt: int, m: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        v_ref[...] = jnp.full_like(v_ref, NEG_INF)
        tau_ref[...] = jnp.zeros_like(tau_ref)
        a_ref[...] = jnp.zeros_like(a_ref)

    # per-instance pool scalars as (1, 1) values; every sum below runs in
    # resource order k = 0..m-1, the order of greedy.primal_gradient's
    # reductions, so the f32 gradients match the jnp round bit for bit
    price = [price_ref[0, j:j + 1, :] for j in range(m)]
    cap = [cap_ref[0, j:j + 1, :] for j in range(m)]
    occ = [occ_ref[0, j:j + 1, :] for j in range(m)]
    o_sq = occ[0] * occ[0]
    any_occ = occ[0] > 0.0
    for j in range(1, m):
        o_sq = o_sq + occ[j] * occ[j]
        any_occ = any_occ | (occ[j] > 0.0)
    o_norm = jnp.sqrt(o_sq)
    sqrt_m = float(np.sqrt(np.float32(m), dtype=np.float32))

    # the latency tile (BT, W) u32, dead rows zeroed by the alive mask;
    # bit k of word w is allocation 32·w + k (greedy._pack_bits layout)
    bits = bits_ref[0] & alive_ref[0]                        # (BT, W)
    w = bits.shape[1]
    word = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    row_max = jnp.full((bt, 1), NEG_INF, jnp.float32)
    row_arg = jnp.zeros((bt, 1), jnp.int32)
    # one pass per bit plane k: allocations {32·w + k}, whose grid rows the
    # wrapper laid out as grid_ref[:, k, :]. Padded lanes carry grid=+inf so
    # they are never cap-feasible (their latency bits are zero anyway).
    for k in range(32):
        g = [grid_ref[j, k:k + 1, :] for j in range(m)]      # m × (1, W)
        cap_ok = g[0] <= cap[0] - occ[0] + 1e-9
        value = price[0] * (cap[0] - g[0])
        norm_use = g[0] / cap[0]
        weighted = g[0] * (occ[0] / cap[0])
        for j in range(1, m):
            cap_ok = cap_ok & (g[j] <= cap[j] - occ[j] + 1e-9)
            value = value + price[j] * (cap[j] - g[j])
            norm_use = norm_use + g[j] / cap[j]
            weighted = weighted + g[j] * (occ[j] / cap[j])
        pg_uni = value * sqrt_m / jnp.maximum(norm_use, _EPS_DEN)
        pg_occ = value * o_norm / jnp.maximum(weighted, _EPS_DEN)
        pg = jnp.where(cap_ok, jnp.where(any_occ, pg_occ, pg_uni),
                       NEG_INF)                              # (1, W)
        lat = ((bits >> k) & 1) != 0                         # (BT, W)
        score = jnp.where(lat, pg, NEG_INF)
        m_k, i_k = _first_max(score, word * 32 + k, axis=1)  # (BT, 1)
        # first-max across planes: a larger value wins, a tie keeps the
        # smaller allocation index (planes interleave the columns)
        row_arg = jnp.where(m_k > row_max, i_k,
                            jnp.where(m_k == row_max,
                                      jnp.minimum(row_arg, i_k), row_arg))
        row_max = jnp.maximum(row_max, m_k)

    rows = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    blk_v, t_loc = _first_max(row_max, rows, axis=0)         # (1, 1)
    a_loc = jnp.where(rows == t_loc, row_arg, _BIG).min(axis=0, keepdims=True)

    # strict > keeps the FIRST T-block attaining the global max — together
    # with the in-block first-max reductions this reproduces the sequential
    # first-max tie-breaking of the jnp round bit-for-bit.
    better = blk_v > v_ref[0]                                # (1, 128)
    v_ref[0] = jnp.where(better, blk_v, v_ref[0])
    tau_ref[0] = jnp.where(better, ti * bt + t_loc, tau_ref[0])
    a_ref[0] = jnp.where(better, a_loc, a_ref[0])


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def batch_round(lat_bits, alive, grid, price, cap, occupied, *,
                block_t: int = 128, interpret: bool | None = None):
    """One fused admission round for a stacked batch (flexible mode).

    Computes, per instance ``b``, the full decision of one
    ``greedy._greedy_jax_batch`` round in a single ``pallas_call`` over
    (B, T-blocks) tiles: the global best feasible gradient ``V``, the first
    alive task attaining it, and that task's first-max allocation. The
    per-plane score tiles and the per-lane gradient live only in VMEM; HBM
    traffic per round is the packed latency bits plus O(B·m) pool state.

    See ``ref.batch_round_ref`` for the dense oracle.

    Args:
      lat_bits: (B, T, W) uint32 — bit-packed static latency feasibility
        (W = ceil(A / 32), ``greedy._pack_bits`` layout).
      alive: (B, T) bool/int8 — per-round candidate mask.
      grid: (A, m) f32 — shared allocation grid.
      price, cap, occupied: (B, m) f32 — per-instance pool state.
      block_t: T tile; compiled (TPU) mode needs a multiple of 8 or >= T.

    Returns:
      v: (B,) f32 — best feasible gradient (-inf ⇒ nothing admissible),
      tau: (B,) i32 — first alive task whose feasible set attains ``v``,
      best_a: (B,) i32 — ``tau``'s first-max allocation index.
    """
    interpret = resolve_interpret(interpret)
    b, t, w = lat_bits.shape
    a, m = grid.shape
    ap = w * 32
    bt = min(block_t, max(t, 1))
    tp = -(-t // bt) * bt

    bits_p = jnp.zeros((b, tp, w), jnp.uint32).at[:, :t].set(lat_bits)
    # alive as an all-ones / all-zeros word mask, one (T, 1) column per cell
    alive_p = jnp.zeros((b, tp, 1), jnp.uint32).at[:, :t, 0].set(
        jnp.where(alive, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)))
    # pad lanes beyond A with +inf so they can never be cap-feasible, then
    # lay the grid out by bit plane: grid3[j, k, w] = grid[32·w + k, j]
    grid_p = jnp.full((m, ap), jnp.inf, jnp.float32).at[:, :a].set(
        grid.T.astype(jnp.float32))
    grid3 = grid_p.reshape(m, w, 32).transpose(0, 2, 1)
    pool = lambda x: jnp.asarray(x, jnp.float32).reshape(b, m, 1)
    inst = lambda bi, ti: (bi, 0, 0)

    v, tau, best_a = pl.pallas_call(
        functools.partial(_round_kernel, bt=bt, m=m),
        grid=(b, tp // bt),
        in_specs=[
            pl.BlockSpec((1, bt, w), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, bt, 1), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((m, 32, w), lambda bi, ti: (0, 0, 0)),
            pl.BlockSpec((1, m, 1), inst),
            pl.BlockSpec((1, m, 1), inst),
            pl.BlockSpec((1, m, 1), inst),
        ],
        out_specs=[pl.BlockSpec((1, 1, _LANES), inst)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, _LANES), jnp.int32),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(bits_p, alive_p, grid3, pool(price), pool(cap), pool(occupied))
    return v[:, 0, 0], tau[:, 0, 0], best_a[:, 0, 0]
