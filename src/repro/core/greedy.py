"""Greedy SF-ESP solver — paper Algorithm 1 (primal effective gradient).

Two interchangeable backends:

* :func:`solve_greedy` — readable numpy reference, line-for-line close to
  Alg. 1. Used as oracle by tests and by the small-scale benchmarks.
* :func:`solve_greedy_jax` — fully jittable ``lax.while_loop`` implementation
  that runs the admission loop on device. Its inner hot op (feasibility +
  primal-gradient + per-task masked argmax over the allocation grid) can be
  served by the Pallas kernel in ``repro.kernels.pg`` (``inner="pallas"``).

Both support the four (semantic × flexible) quadrants so the paper's SI-EDGE /
MinRes-SEM / FlexRes-N-SEM baselines are the same code path with flags — the
paper's framing is that SEM-O-RAN = semantics + flexibility on top of the same
greedy skeleton.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..trace import span

from . import semantics
from .sfesp import (DeviceStack, ShardedStack, device_stack,
                    device_stack_sharded, lexicographic_cost, next_pow2,
                    objective_value, stack_instances)
from .types import ProblemInstance, Solution, StackedInstances

__all__ = ["primal_gradient", "solve_greedy", "solve_greedy_jax",
           "solve_greedy_batch", "solve_greedy_sharded", "solve_greedy_many",
           "solve", "solve_device_batch", "dispatch_device_batch",
           "unpack_device_batch", "solve_sharded_batch",
           "dispatch_sharded_batch", "unpack_sharded_batch",
           "clear_sharded_caches", "lexicographic_cost"]

_EPS_DEN = 1e-9


# ---------------------------------------------------------------------------
# Primal effective gradient (paper lines 21-25, after Toyoda 1975)
# ---------------------------------------------------------------------------

def primal_gradient(grid, price, capacity, occupied, xp=np):
    """PG(s) for every allocation s in ``grid`` (A, m) → (A,).

    Line 23 (no resources occupied yet — penalize usage uniformly):
        PG = Σ_k p_k (S_k - s_k) · m^{1/2} / Σ_k (s_k / S_k)
    Line 25 (penalize according to occupancy o):
        PG = Σ_k p_k (S_k - s_k) · ‖o‖₂ / Σ_k (s_k·o_k / S_k)

    The occupied-branch denominator is clamped to a tiny ε: an allocation that
    touches only currently-unused resources has denominator 0 — i.e. it is
    maximally attractive (Toyoda's balancing intent); the clamp keeps it finite
    while preserving the ordering by value.
    """
    grid = xp.asarray(grid)
    m = grid.shape[-1]
    value = (price * (capacity - grid)).sum(axis=-1)          # Σ p_k (S_k-s_k)
    norm_use = (grid / capacity).sum(axis=-1)                 # Σ s_k/S_k
    pg_uniform = value * xp.sqrt(float(m)) / xp.maximum(norm_use, _EPS_DEN)
    o_norm = xp.sqrt((occupied * occupied).sum())
    weighted = (grid * (occupied / capacity)).sum(axis=-1)    # Σ s_k o_k / S_k
    pg_occ = value * o_norm / xp.maximum(weighted, _EPS_DEN)
    return xp.where((occupied > 0).any(), pg_occ, pg_uniform)


# ---------------------------------------------------------------------------
# numpy reference (Alg. 1 structure)
# ---------------------------------------------------------------------------

def _select_tables(inst: ProblemInstance, semantic: bool):
    if semantic:
        return inst.lat, inst.z_star_idx
    return inst.lat_agnostic, inst.z_star_idx_agnostic


def solve_greedy(inst: ProblemInstance, *, semantic: bool = True,
                 flexible: bool = True) -> Solution:
    """Numpy reference of Alg. 1.

    ``flexible=False`` replaces the PG-maximizing allocation of Eq. (3) with
    the minimum-cost feasible allocation (MinRes-* behaviour); task priority is
    still the gradient evaluated at that fixed allocation.
    """
    lat, z_idx = _select_tables(inst, semantic)
    T, A = lat.shape
    S, p = inst.pool.capacity, inst.pool.price
    grid = inst.grid
    max_lat = inst.tasks.max_latency

    lat_ok = lat <= max_lat[:, None]                       # (T, A) static
    admitted = np.zeros(T, bool)
    alloc_idx = np.full(T, -1, np.int64)
    # line 1/7: candidates = tasks whose accuracy bound is reachable (Eq. 2)
    alive = (z_idx >= 0) & lat_ok.any(axis=1)
    occupied = np.zeros_like(S)
    cost = lexicographic_cost(grid)                        # for MinRes mode

    while alive.any():                                      # lines 8-19
        remaining = S - occupied
        cap_ok = (grid <= remaining + 1e-9).all(axis=1)     # s ≤ S - o
        pg = primal_gradient(grid, p, S, occupied)          # (A,)
        feas = lat_ok & cap_ok[None, :] & alive[:, None]
        has = feas.any(axis=1)
        alive &= has                                        # line 15: discard
        if not alive.any():
            break
        if flexible:                                        # Eq. (3)
            score = np.where(feas, pg[None, :], -np.inf)
        else:                                               # min-cost alloc
            score = np.where(feas, -cost[None, :], -np.inf)
        best_a = score.argmax(axis=1)                       # per-task s*
        G = pg[best_a]                                      # task gradient
        G = np.where(alive, G, -np.inf)
        tau = int(G.argmax())                               # line 16
        admitted[tau] = True                                # line 17
        alloc_idx[tau] = best_a[tau]
        occupied = occupied + grid[best_a[tau]]
        alive[tau] = False                                  # line 18

    return _pack_solution(inst, semantic, admitted, alloc_idx, z_idx)


def _pack_solution(inst, semantic, admitted, alloc_idx, z_idx) -> Solution:
    grid = inst.grid
    T = inst.num_tasks
    alloc = np.zeros((T, inst.m))
    alloc[admitted] = grid[alloc_idx[admitted]]
    z = np.where(admitted & (z_idx >= 0),
                 inst.z_grid[np.clip(z_idx, 0, None)], 1.0)
    # true satisfaction: re-check accuracy on the task's OWN curve (agnostic
    # algorithms may have picked a z that the real class cannot tolerate),
    # under the model that defined the instance (drifted curves included).
    a_true = semantics.resolve(inst.semantics).accuracy(inst.tasks.app_idx, z)
    lat_tbl = inst.lat if semantic else inst.lat_agnostic
    l_val = np.where(admitted & (alloc_idx >= 0),
                     lat_tbl[np.arange(T), np.clip(alloc_idx, 0, None)], np.inf)
    satisfied = admitted & (a_true + 1e-9 >= inst.tasks.min_accuracy) \
        & (l_val <= inst.tasks.max_latency + 1e-9)
    return Solution(
        admitted=admitted, alloc=alloc, z=z,
        objective=objective_value(inst, admitted, alloc),
        satisfied=satisfied,
    )


# ---------------------------------------------------------------------------
# JAX backend (jit + lax.while_loop; optional Pallas inner step)
# ---------------------------------------------------------------------------

def _inner_jnp(grid, price, cap, occupied, remaining, lat_ok, alive, cost,
               flexible: bool):
    """One admission round: per-task best allocation + gradient.

    Returns (G (T,), best_a (T,), has_feasible (T,)).
    """
    cap_ok = (grid <= remaining[None, :] + 1e-9).all(axis=1)      # (A,)
    pg = primal_gradient(grid, price, cap, occupied, xp=jnp)      # (A,)
    feas = lat_ok & cap_ok[None, :] & alive[:, None]              # (T, A)
    sel = pg if flexible else -cost
    score = jnp.where(feas, sel[None, :], -jnp.inf)
    best_a = score.argmax(axis=1)
    has = feas.any(axis=1)
    G = jnp.where(has, pg[best_a], -jnp.inf)
    return G, best_a, has


def _admit(admitted, alloc_idx, alive, tau, best, admit, keep):
    """Admit task ``tau`` at allocation ``best`` where ``admit`` holds.

    The per-round update of the (..., T) loop state as one lane-dense masked
    elementwise pass: ``hit`` is the one-hot row of the admitted task, so no
    indexed scatter (which the TPU applies one update at a time) is left in
    the loop. ``tau``/``best``/``admit``/``keep`` carry the leading batch
    shape, scalars for a single instance. The admitted task leaves the
    candidate set, and an instance whose ``keep`` is False retires whole.
    Returns ``(admitted, alloc_idx, alive, hit)``.
    """
    hit = (jnp.arange(admitted.shape[-1]) == tau[..., None]) \
        & admit[..., None]
    admitted = admitted | hit
    alloc_idx = jnp.where(hit, best[..., None].astype(alloc_idx.dtype),
                          alloc_idx)
    alive = alive & ~hit & keep[..., None]
    return admitted, alloc_idx, alive, hit


def _round(state, lat_ok, grid, price, cap, cost, flexible: bool, inner_fn):
    """One admission round (Alg. 1 lines 8-19) as a masked state update.

    Safe as a no-op: when no candidate is feasible, ``admit_now`` is False and
    every update degenerates to identity, so besides the single-instance
    while-loop it can run vmapped in the batched MinRes path, where finished
    instances keep executing masked rounds until the whole batch converges.
    """
    admitted, alloc_idx, occupied, alive = state
    remaining = cap - occupied
    if inner_fn is not None:
        G, best_a, has = inner_fn(grid, price, cap, occupied, remaining,
                                  lat_ok, alive, cost)
    else:
        G, best_a, has = _inner_jnp(grid, price, cap, occupied, remaining,
                                    lat_ok, alive, cost, flexible)
    alive = alive & has                                  # drop infeasible
    G = jnp.where(alive, G, -jnp.inf)
    tau = jnp.argmax(G)
    admit_now = jnp.any(alive)
    admitted, alloc_idx, alive, _ = _admit(admitted, alloc_idx, alive, tau,
                                           best_a[tau], admit_now, admit_now)
    occupied = occupied + jnp.where(admit_now, grid[best_a[tau]], 0.0)
    return admitted, alloc_idx, occupied, alive


@functools.partial(jax.jit, static_argnames=("flexible", "inner"))
def _greedy_jax(lat_ok, grid, price, cap, alive0, cost,
                flexible: bool = True, inner: str = "jnp"):
    T = lat_ok.shape[0]
    m = grid.shape[1]

    if inner == "pallas":
        from repro.kernels.pg import ops as pg_ops
        inner_fn = functools.partial(pg_ops.pg_argmax, flexible=flexible)
    else:
        inner_fn = None

    def body(state):
        return _round(state, lat_ok, grid, price, cap, cost, flexible,
                      inner_fn)

    def cond(state):
        *_, alive = state
        return jnp.any(alive)

    init = (jnp.zeros(T, bool), jnp.full(T, -1, jnp.int32),
            jnp.zeros(m, grid.dtype), alive0)
    admitted, alloc_idx, occupied, _ = jax.lax.while_loop(cond, body, init)
    return admitted, alloc_idx, occupied


def _pack_bits(mask):
    """Pack a boolean (..., A) mask into uint32 words (..., ceil(A/32)).

    The batched admission loop is memory-bound on (B, T, A) feasibility ops;
    packing the static per-task latency-feasibility rows 32x shrinks the
    per-round working set to ~100 KB for a 64x40x300 sweep.
    """
    a = mask.shape[-1]
    w = -(-a // 32)
    pad = jnp.zeros(mask.shape[:-1] + (w * 32 - a,), bool)
    padded = jnp.concatenate([mask, pad], axis=-1)
    words = padded.reshape(mask.shape[:-1] + (w, 32))
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (words * weights).sum(axis=-1, dtype=jnp.uint32)


def _unpack_bits(bits, a):
    """Inverse of :func:`_pack_bits`: (..., W) uint32 → (..., A) bool."""
    idx = jnp.arange(a)
    return (bits[..., idx // 32] >> (idx % 32).astype(jnp.uint32)) & 1 > 0


def _batch_pg(grid, price, cap, occupied):
    """Batched :func:`primal_gradient`: (B, m) pools → (B, A) gradients.

    vmap of the single-instance function, so the batched engine can never
    drift from the oracle's formula.
    """
    return jax.vmap(
        lambda p, c, o: primal_gradient(grid, p, c, o, xp=jnp)
    )(price, cap, occupied)


def _flex_round_fn(inner: str, lat_bits, grid, price, cap, A):
    """Build the flexible-mode batched round: (occupied, alive) → (V, tau, s*).

    The shared-gradient bit-domain trick of ``_greedy_jax_batch`` (see its
    docstring), factored out so the coupled variant runs the identical round
    with a link-masked ``alive`` — the per-round link feasibility folds into
    the candidate mask, so neither the jnp round nor the fused Pallas kernel
    needs to know about coupling.
    """
    if inner == "pallas":
        from repro.kernels.pg import pg as pg_kernel

        def round_fn(occupied, alive):
            return pg_kernel.batch_round(lat_bits, alive, grid, price, cap,
                                         occupied)
    else:
        def round_fn(occupied, alive):
            remaining = cap - occupied
            cap_ok = (grid[None] <= remaining[:, None, :] + 1e-9).all(-1)
            pg = _batch_pg(grid, price, cap, occupied)                 # (B, A)

            # columns lat-feasible for at least one alive task (bit domain)
            rows = jnp.where(alive[:, :, None], lat_bits, jnp.uint32(0))
            col_bits = jax.lax.reduce(rows, np.uint32(0), jax.lax.bitwise_or,
                                      (1,))                            # (B, W)
            col_any = _unpack_bits(col_bits, A)                        # (B, A)

            pgm = jnp.where(cap_ok & col_any, pg, -jnp.inf)
            v = pgm.max(-1)                                            # (B,)

            # first alive task whose feasible set attains V
            hit_bits = _pack_bits(cap_ok & (pgm == v[:, None]))        # (B, W)
            t_hit = ((lat_bits & hit_bits[:, None, :]) != 0).any(-1) & alive
            tau = jnp.argmax(t_hit, axis=1)                            # (B,)

            # tau's own first-max allocation (dense, but only (B, A))
            lat_tau = _unpack_bits(
                jnp.take_along_axis(lat_bits, tau[:, None, None],
                                    axis=1)[:, 0], A)
            cap_pgm = jnp.where(cap_ok, pg, -jnp.inf)
            best_a = jnp.where(lat_tau, cap_pgm, -jnp.inf).argmax(-1)  # (B,)
            return v, tau, best_a

    return round_fn


def _batch_solve(lat_ok, grid, price, cap, alive0, cost,
                 flexible: bool = True, inner: str = "jnp"):
    """Traced core shared by the plain and fused uncoupled jit entries.

    ``lat_ok`` (B, Tmax, A), ``price``/``cap`` (B, m), ``alive0`` (B, Tmax);
    ``grid``/``cost`` are shared (A, m)/(A,). The data-dependent while-loop of
    the single-instance path does not vmap, so the batch runs masked rounds
    under one while-loop whose condition is "any instance still has alive
    candidates"; finished instances degrade to no-op rounds.

    The flexible (Eq. 3) path exploits that the per-round gradient is shared
    by every task of an instance: the selected task attains the GLOBAL best
    feasible gradient V, so the round needs only bit-mask reductions — no
    (B, T, A) float argmax:

      1. V    = max PG over (cap-feasible ∧ lat-feasible-for-an-alive-task),
      2. tau  = first alive task whose row intersects {PG == V},
      3. s*   = first-max PG allocation within tau's row (tiny (B, A) argmax),

    which reproduces the sequential first-max tie-breaking bit-for-bit.
    ``inner="pallas"`` serves steps 1-3 (plus cap-feasibility and the
    gradient itself) from the fused ``kernels.pg.batch_round`` kernel so the
    per-round intermediates live only in VMEM; ``inner="jnp"`` keeps the
    bit-domain jnp round. The MinRes path (flexible=False) needs each task's
    OWN min-cost allocation, so it keeps the vmapped dense round regardless
    of ``inner``.

    Returns ``(admitted, alloc_idx, occupied, rounds)``: ``rounds`` is the
    loop's int32 trip count, the admission rounds the batch took.
    """
    B, tmax, A = lat_ok.shape
    m = grid.shape[1]

    if not flexible:
        def body(state):
            def f(state_b, lat_ok_b, price_b, cap_b):
                return _round(state_b, lat_ok_b, grid, price_b, cap_b, cost,
                              False, None)
            return (*jax.vmap(f)(state[:4], lat_ok, price, cap), state[4] + 1)

        def cond(state):
            return jnp.any(state[3])

        init = (jnp.zeros((B, tmax), bool), jnp.full((B, tmax), -1, jnp.int32),
                jnp.zeros((B, m), grid.dtype), alive0, jnp.int32(0))
        admitted, alloc_idx, occupied, _, rounds = jax.lax.while_loop(
            cond, body, init)
        return admitted, alloc_idx, occupied, rounds

    lat_bits = _pack_bits(lat_ok)                          # (B, T, W) u32
    round_fn = _flex_round_fn(inner, lat_bits, grid, price, cap, A)

    def body(state):
        admitted, alloc_idx, occupied, alive, rounds = state
        v, tau, best_a = round_fn(occupied, alive)
        admit = v > -jnp.inf
        # the admitted task leaves the candidate set; a round with nothing
        # feasible retires the whole instance (the oracle's line-15 mass drop)
        admitted, alloc_idx, alive, _ = _admit(admitted, alloc_idx, alive,
                                               tau, best_a, admit, admit)
        occupied = occupied + jnp.where(admit[:, None], grid[best_a], 0.0)
        return admitted, alloc_idx, occupied, alive, rounds + 1

    def cond(state):
        return jnp.any(state[3])

    init = (jnp.zeros((B, tmax), bool), jnp.full((B, tmax), -1, jnp.int32),
            jnp.zeros((B, m), grid.dtype), alive0, jnp.int32(0))
    admitted, alloc_idx, occupied, _, rounds = jax.lax.while_loop(
        cond, body, init)
    return admitted, alloc_idx, occupied, rounds


@functools.partial(jax.jit, static_argnames=("flexible", "inner"))
def _greedy_jax_batch(lat_ok, grid, price, cap, alive0, cost,
                      flexible: bool = True, inner: str = "jnp"):
    """Solve B padded instances in ONE device program (see _batch_solve)."""
    admitted, alloc_idx, occupied, _ = _batch_solve(
        lat_ok, grid, price, cap, alive0, cost, flexible, inner)
    return admitted, alloc_idx, occupied


def _pair_add(a_hi, a_lo, b_hi, b_lo):
    """``a + b`` for numbers held as float32 pairs ``hi + lo``: Knuth's
    two-sum of the high parts, their rounding error carried with the low
    parts, renormalised so that ``lo`` is below half an ulp of ``hi``."""
    s = a_hi + b_hi
    bb = s - a_hi
    err = (a_hi - (s - bb)) + (b_hi - bb)
    lo = err + a_lo + b_lo
    hi = s + lo
    return hi, lo - (hi - s)


def _batch_solve_coupled(lat_ok, grid, price, cap, alive0, cost,
                         load, link_cap, incidence, group,
                         flexible: bool = True, inner: str = "jnp"):
    """Coupled variant of :func:`_batch_solve`: cells sharing backhaul
    links admit JOINTLY. Also returns the per-link admitted load ``used``.

    Extra inputs: ``load`` (B, Tmax, 2) per-task shared-link load and
    ``link_cap`` (L, 2) link budgets, each a float32 pair ``hi + lo``
    (``sfesp.split_f32``), ``incidence`` (B, L) bool and ``group`` (B,) int
    — the connected components of the cell–link graph
    (``CouplingSpec.groups``). Link use is summed and compared on the pairs
    (``_pair_add``), so a load fits its link's remaining budget where it
    would in float64. Each round:

      1. per-cell candidate masks additionally require the task's load to fit
         the REMAINING budget of every link its cell traverses (folded into
         ``alive``, so the inner round — jnp bit-domain or the fused Pallas
         kernel — is reused unchanged),
      2. per cell the round yields (V_b, tau_b, s*_b) exactly as uncoupled,
      3. per coupling GROUP only the first cell attaining the group-max V
         admits its pick; the other cells' candidates stay alive and contend
         again next round (the oracle's cell-major first-max scan),
      4. the admitted task's load is charged to every incident link.

    A cell whose V is -inf retires: grid occupancy and link usage only grow,
    so infeasibility is permanent. Uncoupled cells (all-zero incidence rows)
    are singleton groups and admit every round, exactly like the uncoupled
    engine.

    Returns ``(admitted, alloc_idx, occupied, used, rounds)``, ``rounds``
    the loop's int32 trip count.
    """
    B, tmax, A = lat_ok.shape
    m = grid.shape[1]
    bidx = jnp.arange(B)
    inc_b = incidence.astype(bool)                          # (B, L)
    inc_f = incidence.astype(load.dtype)
    finite = jnp.isfinite(link_cap[:, 0])                   # (L,)

    if flexible:
        lat_bits = _pack_bits(lat_ok)
        round_fn = _flex_round_fn(inner, lat_bits, grid, price, cap, A)
    else:
        # MinRes needs each task's OWN min-cost allocation → dense per-cell
        # rounds, reduced to (V, tau, s*) for the joint selection
        def round_fn(occupied, alive):
            def f(lat_ok_b, price_b, cap_b, occ_b, alive_b):
                G, best_a, _ = _inner_jnp(grid, price_b, cap_b, occ_b,
                                          cap_b - occ_b, lat_ok_b, alive_b,
                                          cost, False)
                G = jnp.where(alive_b, G, -jnp.inf)
                tau = jnp.argmax(G)
                return G[tau], tau, best_a[tau]
            return jax.vmap(f)(lat_ok, price, cap, occupied, alive)

    def body(state):
        admitted, alloc_idx, occupied, alive, used, rounds = state
        # remaining budget per link, and per cell its tightest link's
        rem_hi, rem_lo = _pair_add(link_cap[:, 0], link_cap[:, 1],
                                   -used[:, 0], -used[:, 1])         # (L,)
        rem_hi = jnp.where(finite, rem_hi, jnp.inf)
        rem_lo = jnp.where(finite, rem_lo, 0.0)
        head_hi = jnp.where(inc_b, rem_hi[None, :], jnp.inf).min(-1)  # (B,)
        head_lo = jnp.where(inc_b & (rem_hi[None, :] == head_hi[:, None]),
                            rem_lo[None, :], jnp.inf).min(-1)
        head_lo = jnp.where(jnp.isinf(head_hi), 0.0, head_lo)
        # load <= headroom + 1e-9: the hi difference is exact where it is
        # small, so the sign is float64's
        link_ok = ((head_hi[:, None] - load[..., 0])
                   + (head_lo[:, None] - load[..., 1]) + 1e-9) >= 0  # (B, T)
        v, tau, best_a = round_fn(occupied, alive & link_ok)
        # group reductions over a (B, B) same-group mask: elementwise, where
        # segment max/min would scatter one cell at a time
        same = group[:, None] == group[None, :]
        gmax = jnp.where(same, v[None, :], -jnp.inf).max(-1)
        att = (v > -jnp.inf) & (v == gmax)
        first = jnp.where(same & att[None, :], bidx[None, :], B).min(-1)
        admit = att & (bidx == first)
        # a cell with nothing feasible retires; losers stay for next round
        admitted, alloc_idx, alive, hit = _admit(
            admitted, alloc_idx, alive, tau, best_a, admit, v > -jnp.inf)
        occupied = occupied + jnp.where(admit[:, None], grid[best_a], 0.0)
        # the admitted task's load: one non-zero per row and per link, so
        # both sums are exact
        add = (jnp.where(hit[..., None], load, 0.0).sum(1)[:, None, :]
               * inc_f[:, :, None]).sum(axis=0)                      # (L, 2)
        used = jnp.stack(_pair_add(used[:, 0], used[:, 1],
                                   add[:, 0], add[:, 1]), axis=-1)
        return admitted, alloc_idx, occupied, alive, used, rounds + 1

    def cond(state):
        return jnp.any(state[3])

    init = (jnp.zeros((B, tmax), bool), jnp.full((B, tmax), -1, jnp.int32),
            jnp.zeros((B, m), grid.dtype), alive0,
            jnp.zeros(link_cap.shape, load.dtype), jnp.int32(0))
    admitted, alloc_idx, occupied, _, used, rounds = \
        jax.lax.while_loop(cond, body, init)
    return admitted, alloc_idx, occupied, used.sum(-1), rounds


@functools.partial(jax.jit, static_argnames=("flexible", "inner"))
def _greedy_jax_batch_coupled(lat_ok, grid, price, cap, alive0, cost,
                              load, link_cap, incidence, group,
                              flexible: bool = True, inner: str = "jnp"):
    """Coupled batch solve in ONE device program (see _batch_solve_coupled)."""
    admitted, alloc_idx, occupied, _, _ = _batch_solve_coupled(
        lat_ok, grid, price, cap, alive0, cost, load, link_cap, incidence,
        group, flexible, inner)
    return admitted, alloc_idx, occupied


# ---------------------------------------------------------------------------
# Fused serving entry points: device-resident inputs, packed decision output
# ---------------------------------------------------------------------------

def _extract_packed(admitted, alloc_idx, occupied, cap, rounds):
    """Fuse decision extraction into the device program.

    Instead of shipping the full (B, Tmax) solution tables to the host and
    unpacking per task in Python, pack each batch row's decision into ONE
    compact int32 row: ``[admitted bitmask (ceil(T/32) words) | alloc_idx |
    rounds]``, plus the (B, m) residual capacities. The trailing column is
    the admission loop's trip count (the same in every row of a program), so
    the round counter costs no transfer of its own. The serving loop reads
    back a single small buffer per tick.
    """
    bits = _pack_bits(admitted)                           # (B, WT) u32
    packed = jnp.concatenate(
        [bits.astype(jnp.int32), alloc_idx.astype(jnp.int32),
         jnp.broadcast_to(rounds, (admitted.shape[0], 1))], axis=1)
    return packed, cap - occupied


@functools.partial(jax.jit, static_argnames=("flexible", "inner"))
def _serve_batch(lat_ok, grid, price, cap, alive0, cost,
                 flexible: bool = True, inner: str = "jnp"):
    """Uncoupled serving fast path: solve + packed extraction, one program.

    Inputs are expected to be ALREADY device-resident (a
    :class:`~repro.core.sfesp.DeviceStack`): nothing is re-uploaded per call.
    Returns ``(packed (B, WT+Tmax+1) i32, residual (B, m))``.
    """
    admitted, alloc_idx, occupied, rounds = _batch_solve(
        lat_ok, grid, price, cap, alive0, cost, flexible, inner)
    return _extract_packed(admitted, alloc_idx, occupied, cap, rounds)


@functools.partial(jax.jit, static_argnames=("flexible", "inner"))
def _serve_batch_coupled(lat_ok, grid, price, cap, alive0, cost,
                         load, link_cap, incidence, group,
                         flexible: bool = True, inner: str = "jnp"):
    """Coupled serving fast path; additionally returns per-link loads."""
    admitted, alloc_idx, occupied, used, rounds = _batch_solve_coupled(
        lat_ok, grid, price, cap, alive0, cost, load, link_cap, incidence,
        group, flexible, inner)
    packed, residual = _extract_packed(admitted, alloc_idx, occupied, cap,
                                       rounds)
    return packed, residual, used


def solve_device_batch(dev: DeviceStack, *, flexible: bool = True,
                       inner: str = "jnp") -> dict:
    """Solve a device-resident stacked batch via the fused entry points.

    The upload-free dispatch of the serving fast path (and of the delta
    restack tests): all inputs live in ``dev``'s jax arrays, the device
    program fuses the admission loop with decision extraction, and the host
    reads back one compact packed buffer. Returns a dict with ``admitted``
    (B, Tmax) bool, ``alloc_idx`` (B, Tmax) int (-1 where not admitted, as a
    mask-consumer convention: only ``admitted`` rows are meaningful),
    ``residual`` (B, m) remaining capacity, ``link_used`` (L,) admitted
    shared-link load (zeros-length when uncoupled) and ``rounds``, the
    admission rounds the device loop ran. Decisions are identical to
    :func:`solve_greedy_batch` on the equivalently stacked host batch.
    """
    return unpack_device_batch(dispatch_device_batch(
        dev, flexible=flexible, inner=inner))


def dispatch_device_batch(dev: DeviceStack, *, flexible: bool = True,
                          inner: str = "jnp") -> tuple:
    """LAUNCH the fused device solve without awaiting its result.

    The async half of :func:`solve_device_batch`: returns a handle of
    still-device-resident (possibly in-flight) arrays plus the batch shape
    captured at dispatch. The caller keeps mutating host state — e.g.
    ingesting the next tick's events — while the device computes, and blocks
    only in :func:`unpack_device_batch`. JAX arrays are futures under
    asynchronous dispatch, so this is just the solve with the host
    synchronisation point (``np.asarray``) deferred to the unpack — reading
    from ``DeviceStack.inputs()``, the double-buffer snapshot that stays
    valid while the serving loop scatters the next tick's rows.
    """
    program = _serve_batch_coupled if dev.coupled else _serve_batch
    with span("repro.solve.launch", B=dev.batch_size, A=dev.grid.shape[0],
              grids=dev.grids, program=_module_name(program)):
        (lat_ok, grid, price, cap, alive0, cost,
         link_load, link_cap, incidence, group) = dev.inputs()
        if dev.coupled:
            packed, residual, used = _serve_batch_coupled(
                lat_ok, grid, price, cap, alive0, cost,
                link_load, link_cap, incidence, group,
                flexible=flexible, inner=inner)
        else:
            packed, residual = _serve_batch(
                lat_ok, grid, price, cap, alive0, cost,
                flexible=flexible, inner=inner)
            used = np.zeros(0)
    # capture the shape now: unpack must not depend on the (mutable) stack
    return packed, residual, used, dev.batch_size, dev.max_tasks


def _module_name(program) -> str:
    """The name of a jitted program's executions on the device trace (its
    ``XLA Modules`` line, without the trailing id)."""
    return f"jit_{program.__name__}"


def _fetch(packed, residual, used, B: int):
    """Wait for a launched solve, then copy its outputs to the host."""
    with span("repro.solve.wait", B=B):
        # request the decisions' host copy first, so that it starts when the
        # device finishes, as a blocking np.asarray would have it start
        packed.copy_to_host_async()
        packed.block_until_ready()
    with span("repro.solve.fetch", B=B):
        return np.asarray(packed), np.asarray(residual), np.asarray(used)


def _unpack_rows(packed: np.ndarray, tmax: int):
    """Split packed decision rows into (admitted (R, tmax) bool, alloc_idx
    (R, tmax) int64, rounds): see :func:`_extract_packed`."""
    wt = -(-tmax // 32)
    bits = packed[:, :wt].astype(np.uint32)
    idx = np.arange(tmax)
    admitted = (bits[:, idx // 32] >> (idx % 32).astype(np.uint32)) & 1 > 0
    rounds = int(packed[:, -1].max(initial=0))
    return admitted, packed[:, wt:wt + tmax].astype(np.int64), rounds


def unpack_device_batch(dispatched: tuple) -> dict:
    """BLOCK on a :func:`dispatch_device_batch` handle and unpack it into
    the ``solve_device_batch`` result dict (the host synchronisation point)."""
    packed, residual, used, B, tmax = dispatched
    packed, residual, used = _fetch(packed, residual, used, B)
    with span("repro.solve.unpack", B=B):
        # drop inert pad_batch_to rows
        admitted, alloc_idx, rounds = _unpack_rows(packed[:B], tmax)
        return {
            "admitted": admitted,
            "alloc_idx": alloc_idx,
            "residual": residual[:B],
            "link_used": used,
            "rounds": rounds,
        }


def solve_greedy_jax(inst: ProblemInstance, *, semantic: bool = True,
                     flexible: bool = True, inner: str = "jnp") -> Solution:
    """JAX (jit) backend; bitwise-equivalent decisions to :func:`solve_greedy`
    up to argmax tie-breaking (both use first-max)."""
    lat, z_idx = _select_tables(inst, semantic)
    lat_ok = jnp.asarray(lat <= inst.tasks.max_latency[:, None])
    alive0 = jnp.asarray((z_idx >= 0) & np.asarray(lat_ok).any(axis=1))
    grid = jnp.asarray(inst.grid)
    cost = jnp.asarray(lexicographic_cost(inst.grid))
    admitted, alloc_idx, _ = _greedy_jax(
        lat_ok, grid, jnp.asarray(inst.pool.price),
        jnp.asarray(inst.pool.capacity), alive0, cost,
        flexible=flexible, inner=inner)
    return _pack_solution(inst, semantic, np.asarray(admitted),
                          np.asarray(alloc_idx, np.int64), z_idx)


def solve_greedy_batch(insts, *, semantic: bool = True, flexible: bool = True,
                       inner: str = "jnp",
                       pad_batch_to: int | None = None) -> list[Solution]:
    """Batched sweep engine: solve many instances in one jit call.

    ``insts`` is a sequence of :class:`ProblemInstance` (stacked on the fly)
    or a pre-built :class:`StackedInstances`. Decisions are identical to
    running :func:`solve_greedy_jax` per instance, and match the
    :func:`solve_greedy` numpy oracle with the same caveat as every JAX
    backend here: gradients are computed in float32 (unless x64 is enabled),
    so instances whose float64 gradient ordering hinges on sub-f32-ulp
    differences may break argmax ties differently. Returns one
    :class:`Solution` per instance in input order.

    ``inner="pallas"`` serves the flexible round from the fused
    ``kernels.pg.batch_round`` kernel (MinRes falls back to the dense vmapped
    round). ``pad_batch_to`` pads the DEVICE batch with inert instances
    (never-alive, unit capacity) so sweeps bucketed to a common (B, Tmax)
    shape reuse one compiled program; outputs are sliced back to the real B.

    When the stacked batch carries a :class:`~repro.core.types.CouplingSpec`
    (shared midhaul/backhaul links), cells coupled through a link admit
    JOINTLY — one global-max pick per coupling group per round, capacity-
    checked against both the cell's grid and the shared link budgets; the
    reference semantics are ``baselines.solve_coupled_ref``. Uncoupled
    batches take the exact uncoupled device program as before.
    """
    stacked = insts if isinstance(insts, StackedInstances) \
        else stack_instances(insts)
    B = stacked.batch_size
    # device-resident half, memoized on the batch: repeated solves of the
    # same stacked batch (sweep reruns, what-if studies) re-upload nothing
    dev = device_stack(stacked, semantic=semantic, pad_batch_to=pad_batch_to)
    if dev.coupled:
        admitted, alloc_idx, _ = _greedy_jax_batch_coupled(
            dev.lat_ok, dev.grid, dev.price, dev.capacity, dev.alive0,
            dev.cost, dev.link_load, dev.link_cap, dev.incidence, dev.group,
            flexible=flexible, inner=inner)
    else:
        admitted, alloc_idx, _ = _greedy_jax_batch(
            dev.lat_ok, dev.grid, dev.price, dev.capacity, dev.alive0,
            dev.cost, flexible=flexible, inner=inner)
    admitted = np.asarray(admitted)[:B]
    alloc_idx = np.asarray(alloc_idx, np.int64)[:B]
    return _pack_batch_solutions(stacked, admitted, alloc_idx, semantic)


def _pack_batch_solutions(stacked: StackedInstances, admitted: np.ndarray,
                          alloc_idx: np.ndarray,
                          semantic: bool) -> list[Solution]:
    """Vectorized _pack_solution over a whole batch (per-instance Python
    packing would dwarf the device solve at sweep sizes). ``admitted`` /
    ``alloc_idx`` are host (B, Tmax) decision tables in STACKED row order;
    returns one :class:`Solution` per stacked instance, same order."""
    if semantic:
        lat, z_idx = stacked.lat, stacked.z_star_idx
        z_star = stacked.z_star
    else:
        lat, z_idx = stacked.lat_agnostic, stacked.z_star_idx_agnostic
        z_star = stacked.z_star_agnostic
    grid = stacked.grid
    safe_idx = np.clip(alloc_idx, 0, None)
    alloc = grid[safe_idx] * admitted[:, :, None]                 # (B, T, m)
    z = np.where(admitted & (z_idx >= 0), z_star, 1.0)
    a_true = semantics.resolve(stacked.semantics).accuracy(stacked.app_idx, z)
    l_val = np.take_along_axis(lat, safe_idx[:, :, None], axis=2)[:, :, 0]
    l_val = np.where(admitted & (alloc_idx >= 0), l_val, np.inf)
    satisfied = admitted & (a_true + 1e-9 >= stacked.min_accuracy) \
        & (l_val <= stacked.max_latency + 1e-9)
    per_task = (stacked.price[:, None, :]
                * (stacked.capacity[:, None, :] - alloc)).sum(axis=2)
    objective = (per_task * admitted).sum(axis=1)                 # (B,)

    out = []
    for b, inst in enumerate(stacked.instances):
        t = inst.num_tasks
        out.append(Solution(
            admitted=admitted[b, :t], alloc=alloc[b, :t], z=z[b, :t],
            objective=float(objective[b]), satisfied=satisfied[b, :t]))
    return out


def _to_input_order(stacked: StackedInstances, sols: list) -> list:
    """Undo a group-major stacking permutation: ``out[perm[b]] = sols[b]``."""
    if stacked.perm is None:
        return sols
    out = [None] * len(sols)
    for b, sol in enumerate(sols):
        out[int(stacked.perm[b])] = sol
    return out


# Bounded: the cache key holds a live Mesh (and its device buffers' metadata);
# test suites that build many meshes must not accumulate them forever. The
# fake-device fixtures call clear_sharded_caches() on teardown.
@functools.lru_cache(maxsize=16)
def _sharded_solve_fn(mesh, axis: str, flexible: bool, inner: str):
    """Jitted shard_map entry of the metro solve, cached per (mesh, mode).

    Each shard runs the UNMODIFIED coupled batch core on its block of the
    group-major batch: local group ids keep every group max / min reduction
    shard-local, so no collective appears in the loop and each shard's
    ``while_loop`` converges independently — a congested group never
    serializes the fleet (per-group round convergence, no global barrier).
    """
    from jax.sharding import PartitionSpec as P

    def sharded_solve(lat_ok, grid, price, cap, alive0, cost, load, link_cap,
                      incidence, group):
        admitted, alloc_idx, _, _, _ = _batch_solve_coupled(
            lat_ok, grid, price, cap, alive0, cost, load, link_cap,
            incidence, group, flexible, inner)
        return admitted, alloc_idx

    cells, rep = P(axis), P()
    fn = jax.shard_map(
        sharded_solve, mesh=mesh,
        in_specs=(cells, rep, cells, cells, cells, rep, cells, rep, cells,
                  cells),
        out_specs=(cells, cells), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=16)
def _sharded_serve_fn(mesh, axis: str, flexible: bool, inner: str):
    """Jitted shard_map entry of the metro SERVING tick: coupled solve plus
    packed decision extraction fused into each shard's program.

    The sharded sibling of :func:`_serve_batch_coupled`: every shard solves
    its block of coupling groups and packs its own rows' decisions
    (``_extract_packed``), so the host reads back one small
    ``(B', WT+Tmax+1)`` buffer instead of the full solution tables; each
    shard's rows carry that shard's own round count. The
    per-shard link loads come back block-stacked — each link belongs to
    exactly one group, hence one shard, so summing the blocks reconstructs
    the global (L,) usage without a collective in the loop.
    """
    from jax.sharding import PartitionSpec as P

    def sharded_serve(lat_ok, grid, price, cap, alive0, cost, load, link_cap,
                      incidence, group):
        admitted, alloc_idx, occupied, used, rounds = _batch_solve_coupled(
            lat_ok, grid, price, cap, alive0, cost, load, link_cap,
            incidence, group, flexible, inner)
        packed, residual = _extract_packed(admitted, alloc_idx, occupied, cap,
                                           rounds)
        return packed, residual, used

    cells, rep = P(axis), P()
    fn = jax.shard_map(
        sharded_serve, mesh=mesh,
        in_specs=(cells, rep, cells, cells, cells, rep, cells, rep, cells,
                  cells),
        out_specs=(cells, cells, cells), check_vma=False)
    return jax.jit(fn)


def clear_sharded_caches() -> None:
    """Drop the memoized sharded shard_map programs.

    Test hygiene: :func:`_sharded_solve_fn` / :func:`_sharded_serve_fn` hold
    ``Mesh`` objects as lru_cache keys; suites that build many meshes call
    this (via the ``run_with_fake_devices`` fixture teardown) so retired
    meshes and their compiled programs are actually collectable.
    """
    _sharded_solve_fn.cache_clear()
    _sharded_serve_fn.cache_clear()


def dispatch_sharded_batch(shd: ShardedStack, *, flexible: bool = True,
                           inner: str = "jnp") -> tuple:
    """LAUNCH the fused SHARDED serve without awaiting its result.

    The mesh-resident sibling of :func:`dispatch_device_batch`: reads the
    :meth:`~repro.core.sfesp.ShardedStack.inputs` double-buffer snapshot,
    launches one ``shard_map`` program (solve + packed extraction per shard),
    and returns a handle for :func:`unpack_sharded_batch`. The row map is
    captured at dispatch so a session replan cannot skew an in-flight tick.
    """
    program = _sharded_serve_fn(shd.mesh, shd.axis, flexible, inner)
    with span("repro.solve.launch", B=shd.batch_size, A=shd.grid.shape[0],
              grids=shd.grids, program=_module_name(program)):
        (lat_ok, grid, price, cap, alive0, cost,
         link_load, link_cap, incidence, group) = shd.inputs()
        packed, residual, used = program(
            lat_ok, grid, price, cap, alive0, cost,
            link_load, link_cap, incidence, group)
    return (packed, residual, used, shd.batch_size, shd.max_tasks,
            shd.row_of, shd.num_shards, shd.coupled)


def unpack_sharded_batch(dispatched: tuple) -> dict:
    """BLOCK on a :func:`dispatch_sharded_batch` handle and unpack it into
    the ``solve_device_batch`` result dict, in INPUT (cell) order.

    The packed buffer arrives in the padded shard layout; ``row_of`` gathers
    the live rows back so callers (the serving session's slot unpacker, the
    twin-engine tests) never see the plan. Inert padding rows never admit —
    their decision rows are dropped. ``rounds`` is the largest of the
    shards' round counts: the slowest shard sets the solve's time.
    """
    (packed, residual, used, B, tmax, row_of, n_shards, coupled) = dispatched
    packed, residual_p, used = _fetch(packed, residual, used, B)
    with span("repro.solve.unpack", B=B):
        admitted_p, alloc_p, rounds = _unpack_rows(packed, tmax)
        live = row_of >= 0
        admitted = np.zeros((B, tmax), bool)
        alloc_idx = np.full((B, tmax), -1, np.int64)
        out_residual = np.zeros((B, residual_p.shape[1]))
        admitted[row_of[live]] = admitted_p[live]
        alloc_idx[row_of[live]] = alloc_p[live]
        out_residual[row_of[live]] = residual_p[live]
        # per-shard (L,) blocks; disjoint link ownership makes the sum exact
        used = used.reshape(n_shards, -1).sum(axis=0)
        return {
            "admitted": admitted,
            "alloc_idx": alloc_idx,
            "residual": out_residual,
            "link_used": used if coupled else np.zeros(0),
            "rounds": rounds,
        }


def solve_sharded_batch(shd: ShardedStack, *, flexible: bool = True,
                        inner: str = "jnp") -> dict:
    """Solve a mesh-resident stack via the fused sharded entry points —
    :func:`solve_device_batch` for a :class:`~repro.core.sfesp.ShardedStack`.
    Decisions are identical to the single-device fused serve on the same
    rows (asserted in tests)."""
    return unpack_sharded_batch(dispatch_sharded_batch(
        shd, flexible=flexible, inner=inner))


def solve_greedy_sharded(insts, *, mesh=None, semantic: bool = True,
                         flexible: bool = True, inner: str = "jnp",
                         axis: str = "cells") -> list[Solution]:
    """Metro-scale front door: the coupled batched solve sharded over a
    device mesh, one block of coupling groups per device.

    ``insts`` is a sequence of :class:`ProblemInstance` (stacked group-major
    on the fly) or a pre-built :class:`StackedInstances` (any layout — the
    sharded device half permutes group-major itself). ``mesh`` is a 1-D mesh
    whose ``axis`` names the batch split (``launch.mesh.make_cells_mesh``);
    ``None`` builds one over all visible devices. Solutions come back in
    INPUT order regardless of layout.

    Decisions are bit-identical to :func:`solve_greedy_batch` on the same
    instances (asserted in tests): the group-major permutation is stable, so
    within-group cell order — the coupled tie-break — is preserved, and each
    shard runs the same per-round core on its groups. With one device (or a
    size-1 mesh) this IS the single-device solve, reordered.
    """
    stacked = insts if isinstance(insts, StackedInstances) \
        else stack_instances(
            insts, group_major=True,
            tmax=next_pow2(max((i.num_tasks for i in insts), default=1)))
    if mesh is None:
        from repro.launch.mesh import make_cells_mesh
        mesh = make_cells_mesh(axis=axis)
    if int(mesh.shape[axis]) == 1:
        sols = solve_greedy_batch(stacked, semantic=semantic,
                                  flexible=flexible, inner=inner)
        return _to_input_order(stacked, sols)
    shd = device_stack_sharded(stacked, mesh, semantic=semantic, axis=axis)
    admitted_p, alloc_p = _sharded_solve_fn(mesh, axis, flexible, inner)(
        shd.lat_ok, shd.grid, shd.price, shd.capacity, shd.alive0, shd.cost,
        shd.link_load, shd.link_cap, shd.incidence, shd.group)
    admitted_p = np.asarray(admitted_p)
    alloc_p = np.asarray(alloc_p, np.int64)
    B, tmax = stacked.batch_size, stacked.max_tasks
    admitted = np.zeros((B, tmax), bool)
    alloc_idx = np.full((B, tmax), -1, np.int64)
    live = shd.row_of >= 0
    admitted[shd.row_of[live]] = admitted_p[live]
    alloc_idx[shd.row_of[live]] = alloc_p[live]
    sols = _pack_batch_solutions(stacked, admitted, alloc_idx, semantic)
    return _to_input_order(stacked, sols)


def solve_greedy_many(insts, *, semantic: bool = True, flexible: bool = True,
                      inner: str = "jnp") -> list[Solution]:
    """Grid-grouped sweep dispatcher: batch-solve instances with MIXED grids.

    Heterogeneous multi-cell traces (per-cell ``pool.levels``) would pad
    every instance onto one union grid if stacked whole. This front door
    groups the instances by grid identity and solves each group through the
    batched engine, padding ``Tmax`` and the device batch to power-of-two
    buckets so repeated sweeps with fluctuating task counts / group sizes
    land on a handful of cached device programs instead of recompiling.

    Returns one :class:`Solution` per instance, in input order. Decisions are
    exactly those of :func:`solve_greedy_batch` on each group (hence the same
    f32 tie-break caveat vs the numpy oracle). Backhaul-coupled instances are
    solved jointly: grid groups joined by a shared link stack together onto
    their union grid (:func:`~repro.core.sfesp.stack_instances`), so a link
    whose users sit on different grids is charged once, by one solve.
    """
    insts = list(insts)
    keys: dict[bytes, int] = {}
    key_of = []
    for inst in insts:
        key = np.ascontiguousarray(inst.grid).tobytes() \
            + repr(inst.grid.shape).encode()
        key_of.append(keys.setdefault(key, len(keys)))
    # grid groups joined by a link (transitively) solve as one stack
    parent = list(range(len(keys)))

    def find(k: int) -> int:
        while parent[k] != k:
            k = parent[k]
        return k

    first_user: dict[tuple, int] = {}
    for i, inst in enumerate(insts):
        spec = inst.coupling
        if spec is None:
            continue
        for link in np.nonzero(spec.incidence[0])[0]:
            # link sets are identified by capacity-array identity, matching
            # the merge_coupling contract
            lid = (id(spec.link_capacity), int(link))
            a, b = find(key_of[i]), find(first_user.setdefault(lid,
                                                               key_of[i]))
            parent[max(a, b)] = min(a, b)
    groups: dict[int, list[int]] = {}
    for i, k in enumerate(key_of):
        groups.setdefault(find(k), []).append(i)
    out: list[Solution | None] = [None] * len(insts)
    for idxs in groups.values():
        sub = [insts[i] for i in idxs]
        tmax = next_pow2(max(inst.num_tasks for inst in sub))
        stacked = stack_instances(sub, tmax=tmax)
        sols = solve_greedy_batch(stacked, semantic=semantic,
                                  flexible=flexible, inner=inner,
                                  pad_batch_to=next_pow2(len(sub)))
        for i, sol in zip(idxs, sols):
            out[i] = sol
    return out


def solve(inst: ProblemInstance, *, semantic: bool = True, flexible: bool = True,
          backend: str = "numpy", inner: str = "jnp") -> Solution:
    """Front door used by serving admission + benchmarks."""
    if backend == "numpy":
        return solve_greedy(inst, semantic=semantic, flexible=flexible)
    return solve_greedy_jax(inst, semantic=semantic, flexible=flexible,
                            inner=inner)
