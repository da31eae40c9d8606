"""Mixture-of-Experts FFN: token-choice top-k routing, three dispatch modes.

* ``dense`` — every expert computes every token, outputs gate-weighted. Exact
  oracle; used for smoke tests, correctness tests, and tiny decode batches
  (where top-k gather would cost more than it saves).
* ``ep``    — expert parallelism via ``shard_map`` + ``all_to_all`` over the
  ``model`` mesh axis (requires n_experts % mesh_model == 0; qwen3: 128/16).
  Sort-based dispatch into fixed-capacity per-expert buckets (static shapes;
  overflow tokens drop to the residual path — standard token dropping).
* ``tp``    — tensor parallelism over the expert FFN hidden dim with *local*
  sort-based dispatch and a psum epilogue (works for any expert count;
  mixtral: 8 experts < 16-way model axis, so EP is impossible but TP is free).

TPU adaptation (DESIGN.md §4): dispatch is sort + fixed-capacity scatter
feeding *batched dense matmuls* on the MXU — not NCCL-style point-to-point.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import dense_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg, dtype=jnp.float32):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, f), dtype=dtype),
        "w_down": dense_init(ks[3], (e, f, d), dtype=dtype),
    }


def _route(params, x, cfg):
    """Top-k routing. x (..., d) → gates (..., k) f32, idx (..., k) int32."""
    logits = x.astype(jnp.float32) @ params["router"]
    gates, idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(gates, axis=-1)        # normalize over the top-k
    return gates, idx


def _expert_ffn(w_gate, w_up, w_down, xb):
    """Batched SwiGLU over expert buckets: xb (E, C, d) → (E, C, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xb, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _dispatch_sort(e_flat, n_experts: int, capacity: int):
    """Sort-based bucket dispatch. e_flat (a,) int32 expert per assignment.

    Returns (order, expert_sorted, slot_sorted, valid_sorted): the a
    assignments in expert-sorted order, each with its bucket slot (< capacity)
    and validity (False = dropped by capacity overflow)."""
    a = e_flat.shape[0]
    order = jnp.argsort(e_flat)                    # stable
    e_sorted = e_flat[order]
    idx = jnp.arange(a, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), e_sorted[1:] != e_sorted[:-1]])
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, -1))
    slot = idx - run_start
    valid = slot < capacity
    return order, e_sorted, slot, valid


def _scatter_combine(x_flat, gates_flat, tok_flat, order, e_sorted, slot,
                     valid, n_experts, capacity, expert_fn):
    """Shared dispatch → expert_fn((E, C, d)) → combine path."""
    d = x_flat.shape[-1]
    tok_sorted = tok_flat[order]
    gate_sorted = gates_flat[order]
    e_safe = jnp.where(valid, e_sorted, 0)
    slot_safe = jnp.where(valid, slot, 0)
    xb = jnp.zeros((n_experts, capacity, d), x_flat.dtype)
    vals = jnp.where(valid[:, None], x_flat[tok_sorted], 0)
    xb = xb.at[e_safe, slot_safe].add(vals)        # unique (e,slot) per valid
    yb = expert_fn(xb)
    y_sorted = yb[e_safe, slot_safe] * jnp.where(valid, gate_sorted, 0.0)[:, None]
    out = jnp.zeros_like(x_flat).at[tok_sorted].add(y_sorted.astype(x_flat.dtype))
    return out


def _moe_local(params, x, cfg, *, capacity_scale: float = 1.0, psum_axis=None,
               ep_axis=None, n_ep: int = 1, psum_late: bool = False):
    """Dispatch path shared by tp (psum_axis set) and ep (ep_axis set).

    ``psum_late`` (TP only): apply the cross-shard reduction AFTER the
    combine, on the (n_tok, d) output instead of the (E, C, d) expert buckets
    — the buckets carry capacity_factor × top_k more rows than tokens, so the
    late psum moves ~2.5x fewer bytes (§Perf iteration on the
    collective-bound mixtral prefill cell). Valid because the combine is
    linear in the expert outputs."""
    b, t, d = x.shape
    n_tok = b * t
    e = cfg.n_experts
    gates, idx = _route(params, x, cfg)
    x_flat = x.reshape(n_tok, d)
    gates_flat = gates.reshape(n_tok * cfg.top_k)
    e_flat = idx.reshape(n_tok * cfg.top_k).astype(jnp.int32)
    tok_flat = jnp.repeat(jnp.arange(n_tok, dtype=jnp.int32), cfg.top_k)
    capacity = max(8, int(math.ceil(
        n_tok * cfg.top_k * cfg.capacity_factor * capacity_scale / e)))
    order, e_sorted, slot, valid = _dispatch_sort(e_flat, e, capacity)

    if ep_axis is None:
        def expert_fn(xb):
            y = _expert_ffn(params["w_gate"], params["w_up"],
                            params["w_down"], xb)
            if psum_axis is not None and not psum_late:
                y = jax.lax.psum(y, psum_axis)
            return y
    else:
        e_loc = e // n_ep

        def expert_fn(xb):
            # (E, C, d) → exchange so each device holds its local experts'
            # tokens from every peer: (E, C, d) -all_to_all-> rows regrouped
            # as (src_dev, E_loc, C, d).
            recv = jax.lax.all_to_all(xb, ep_axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            recv = recv.reshape(n_ep, e_loc, capacity, d) \
                       .transpose(1, 0, 2, 3).reshape(e_loc, n_ep * capacity, d)
            y = _expert_ffn(params["w_gate"], params["w_up"],
                            params["w_down"], recv)
            y = y.reshape(e_loc, n_ep, capacity, d).transpose(1, 0, 2, 3) \
                 .reshape(e, capacity, d)
            return jax.lax.all_to_all(y, ep_axis, split_axis=0,
                                      concat_axis=0, tiled=True)

    out = _scatter_combine(x_flat, gates_flat, tok_flat, order, e_sorted,
                           slot, valid, e, capacity, expert_fn)
    if psum_axis is not None and psum_late:
        out = jax.lax.psum(out, psum_axis)
    return out.reshape(b, t, d)


def _moe_dense(params, x, cfg):
    gates, idx = _route(params, x, cfg)
    h = jax.nn.silu(jnp.einsum("btd,edf->btef", x, params["w_gate"])) \
        * jnp.einsum("btd,edf->btef", x, params["w_up"])
    y_all = jnp.einsum("btef,efd->bted", h, params["w_down"])
    onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)  # (b,t,k,e)
    w = (onehot * gates[..., None]).sum(axis=2)                      # (b,t,e)
    return jnp.einsum("bted,bte->btd", y_all, w.astype(x.dtype))


def moe_apply(params, x, cfg, *, impl: str | None = None, mesh=None,
              data_axes=("pod", "data"), model_axis="model",
              psum_late: bool = False):
    """MoE FFN. ``impl`` ∈ {dense, tp, ep}; tp/ep need ``mesh``."""
    impl = impl or cfg.moe_impl
    if impl == "dense" or mesh is None:
        return _moe_dense(params, x, cfg)

    axes = tuple(a for a in data_axes if a in mesh.axis_names)
    n_ep = mesh.shape[model_axis]
    if impl == "ep":
        # tokens are sequence-sharded over the model axis for the dispatch so
        # every device routes a *unique* token shard (no duplicated dispatch
        # work across the EP group); shard_map's in_spec does the reshard.
        assert cfg.n_experts % n_ep == 0, "EP needs E % mesh_model == 0"
        assert x.shape[1] % n_ep == 0, "EP needs T % mesh_model == 0"
        x_spec = P(axes, model_axis, None)
        w_specs = {"router": P(None, None),
                   "w_gate": P(model_axis, None, None),
                   "w_up": P(model_axis, None, None),
                   "w_down": P(model_axis, None, None)}
        fn = lambda p, xx: _moe_local(p, xx, cfg, ep_axis=model_axis,
                                      n_ep=n_ep)
    elif impl == "tp":
        # experts replicated over data axes, FFN hidden dim sharded over the
        # model axis; every model peer dispatches the same tokens and the
        # down-projection partial sums are psum'ed.
        x_spec = P(axes, None, None)
        w_specs = {"router": P(None, None),
                   "w_gate": P(None, None, model_axis),
                   "w_up": P(None, None, model_axis),
                   "w_down": P(None, model_axis, None)}
        fn = lambda p, xx: _moe_local(p, xx, cfg, psum_axis=model_axis,
                                      psum_late=psum_late)
    else:
        raise ValueError(impl)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(w_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(params, x)
