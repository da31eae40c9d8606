"""Compile-only checks for one TPU v5e chip, with no chip attached.

The kernels and device programs of the metro re-slice loop are lowered and
compiled by the TPU compiler against a described ``v5e:2x2`` topology, at
the widths the serving path runs: what interpret-mode tests cannot see
(block tiling, layouts, VMEM) fails here. Nothing executes, so results and
times are out of scope. The topology is described inside a module-scoped
fixture, never at import, so every test worker collects the same tests and
only the worker that runs this file loads the TPU library.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.greedy import (_serve_batch, _serve_batch_coupled,
                               _sharded_serve_fn, clear_sharded_caches)
from repro.data.pipeline import FrameStream
from repro.kernels.pg import pg
from repro.kernels.resize import ref as resize_ref
from repro.kernels.resize import resize


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("b,t,a,m", [(256, 64, 300, 2),     # metro pools
                                     (256, 64, 1280, 4)])   # paper 4-resource
def test_batch_round_compiles(one_chip, b, t, a, m):
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    w = -(-a // 32)
    compiled = _compile(
        functools.partial(pg.batch_round, interpret=False),
        spec((b, t, w), jnp.uint32), spec((b, t), jnp.bool_),
        spec((a, m), jnp.float32), spec((b, m), jnp.float32),
        spec((b, m), jnp.float32), spec((b, m), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_masked_argmax_compiles(one_chip):
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    t, a = 64, 1280
    compiled = _compile(
        functools.partial(pg.masked_argmax, interpret=False),
        spec((a,), jnp.float32), spec((t, a), jnp.bool_),
        spec((a,), jnp.bool_), spec((t,), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()


def test_resize_bilinear_compiles(one_chip):
    frames = FrameStream(seed=0).frames(step=0, batch=8)     # 8x128x128x3
    _, h, w, c = frames.shape
    ho, wo = resize_ref.out_size_for_z(h, w, 0.25)
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = _compile(
        functools.partial(resize.resize_bilinear, interpret=False),
        spec(frames.shape, frames.dtype), spec((ho, h), jnp.float32),
        spec((wo, w), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def _coupled_args(spec, b, tmax, a, m, links):
    return (spec((b, tmax, a), jnp.bool_), spec((a, m), jnp.float32),
            spec((b, m), jnp.float32), spec((b, m), jnp.float32),
            spec((b, tmax), jnp.bool_), spec((a,), jnp.float32),
            spec((b, tmax, 2), jnp.float32), spec((links, 2), jnp.float32),
            spec((b, links), jnp.bool_), spec((b,), jnp.int32))


@pytest.mark.parametrize("inner", ["jnp", "pallas"])
def test_serve_batch_coupled_compiles_at_metro_shapes(one_chip, inner):
    """The coupled serve program of the 256-cell / 32-domain metro tick
    (hour-13 snapshot: Tmax = 15, A = 300 allocations, m = 2)."""
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = _compile(
        functools.partial(_serve_batch_coupled, flexible=True, inner=inner),
        *_coupled_args(spec, 256, 15, 300, 2, 32))
    assert compiled.memory_analysis().argument_size_in_bytes < 16 * 2**20


@pytest.mark.parametrize("b,t,a,m,flexible", [
    (960, 50, 1280, 4, True), (960, 50, 1280, 4, False),   # paper4res sweep
    (960, 50, 300, 2, True), (960, 50, 300, 2, False),     # paper2res sweep
    (256, 15, 300, 2, None)])                              # coupled metro
def test_admission_loop_holds_no_scatter(one_chip, b, t, a, m, flexible):
    """The admission round updates its (B, Tmax) state by one-hot masks: no
    scatter, which the TPU applies one update at a time, is left in the
    serve programs (``flexible=None`` is the coupled program, flexible)."""
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = _coupled_args(spec, b, t, a, m, 32)
    if flexible is None:
        fn = functools.partial(_serve_batch_coupled, flexible=True,
                               inner="jnp")
    else:
        fn, args = functools.partial(_serve_batch, flexible=flexible,
                                     inner="jnp"), args[:6]
    assert " scatter(" not in _compile(fn, *args).as_text()


def test_sharded_serve_compiles_on_four_chips(topo):
    """The 4-chip metro serve (1024 cells / 128 domains, group-major blocks
    of 256 rows per chip) compiles as one shard_map program with no
    collective inside the admission loop."""
    mesh = Mesh(np.array(topo.devices[:4]), ("cells",),
                axis_types=(AxisType.Auto,))
    cells, rep = NamedSharding(mesh, P("cells")), NamedSharding(mesh, P())
    shardings = (cells, rep, cells, cells, cells, rep, cells, rep, cells,
                 cells)
    shapes = _coupled_args(lambda s, d: (s, d), 1024, 8, 300, 2, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh)
            for (s, d), sh in zip(shapes, shardings)]
    try:
        fn = _sharded_serve_fn(mesh, "cells", True, "jnp")
        text = fn.lower(*args).compile().as_text()
    finally:
        clear_sharded_caches()
    assert "all-reduce" not in text and "all-gather" not in text
