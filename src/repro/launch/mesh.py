"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is pure
data parallelism so the only cross-pod (DCI) traffic is the per-step gradient
all-reduce.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — smoke tests see 1 CPU device;
only ``dryrun.py`` sets XLA_FLAGS for 512 host devices before first jax init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "make_cells_mesh", "HW"]


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    Newer JAX defaults mesh axes to ``Explicit`` sharding, under which a
    scatter into a sharded operand must name its ``out_sharding``. The
    programs here leave placement to the compiler (``NamedSharding`` inputs,
    ``shard_map`` bodies), which is what ``Auto`` axes mean.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CI-scale sharding tests (8 fake devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_cells_mesh(n_devices: int | None = None, *, axis: str = "cells"):
    """1-D mesh over the local devices for the metro-scale sharded coupled
    solve (``core.greedy.solve_greedy_sharded``): the batch axis is split
    over ``axis``, one block of coupling groups per device. Defaults to all
    visible devices; pass ``n_devices`` to restrict (must divide nothing —
    any count works, lighter shards are padded)."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return _auto_mesh((n,), (axis,))


class HW:
    """TPU v5e roofline constants (per chip)."""

    PEAK_FLOPS_BF16 = 197e12        # FLOP/s
    HBM_BW = 819e9                  # B/s
    ICI_BW = 50e9                   # B/s per link
    HBM_BYTES = 16 * 1024**3
