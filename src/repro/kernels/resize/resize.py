"""Pallas TPU kernel: semantic-compression bilinear resize as two MXU matmuls.

Hardware adaptation: the paper compresses JPEGs at the UE (entropy coding —
bit-serial, no TPU analogue; see DESIGN.md). The TPU-native realization of the
compression factor ``z`` is resolution scaling, and bilinear resampling is a
pair of *separable* linear maps — so instead of a CUDA-style per-pixel gather
kernel we evaluate ``out = R_h @ img @ R_wᵀ`` per (batch, channel) slab:

  * both contractions feed the 128×128 MXU (gathers become dense matmuls with
    2-banded interpolation matrices),
  * the (h, W) intermediate lives entirely in VMEM,
  * grid = (B, C): one image-channel slab per step — input slab (H, W) plus
    both interpolation matrices comfortably fit VMEM for edge-camera frames
    (e.g. 1024×2048 f32 slab = 8 MB),
  * the kernel reads a channel-major (B, C, H, W) view, so each block's last
    two dims are the whole (H, W) slab — Mosaic's (8, 128) tiling accepts
    any full-extent block, never a width-1 channel slice,
  * both contractions run at fp32 contract precision, the arithmetic of the
    matrix-form oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

__all__ = ["resize_bilinear"]


def _kernel(img_ref, rh_ref, rwt_ref, out_ref):
    img = img_ref[0, 0].astype(jnp.float32)                     # (H, W)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    tmp = dot(rh_ref[...], img)                                 # (h, W) MXU
    out_ref[0, 0] = dot(tmp, rwt_ref[...]).astype(out_ref.dtype)  # (h, w)


@functools.partial(jax.jit, static_argnames=("interpret",))
def resize_bilinear(img, r_h, r_w, *, interpret: bool | None = None):
    """img (B, H, W, C); r_h (h, H) f32; r_w (w, W) f32 → (B, h, w, C).

    ``interpret=None`` → interpreter unless the default backend is the TPU.
    """
    interpret = resolve_interpret(interpret)
    b, hin, win, c = img.shape
    hout = r_h.shape[0]
    wout = r_w.shape[0]
    slab = lambda bi, ci: (bi, ci, 0, 0)
    whole = lambda bi, ci: (0, 0)
    out = pl.pallas_call(
        _kernel,
        grid=(b, c),
        in_specs=[
            pl.BlockSpec((1, 1, hin, win), slab),
            pl.BlockSpec((hout, hin), whole),
            pl.BlockSpec((win, wout), whole),
        ],
        out_specs=pl.BlockSpec((1, 1, hout, wout), slab),
        out_shape=jax.ShapeDtypeStruct((b, c, hout, wout), img.dtype),
        interpret=interpret,
    )(jnp.transpose(img, (0, 3, 1, 2)), r_h.astype(jnp.float32),
      r_w.T.astype(jnp.float32))
    return jnp.transpose(out, (0, 2, 3, 1))
