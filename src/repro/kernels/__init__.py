"""Pallas TPU kernels: compiled on the TPU, interpreted on any other backend."""

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the ``interpret=None`` kernel default from the JAX backend.

    Compiled mode is enabled on TPU only: these kernels accumulate carries in
    output blocks revisited across grid steps, which relies on Mosaic's
    SEQUENTIAL grid execution — under the GPU (Triton) backend grid instances
    run as parallel blocks and the carry would race, so GPU stays on the
    interpreter until the kernels grow cross-block reductions. ``None`` means
    "infer from :func:`jax.default_backend`"; explicit booleans pass through
    so tests and benchmarks can force either mode.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
