"""Jit'd public wrappers for the 3x3 pooling ops with backend dispatch.

``use_pallas``/``interpret`` resolve through
:func:`repro.kernels.backend.resolve`: the compiled Pallas kernel on TPU,
the pure-jnp reference elsewhere, the Pallas interpreter only on
``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.maxpool import ref


def _pallas(use_pallas, interpret):
    """``None`` for the reference, else the kernel's ``interpret`` flag."""
    impl = backend.resolve("maxpool", use_pallas, interpret)
    return None if impl == backend.XLA else impl == backend.INTERPRET


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def maxargmaxpool3x3(x: jnp.ndarray, *, use_pallas: bool | None = None,
                     interpret: bool = False):
    """Fused 3x3 (maxpool, argmaxpool), stride 1, pad 1.

    Returns (max: x.dtype, argmax: int32 flat index), shapes == x.shape.
    """
    interp = _pallas(use_pallas, interpret)
    if interp is not None:
        from repro.kernels.maxpool import kernel
        return kernel.maxargmaxpool3x3(x, interpret=interp)
    return ref.maxargmaxpool3x3(x)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def maxpool3x3(x: jnp.ndarray, *, use_pallas: bool | None = None,
               interpret: bool = False):
    interp = _pallas(use_pallas, interpret)
    if interp is not None:
        from repro.kernels.maxpool import kernel
        return kernel.maxpool3x3(x, interpret=interp)
    return ref.maxpool3x3(x)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def minpool3x3(x: jnp.ndarray, *, use_pallas: bool | None = None,
               interpret: bool = False):
    interp = _pallas(use_pallas, interpret)
    if interp is not None:
        from repro.kernels.maxpool import kernel
        return kernel.minpool3x3(x, interpret=interp)
    return ref.minpool3x3(x)
