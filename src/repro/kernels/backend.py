"""The one backend decision shared by every Pallas kernel package.

Each package (``maxpool``, ``ph_phase_a``, ``ph_phase_c``,
``ph_distance``) ships a Pallas kernel and its bit-identical XLA
reference; :func:`resolve` picks which one runs from the caller's
``use_pallas``/``interpret`` toggles and the backend:

========================  ==========  ==========  ==========
``use_pallas``            TPU         other       ``interpret=True``
========================  ==========  ==========  ==========
``None`` (default)        pallas      xla         interpret
``True``                  pallas      error       interpret
``False``                 xla         xla         xla
========================  ==========  ==========  ==========

Interpret mode runs only when a caller asks for it: a compiled kernel
that is forced where no TPU exists is an error, never a silent switch
to the interpreter.  A kernel whose body Mosaic cannot lower runs its
XLA reference on TPU instead, under the name :data:`XLA`, so the choice
is visible in ``PHEngine.plan_stats()``; :data:`NO_MOSAIC` lists them.
"""
from __future__ import annotations

import jax
import numpy as np

PALLAS, INTERPRET, XLA = "pallas", "interpret", "xla"

# Kernels on the packed-key path are traced under x64, where a bare ``0``
# in a BlockSpec index map or a loop counter lowers as i64, which Mosaic
# refuses; kernels use this int32 zero instead.
I32_ZERO = np.int32(0)

# Kernels whose Pallas body Mosaic refuses: ``ph_distance`` sorts inside
# the kernel (``jnp.sort`` has no Pallas TPU lowering).
NO_MOSAIC = frozenset({"ph_distance"})


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(kernel: str, use_pallas: bool | None = None,
            interpret: bool = False) -> str:
    """The implementation ``kernel`` runs: :data:`PALLAS`,
    :data:`INTERPRET` or :data:`XLA` (see the module table)."""
    if use_pallas is False:
        return XLA
    if interpret:
        return INTERPRET
    if on_tpu():
        return XLA if kernel in NO_MOSAIC else PALLAS
    if use_pallas:
        raise ValueError(
            f"use_pallas=True for {kernel} needs a TPU backend (found "
            f"{jax.default_backend()!r}); pass interpret=True to run the "
            f"kernel in the Pallas interpreter")
    return XLA
