"""Production meshes (functions, not module constants: importing this module
never touches jax device state).

Target: TPU v5e pods.  Single pod = 256 chips as (16, 16) ("data", "model");
multi-pod = 2 pods as (2, 16, 16) ("pod", "data", "model") — `pod` is pure
data parallelism (one DCN gradient all-reduce per step).

Every mesh is built with Auto axes: ``jax.make_mesh`` defaults to Explicit
axes, under which the ``with_sharding_constraint`` calls of the tiled PH
path (``distributed.sharding.constrain``) are rejected.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.distributed.context import DistContext


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = len(jax.devices())
    need = 512 if multi_pod else 256
    if n < need:  # reduced test environments (REPRO_DRYRUN_DEVICES): shrink
        shape = (2, 2, 2) if multi_pod else (2, 4)
        if n < (8 if multi_pod else 8):
            shape = (1, 1, 1) if multi_pod else (1, 1)
    return _auto_mesh(shape, axes)


def make_context(*, multi_pod: bool = False) -> DistContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = ("pod", "data") if multi_pod else ("data",)
    return DistContext(mesh=mesh, dp_axes=dp, tp_axis="model")


def make_small_context(data: int = 1, model: int = 1) -> DistContext:
    """Small mesh over however many (host) devices exist — tests/examples."""
    mesh = _auto_mesh((data, model), ("data", "model"))
    return DistContext(mesh=mesh, dp_axes=("data",), tp_axis="model")


def auto_context() -> DistContext:
    """Context over whatever devices exist: one data axis across all local
    devices, model axis 1 (the PH pipeline's default executor mesh)."""
    from repro.distributed.context import single_device_ctx
    n = len(jax.devices())
    return make_small_context(data=n, model=1) if n > 1 \
        else single_device_ctx()
