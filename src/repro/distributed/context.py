"""Distribution context threaded through model code (mesh + axis roles)."""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, PartitionSpec as P

def shard_map_compat(fn, **kwargs):
    """``jax.shard_map`` with replication checking off."""
    kwargs.setdefault("check_vma", False)
    return jax.shard_map(fn, **kwargs)


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: Mesh
    dp_axes: tuple[str, ...] = ("data",)   # batch axes (pod + data)
    tp_axis: str | None = "model"          # tensor/expert-parallel axis

    def axis_size(self, name: str | None) -> int:
        if name is None:
            return 1
        return self.mesh.shape[name]

    @property
    def dp_size(self) -> int:
        return int(
            __import__("math").prod(self.mesh.shape[a] for a in self.dp_axes))

    def batch_spec(self, ndim: int) -> P:
        """(B, ...) activations: batch over dp axes, rest replicated."""
        return P(self.dp_axes, *([None] * (ndim - 1)))


def single_device_ctx() -> DistContext:
    """1x1 ("data","model") mesh for smoke tests and CPU examples."""
    dev = jax.devices()[0]
    import numpy as np
    mesh = Mesh(np.array([dev]).reshape(1, 1), ("data", "model"))
    return DistContext(mesh=mesh, dp_axes=("data",), tp_axis="model")
