"""Dispatch + whole-batch driver for the diagram-distance kernels.

Two public entry points:

* :func:`pairwise_distances` — the (B, B) matrix pair reduction over
  pre-built projection/profile tables, routed to the Pallas kernel
  (``interpret=True`` anywhere) or the bit-identical XLA reference.  The
  kernel sorts inside its body, which Mosaic cannot lower, so on TPU
  the XLA reference is the implementation
  (:data:`repro.kernels.backend.NO_MOSAIC`).

* :func:`diagram_distances` — the whole-batch driver: capacity-padded
  diagram arrays in, ``(sw, bn)`` matrices out.  The preparation stages
  (projection tables, persistence profiles) are shared XLA code from
  ``ref`` whichever backend reduces the pairs, so backend choice cannot
  perturb a single input bit of the reduction.

NaN policy matches the engine boundary: diagram values are checked
host-side by :func:`repro.core.packed_keys.check_finite` with
``allow_inf=True`` — pad rows legitimately carry the ±inf sentinels of
their filtration, but a NaN birth/death cannot be ordered, projected,
or profiled, and fails fast here instead of silently poisoning a row of
the matrix.  Inside a jit trace the check is a no-op (tracers pass
through); ``PHEngine.distance_matrix`` re-checks its host inputs.
"""
from __future__ import annotations

from repro.core.packed_keys import check_finite
from repro.kernels import backend
from repro.kernels.ph_distance import kernel, ref


def pairwise_distances(pts, diag, prof, *, use_pallas: bool | None = None,
                       interpret: bool = False):
    """Pair-grid ``(sw, bn)`` matrices, Pallas or XLA backend, chosen by
    :func:`repro.kernels.backend.resolve` (the XLA reference on every
    compiled backend; ``interpret=True`` runs the kernel in the Pallas
    interpreter, CI's parity path).
    """
    impl = backend.resolve("ph_distance", use_pallas, interpret)
    if impl == backend.XLA:
        return ref.distance_matrix(pts, diag, prof)
    return kernel.distance_matrix(pts, diag, prof, interpret=True)


def diagram_distances(birth, death, p_birth, *, n_dirs: int = 16,
                      merge_keys: str = "rank", width: int = 2,
                      use_pallas: bool | None = None,
                      interpret: bool = False):
    """Distance matrices of a batch of capacity-padded diagrams.

    ``birth``/``death``: (B, F) float arrays; ``p_birth``: (B, F) int32
    with -1 on pad rows (the :class:`repro.core.pixhomology.Diagram`
    layout, stacked).  Returns ``(sw, bn)``, both (B, B): sliced
    Wasserstein and the bottleneck lower bound — see ``ref`` for the
    definitions and the capacity-pad inertness argument.
    """
    if birth.ndim != 2:
        raise ValueError(
            f"diagram_distances expects stacked (B, F) diagrams, got "
            f"shape {tuple(birth.shape)}")
    check_finite(birth, where="diagram births", allow_inf=True)
    check_finite(death, where="diagram deaths", allow_inf=True)
    pts, diag = ref.diagram_projections(birth, death, p_birth,
                                        n_dirs=n_dirs)
    prof = ref.persistence_profiles(birth, death, p_birth,
                                    merge_keys=merge_keys, width=width)
    return pairwise_distances(pts, diag, prof, use_pallas=use_pallas,
                              interpret=interpret)
