"""Bucket padding and pad-artifact repair, shared by every padded dispatch.

Three call sites stage mixed-shape images into one fixed compiled batch
shape: the sharded executor's bucketed rounds
(:meth:`repro.pipeline.executor.ShardedPHExecutor.load_round`), the
engine's mixed-shape :meth:`repro.ph.PHEngine.run_batch`, and the serving
daemon's coalescing tick (:class:`repro.serving.PHServer`).  They all rely
on the same exactness argument (src/repro/ph/README.md "Padding
correctness"), stated here for the superlevel filtration with the
sublevel dual in parentheses:

* pad pixels are filled with the *inert extreme* of the filtration — the
  dtype minimum / ``-inf`` under superlevel (``+inf`` under sublevel, where
  the analysis keeps *low* values) — so under a finite per-image Variant-2
  threshold they are **provably inert**: below (above) every threshold,
  they produce no births, no candidates, and no merges;
* when no filter level supplies a threshold, the **image minimum**
  (maximum) is an exact substitute: ``pixhomology`` keeps pixels
  ``>= truncate_value`` (``<= t``), so a threshold at the extreme excludes
  nothing real while still excluding every pad pixel (the essential death
  it clips is restored by the fixup below) — this is what lets VANILLA
  requests share padded buckets;
* the two residual artifacts are repaired host-side from load-time
  metadata: flat indices are strided by the bucket width instead of the
  image width (a pure remap, row order among real pixels is preserved by
  right/bottom padding — filtration-invariant), and the essential class
  dies at the pad fill instead of the recorded image minimum (maximum).

Historical bug this layout fixes: the fixup used to *assume* the pad fill
is the global minimum, so an image whose true minimum sat in a padded
margin row — or any sublevel request — silently restored the wrong death.
Every function now takes the filtration and records the matching extreme.

:func:`pad_fixup` captures the metadata at staging time;
:func:`unpad_diagram` applies the repair, making padded diagrams
bit-identical to unpadded per-image runs (incl. ``p_birth``/``p_death``).
"""
from __future__ import annotations

import numpy as np

from repro.core import Diagram
from repro.core.packed_keys import resolve_filtration


def pad_fill_value(dtype, filtration: str = "superlevel"):
    """The inert fill for pad pixels of ``dtype`` under ``filtration``:
    below everything for superlevel, above everything for sublevel."""
    dtype = np.dtype(dtype)
    resolve_filtration(filtration)
    if filtration == "sublevel":
        if not np.issubdtype(dtype, np.floating):
            raise ValueError(
                f"filtration='sublevel' requires a floating dtype, "
                f"got {dtype}")
        return np.inf
    return -np.inf if np.issubdtype(dtype, np.floating) \
        else np.iinfo(dtype).min


def pad_threshold(img: np.ndarray, threshold: float | None,
                  filtration: str = "superlevel") -> float:
    """The finite threshold a padded dispatch of ``img`` runs under.

    An explicit finite ``threshold`` passes through; otherwise the image
    extreme stands in — the minimum under superlevel, the maximum under
    sublevel (exact — see the module docstring).  Raises when no finite
    threshold separating the image from the pad fill exists (an integer
    image whose minimum sits at the dtype minimum is indistinguishable
    from its own padding).
    """
    if threshold is not None and np.isfinite(threshold):
        return float(threshold)
    fill = pad_fill_value(img.dtype, filtration)
    if filtration == "sublevel":
        t = float(img.max())
        bad = not np.isfinite(t) or t >= fill
    else:
        t = float(img.min())
        bad = not np.isfinite(t) or t <= fill
    if bad:
        raise ValueError(
            f"cannot pad image: no finite threshold separating the pad "
            f"fill {fill!r} from the image extreme {t!r}; pass an "
            f"explicit truncate_value or use exact-shape batches")
    return t


def pad_fixup(img: np.ndarray,
              filtration: str = "superlevel") -> tuple[int, int, float, int]:
    """Repair metadata of one to-be-padded image: ``(H, W, ext_val,
    ext_idx)`` with the index flat in the *unpadded* frame.  The extreme
    is the essential death point of the filtration — the global minimum
    under superlevel, the global maximum under sublevel; ``argmin`` /
    ``argmax`` return the first (lowest flat index) occurrence, exactly
    the pixel the elder rule's ``(value, index)`` total order picks."""
    resolve_filtration(filtration)
    h, w = img.shape
    ei = int(img.argmax() if filtration == "sublevel" else img.argmin())
    return (h, w, img.reshape(-1)[ei], ei)


def pad_image(img: np.ndarray, bucket: tuple[int, int],
              filtration: str = "superlevel") -> np.ndarray:
    """Right/bottom-pad ``img`` to ``bucket`` with the inert fill (row
    order among real pixels is preserved, so :func:`unpad_diagram`'s
    stride remap is exact)."""
    h, w = img.shape
    hb, wb = bucket
    if (h, w) == (hb, wb):
        return img
    if h > hb or w > wb:
        raise ValueError(f"image {img.shape} exceeds bucket {bucket}")
    out = np.full((hb, wb), pad_fill_value(img.dtype, filtration), img.dtype)
    out[:h, :w] = img
    return out


def unpad_diagram(d: Diagram, fixup, bucket: tuple[int, int]) -> Diagram:
    """Undo the two pad artifacts of a bucket-padded image's diagram.

    ``fixup = (H, W, ext_val, ext_idx)`` from :func:`pad_fixup` (already
    filtration-aware: the recorded extreme *is* the essential death point
    of whichever filtration staged it).  Remapping flat indices from
    stride ``Wb`` to stride ``W`` and restoring the essential death makes
    the diagram bit-identical to the unpadded whole-image run.  Row 0 is
    the essential class under both filtrations (the elder root sorts
    first in the internal key order).
    """
    h, w, env, eni = fixup
    wb = bucket[1]

    def remap(p):
        p = p.copy()
        valid = p >= 0
        p[valid] = (p[valid] // wb) * w + (p[valid] % wb)
        return p

    p_birth = remap(d.p_birth)
    p_death = remap(d.p_death)
    death = d.death.copy()
    if int(d.count) > 0:        # row 0 is the essential class
        death[0] = env
        p_death[0] = eni
    return Diagram(d.birth, death, p_birth, p_death,
                   d.count, d.n_unmerged, d.overflow, d.n_candidates)
