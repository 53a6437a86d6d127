"""Packed (value, index) merge keys: the rank-free phase-C total order.

Phase C used to key every merge decision on ``total_order_rank`` — a
full-image stable argsort whose cost dominates end-to-end CPU time once
phases A/B are fused (BENCH_core.json: ~4.3 s of ~5 s at 2k²).  But a rank
is just *one* order-isomorphic encoding of the strict total order
``(value, flat_index)``; this module provides another that needs no sort:

* :func:`monotone_key32` bit-casts a <= 32-bit value to a sign-corrected
  monotone ``int32`` — ``key(a) < key(b)`` iff ``a < b`` and
  ``key(a) == key(b)`` iff the backend's own comparisons call them equal
  (signed zeros are canonicalized first, so ``-0.0`` and ``+0.0`` share a
  key exactly like they tie under a stable argsort);
* :func:`pack_keys` packs ``(key32 << 32) | (flat_index + 1)`` into an
  ``int64`` that is order-isomorphic to the full ``(value, index)`` order.
  The ``+1`` reserves low word 0, so :data:`int64` min is a sentinel
  strictly below every real key even for full-range ``int32``/``uint32``
  images; :func:`packed_index` recovers the index (and maps the sentinel
  to -1, the usual "no pixel" value).

Every phase-C comparison (candidate ordering, elder selection, Boruvka
best-edge reduction, diagram top-k) consumes these keys exactly where it
consumed ranks, so the two paths are bit-identical
(``tests/test_merge_keys.py``) — only the compiled program changes.

The packed path needs 64-bit integers, which JAX disables by default.
Rather than flipping ``jax_enable_x64`` globally (which would change
default dtypes across the whole process), every public entry point wraps
its **outermost** jit call in :func:`key_scope` (``jax.enable_x64``) —
the scope must cover trace *and* lowering, which is why it cannot live
inside a jitted function.  :func:`resolve_merge_keys` falls back to
``"rank"`` whenever packing cannot be used: > 32-bit dtypes, or a caller
tracing us inside their own jit without the scope active
(results are bit-identical either way; only performance differs).

NaNs are outside the contract: a stable argsort orders every NaN after
+inf while the bit trick orders negative NaNs below -inf.  Images are
filtrations here — NaN pixels are rejected upstream, not ordered.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

MERGE_KEYS = ("packed", "rank")
FILTRATIONS = ("superlevel", "sublevel")

_I64_MIN = np.int64(np.iinfo(np.int64).min)
_LOW32 = np.int64(0xFFFFFFFF)


def resolve_filtration(filtration: str) -> str:
    """Validate a ``filtration`` request (superlevel or sublevel)."""
    if filtration not in FILTRATIONS:
        raise ValueError(f"filtration must be one of {FILTRATIONS}, "
                         f"got {filtration!r}")
    return filtration


def _float_dtype(dt) -> bool:
    dt = jnp.dtype(dt)
    return dt.kind == "f" or dt == jnp.bfloat16


def filtration_view(values, filtration: str):
    """Map values between user space and the internal superlevel order.

    The whole compute stack is written for the superlevel filtration
    (births at maxima, elder-rule merges downward).  A sublevel request
    is exact negation at the boundary: IEEE sign flips are bit-exact and
    order-reversing, so running the unchanged superlevel machinery on
    ``-x`` and negating the resulting diagram values is *bit-identical*
    to ``superlevel(-x)`` — the differential oracle the tests hold every
    path to.  Negation is an involution, so the same function maps both
    directions (image and threshold in, diagram births/deaths out).

    Sublevel needs a floating dtype: negating an integer image overflows
    at the dtype minimum (``-int32.min`` does not exist), so integer
    inputs are rejected with a clear error instead of wrapping silently.
    """
    resolve_filtration(filtration)
    if filtration == "superlevel":
        return values
    if not _float_dtype(values.dtype):
        raise ValueError(
            f"filtration='sublevel' requires a floating dtype (negation "
            f"of {jnp.dtype(values.dtype)} overflows at the minimum); "
            f"cast the image to a float dtype first")
    return -values


def check_finite(values, where: str = "image", *,
                 allow_inf: bool = False):
    """Reject non-finite pixels at a public boundary (shared message).

    Filtrations order pixels; NaN admits no order — the packed bit-cast
    (:func:`monotone_key32`) scatters negative-sign NaNs below ``-inf``
    while a stable argsort puts every NaN after ``+inf``, silently
    corrupting diagrams either way — so NaN is rejected with the same
    error at every public entry point (engine cast, core wrappers,
    packed *and* rank key paths).  ``±inf`` is rejected at the *user*
    boundary (``allow_inf=False``, the engine's ``cast_input_host``): it
    collides with the inert pad/halo sentinels.  The core wrappers pass
    ``allow_inf=True`` because padded/halo-filled frames legitimately
    carry the ``±inf`` fill by the time they reach them.  Subnormals are
    inside the contract: they order correctly under the sign-corrected
    bit-cast and the ``-0.0`` canonicalization keeps key equality
    matching comparison equality.

    Tracers pass through unchecked (a jitted caller's values are
    abstract); concrete device arrays sync once, which is the price of
    the check at an eager boundary.  Returns ``values`` unchanged.
    """
    if isinstance(values, jax.core.Tracer):
        return values
    arr = np.asarray(values)
    if not _float_dtype(arr.dtype):
        return values
    if arr.dtype.kind != "f":          # bfloat16: widen exactly for the test
        arr = arr.astype(np.float32)
    if np.isnan(arr).any():
        raise ValueError(
            f"non-finite pixel(s) in {where}: NaN values cannot be "
            f"ordered by a filtration; mask or clean the image before "
            f"calling")
    if not allow_inf and not np.isfinite(arr).all():
        raise ValueError(
            f"non-finite pixel(s) in {where}: infinite values collide "
            f"with the inert pad sentinels; mask or clean the image "
            f"before calling")
    return values


def packable_dtype(dtype) -> bool:
    """True when ``dtype`` values fit the 32-bit monotone key map."""
    dt = jnp.dtype(dtype)
    if dt.kind in ("i", "u"):
        return dt.itemsize <= 4
    if dt.kind == "f" or dt == jnp.bfloat16:
        return dt.itemsize <= 4
    return False


def _top_level() -> bool:
    """True when no trace is in progress (eager, outermost call)."""
    return jax.core.trace_ctx.is_top_level()


def resolve_merge_keys(requested: str, dtype) -> str:
    """Resolve a ``merge_keys`` request against what can actually run.

    ``"packed"`` degrades to ``"rank"`` (bit-identical, just argsort-keyed)
    when the dtype exceeds 32 bits, or when we are already inside someone
    else's trace without x64 active — entering the scope mid-trace would
    not cover lowering, and tracing int64 ops without it silently
    truncates them.
    """
    if requested not in MERGE_KEYS:
        raise ValueError(f"merge_keys must be one of {MERGE_KEYS}, "
                         f"got {requested!r}")
    if requested == "rank":
        return "rank"
    if not packable_dtype(dtype):
        return "rank"
    if not _top_level() and not jax.config.jax_enable_x64:
        return "rank"
    return "packed"


def key_scope(merge_keys: str):
    """Context manager covering one packed-key trace+lower+execute.

    A no-op for the rank path, when x64 is already on, or when a trace is
    already in progress (the outer caller holds the scope then — entering
    here could not cover lowering anyway).
    """
    if (merge_keys == "packed" and not jax.config.jax_enable_x64
            and _top_level()):
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def assert_key_context(merge_keys: str) -> None:
    """Trace-time guard: packed keys without x64 active would silently
    truncate to int32 — fail loudly instead.  Call from jitted cores."""
    if merge_keys == "packed" and not jax.config.jax_enable_x64:
        raise ValueError(
            "merge_keys='packed' traced without an x64 scope; call through "
            "the public entry points (pixhomology, PHEngine) or wrap the "
            "outermost jit call in repro.core.packed_keys.key_scope")


def key_pad(dtype) -> jnp.ndarray:
    """Sentinel at or below every valid key of ``dtype``.

    ``int64`` packed keys of real pixels never reach int64 min (their low
    word is ``index + 1`` >= 1, since real pixels carry index >= 0);
    ``int32`` ranks are >= 0, so int32 min is below them too — one rule
    serves both encodings.  The one equality case: a tiled *halo fill*
    cell (index -1) whose fill value is the integer dtype's minimum packs
    to exactly this sentinel — callers there already exclude halo cells
    by mask (``& interior``), never by key comparison.
    """
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def key_top(dtype) -> jnp.ndarray:
    """Sentinel >= every valid key of ``dtype`` (directional stencil fill)."""
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def monotone_key32(values: jnp.ndarray) -> jnp.ndarray:
    """Order-isomorphic ``int32`` key of <= 32-bit values (any shape).

    Floats use the sign-corrected bit-cast: non-negative patterns are
    already ascending, negative ones are flipped.  Signed zeros are
    canonicalized through the backend's own equality (``v == 0``), so on
    backends that flush subnormals in comparisons the keys flush with
    them — key equality always matches comparison equality.
    """
    dt = jnp.dtype(values.dtype)
    if dt.kind in ("i", "u"):
        if dt.kind == "u" and dt.itemsize == 4:
            # Full-range uint32: recenter by flipping the top bit.
            return (values ^ jnp.uint32(0x80000000)).view(jnp.int32)
        return values.astype(jnp.int32)
    if not packable_dtype(dt):
        raise ValueError(f"dtype {dt} does not fit 32-bit monotone keys")
    v = values.astype(jnp.float32)
    v = jnp.where(v == 0, jnp.zeros_like(v), v)   # -0.0 ties +0.0
    u = v.view(jnp.uint32)
    return jnp.where(u >> 31 == 1, u ^ jnp.uint32(0x7FFFFFFF), u).view(
        jnp.int32)


def pack_keys(values_flat: jnp.ndarray,
              index_flat: jnp.ndarray | None = None) -> jnp.ndarray:
    """``(monotone_key32(v) << 32) | (index + 1)`` as int64 (flat arrays).

    Order-isomorphic to the strict total order ``(value, index)`` the
    stable-argsort ranks encode — the drop-in phase-C replacement that
    costs one bit-cast instead of a full-image sort.  ``index_flat``
    defaults to the flat position (the whole-image case); the tiled path
    passes *global* pixel indices so per-tile keys stay globally
    comparable.  Cells with index -1 (out-of-frame halo fill) pack low
    word 0: below every real pixel of equal value, above the int64-min
    pad sentinel.
    """
    k32 = monotone_key32(values_flat)
    if index_flat is None:
        index_flat = jnp.arange(values_flat.shape[0], dtype=jnp.int32)
    low = (index_flat.astype(jnp.int64) + 1) & _LOW32
    return (k32.astype(jnp.int64) << 32) | low


def packed_index(keys: jnp.ndarray) -> jnp.ndarray:
    """Recover the flat index from packed keys (pad sentinel maps to -1)."""
    return ((keys & _LOW32) - 1).astype(jnp.int32)


def select_descending(key_flat: jnp.ndarray, mask_flat: jnp.ndarray,
                      k: int, width: int = 2
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-``k`` masked keys in descending order: ``(keys, indices)``.

    Bit-identical to ``top_k(where(mask, key, pad), k)`` over the full
    array — same selected set, same order, valid keys are distinct by
    construction, **including under overflow** (more than ``k`` set
    lanes: the k largest keys are retained, exactly like the rank path's
    full ``top_k``) — but evaluated as a blockwise tournament: each
    round takes the per-block top-k of ``width * k``-wide blocks, so no
    sort ever spans more than ``width * k`` elements (``lax.top_k``
    lowers to a full sort of its operand on CPU; this is how "top-k over
    candidates only" stays true in the compiled HLO).  ``width`` trades
    round count against per-round sort extent (identical results for any
    ``width >= 2`` — every global top-k element survives its block's
    top-k — so it is a pure tuning knob, the one the autotuner picks).
    Lanes beyond the number of set entries return the pad key and
    index -1.
    """
    n = key_flat.shape[0]
    k = min(k, n)
    if width < 2:
        raise ValueError(f"tournament width must be >= 2, got {width}")
    pad = key_pad(key_flat.dtype)
    keys = jnp.where(mask_flat, key_flat, pad)
    ids = jnp.arange(n, dtype=jnp.int32)
    block = width * k
    while keys.shape[0] > block:
        length = keys.shape[0]
        m = -(-length // block)
        extra = m * block - length
        if extra:
            keys = jnp.concatenate(
                [keys, jnp.full(extra, pad, keys.dtype)])
            ids = jnp.concatenate([ids, jnp.full(extra, -1, jnp.int32)])
        top, order = jax.lax.top_k(keys.reshape(m, block), k)
        keys = top.reshape(-1)                       # shrinks: m*k <= L/w + k
        ids = jnp.take_along_axis(ids.reshape(m, block), order,
                                  axis=1).reshape(-1)
    top, order = jax.lax.top_k(keys, k)
    return top, jnp.where(top > pad, ids[order], -1)


def masked_top_k(key_flat: jnp.ndarray, mask_flat: jnp.ndarray,
                 k: int, width: int = 2) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Descending top-``k`` of the masked keys: ``(keys, positions)``.

    The single selection primitive every phase-C site uses: packed int64
    keys route through the blockwise tournament
    (:func:`select_descending`, block extent ``width * k``), dense int32
    ranks through one full-array ``top_k`` (their argsort already
    materialized the order, so there is nothing left to save).  Lanes
    beyond the number of set entries carry the pad key and an
    **in-range** position (clipped to 0) — consumers must mask on
    ``keys > key_pad(...)``, never on the position.
    """
    if key_flat.dtype == jnp.int64:
        top, idx = select_descending(key_flat, mask_flat, k, width)
        return top, jnp.clip(idx, 0)
    masked = jnp.where(mask_flat, key_flat, key_pad(key_flat.dtype))
    return jax.lax.top_k(masked, min(k, key_flat.shape[0]))
