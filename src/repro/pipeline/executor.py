"""Executor layer: sharded batched PixHomology over the device mesh.

One SPMD program per round: a (M, Hb, Wb) image batch sharded over the data
axes, vmapped PixHomology per device (the paper's ``process_image`` map).
Images are *generated/loaded per executor* (Variant 1 ``load_self``): the
driver passes image metadata, each host materializes only its shard — and
for oversized images only its halo-padded *tiles*
(:meth:`ShardedPHExecutor.load_self_tiled`, windowed loading through
:class:`repro.data.astro.AstroImage`).

Heterogeneous rounds: a round's images share one padded bucket shape
``(Hb, Wb)``; smaller images are padded with ``-inf``.  Under the finite
per-image Variant-2 threshold the pipeline always supplies for padded
rounds, the pad pixels are provably inert — they are below every
threshold, so they produce no births, no candidates, and no merges —
leaving exactly two pad artifacts, both repaired host-side in
:meth:`ShardedPHExecutor.run_staged`:

* flat pixel indices are laid out with stride ``Wb`` instead of ``W``
  (row-order among real pixels is preserved, so a pure index remap
  suffices), and
* the essential class dies at the pad minimum (``-inf``) instead of the
  image minimum, which the loader records at generation time.

The padding/repair primitives live in :mod:`repro.pipeline.padding` and
are shared with ``PHEngine.run_batch``'s mixed-shape path and the serving
daemon's coalescing tick.

The compiled sharded program comes from the engine's plan cache
(:meth:`repro.ph.PHEngine.sharded_plan`); this module only moves data and
applies the engine's overflow auto-regrow policy round by round.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import Diagram
from repro.data import astro
from repro.ph.config import FilterLevel
from repro.ph import trace
from repro.ph.engine import PHEngine, threshold_dtype
from repro.ph.overlap import PendingResult
from repro.pipeline.padding import pad_fill_value, pad_fixup, unpad_diagram
from repro.pipeline.scheduler import BucketRound, ImageMeta


@dataclasses.dataclass
class StagedRound:
    """Device-staged inputs of one scheduled round (built by
    :meth:`ShardedPHExecutor.load_round`, possibly on the driver's
    prefetch thread while the previous round computes).

    The host copies are retained past staging: donated device buffers
    are consumed by their dispatch, so the rare overflow replay
    re-stages from ``host_batch`` instead of regenerating images."""

    rnd: BucketRound
    batch: Any = None           # whole rounds: (M, Hb, Wb) device array
    tvals: Any = None           # whole rounds: (M,) device thresholds
    fixups: list | None = None  # per entry: None | (H, W, min_val, min_idx)
    tiles: Any = None           # tiled rounds: repro.core.tiling.StagedTiles
    threshold: float | None = None  # tiled rounds: Variant-2 threshold
    host_batch: Any = None      # whole rounds: pinned host (M, Hb, Wb)
    host_tvals: Any = None      # whole rounds: host (M,) thresholds


class ShardedPHExecutor:
    """Engine-backed executor pool over a device mesh.

    Capacities start at the engine config's values and, with
    ``auto_regrow`` on, stick at any regrown size for subsequent rounds
    and runs (the engine's regrow memo: an overflow in round r means
    round r+1 likely overflows too).
    """

    def __init__(self, engine: PHEngine, ctx, *, image_size: int = 512):
        if not isinstance(engine, PHEngine):
            raise TypeError(f"engine must be a PHEngine, "
                            f"got {type(engine).__name__}")
        self.engine = engine
        self.ctx = ctx
        self.image_size = image_size
        self._spec = NamedSharding(ctx.mesh, P(ctx.dp_axes, None, None))
        self._tspec = NamedSharding(ctx.mesh, P(ctx.dp_axes))
        # Variant-3 costs measured from actually-loaded images, keyed by
        # (id, shape) — the same id can appear at different sizes across
        # runs of a reused pool; they override the schedule-time estimate
        # on re-scheduling (retries).
        self._measured_costs: dict[tuple, float] = {}

    @property
    def num_executors(self) -> int:
        return self.ctx.dp_size

    # -- scheduling knobs (read by the driver) -----------------------------

    @property
    def bucket_rounding(self) -> str:
        return self.engine.config.bucket_rounding

    @property
    def pad_ok(self) -> bool:
        """Padded (mixed-shape) rounds need a finite Variant-2 threshold
        to keep the pad pixels out of the analysis — VANILLA runs use
        exact-shape buckets instead."""
        return self.engine.config.filter_level is not FilterLevel.VANILLA

    @property
    def prefetch_rounds(self) -> int:
        return self.engine.config.prefetch_rounds

    @property
    def max_tile_pixels(self) -> int | None:
        t = self.engine.config.tile
        return t.max_tile_pixels if t is not None else None

    @property
    def overlap(self):
        """The engine's effective overlap policy (the driver reads
        ``enabled`` / ``staging_depth`` / ``async_harvest``)."""
        return self.engine.overlap_spec()

    # -- Variant-3 costs ---------------------------------------------------

    def estimate_costs(self, metas) -> dict[int, float]:
        """Schedule-time costs: the executor-measured cost where a load
        already happened (Variant 2/3's per-image pass), else the
        render-free star-stream estimate.  Also the earliest point every
        image spec reaches this executor, so shapes it cannot load are
        rejected here instead of mid-run on the prefetch thread."""
        out = {}
        for meta in metas:
            _require_square(meta.shape)
            got = self._measured_costs.get((meta.image_id, meta.shape))
            out[meta.image_id] = got if got is not None else \
                astro.estimate_cost_from_id(meta.image_id, meta.shape[0])
        return out

    # -- Variant-1 loading -------------------------------------------------

    def _load_one(self, meta: ImageMeta):
        """Generate one whole (sub-bucket-size) image + its threshold and
        measured cost.  (On a real cluster each process runs this only for
        its addressable slots.)"""
        h, _ = _require_square(meta.shape)
        img = astro.generate_image(meta.image_id, h)
        # Engine-derived so the threshold statistic mirrors correctly
        # under filtration='sublevel' (None under VANILLA either way).
        t = self.engine.auto_threshold(img)
        self._measured_costs[(meta.image_id, meta.shape)] = \
            astro.estimate_cost(img, self.engine.config.filter_level)
        return img, t

    def load_round(self, rnd: BucketRound) -> StagedRound:
        """Stage one scheduled round on device (thread-safe: the driver
        calls this on a background loader thread for round r+1 while round
        r computes).  Recorded as a ``ph.load`` span."""
        with trace.span("ph.load", kind=rnd.kind, images=len(rnd.entries)):
            if rnd.kind == "tiled":
                assert len(rnd.entries) == 1
                return self.load_self_tiled(rnd, rnd.entries[0][1])
            return self._stage_round(self._build_host_round(rnd))

    def _build_host_round(self, rnd: BucketRound) -> StagedRound:
        """Host half of staging: generate, cast, and pad one round into a
        pinned (M, Hb, Wb) host batch plus its (M,) thresholds.

        Pure-CPU by construction: the dtype cast runs through
        ``cast_input_host`` (numpy), so building a round allocates **no**
        device buffer — a regression test monkeypatches ``device_put``
        to assert exactly that.  The one H2D transfer for the whole
        round happens in :meth:`_stage_round`."""
        m = self.num_executors
        hb, wb = rnd.shape
        filt = self.engine.config.filtration
        inert = np.inf if filt == "sublevel" else -np.inf
        bdt = self.engine.cast_input_host(np.zeros((), np.float32)).dtype
        batch = np.full((m, hb, wb), pad_fill_value(bdt, filt), bdt)
        tvals = np.full((m,), inert, np.dtype(threshold_dtype(bdt)))
        fixups: list = [None] * len(rnd.entries)
        for k, (slot, meta) in enumerate(rnd.entries):
            img, t = self._load_one(meta)
            # The config dtype cast happens here, per image, so the pad
            # fixup below observes exactly the values the compute sees
            # (a lossy cast can move the argmin between near-min pixels).
            img = self.engine.cast_input_host(img)
            h, w = img.shape
            if (h, w) != (hb, wb):
                if t is None:
                    raise ValueError(
                        "padded round without a finite threshold (the "
                        "scheduler must use exact buckets when pad_ok is "
                        "False)")
                batch[slot, :h, :w] = img
                tvals[slot] = t
                fixups[k] = pad_fixup(img, filt)
            else:
                batch[slot] = img
                tvals[slot] = inert if t is None else t
        filled = {slot for slot, _ in rnd.entries}
        src = rnd.entries[0][0]
        for s in range(m):          # pad free slots: repeat a staged image
            if s not in filled:
                batch[s] = batch[src]
                tvals[s] = tvals[src]
        return StagedRound(rnd, fixups=fixups, host_batch=batch,
                           host_tvals=tvals)

    def _stage_round(self, staged: StagedRound) -> StagedRound:
        """Device half of staging: the round's batch **and** thresholds
        go up in one fused ``device_put`` (a single transfer per round,
        not a second tiny put for the scalars — the bench counts
        ``h2d_transfers`` per round to hold this at one)."""
        with trace.span("ph.stage"):
            staged.batch, staged.tvals = jax.device_put(
                (staged.host_batch, staged.host_tvals),
                (self._spec, self._tspec))
        self.engine.overlap_counters.bump("h2d_transfers")
        return staged

    def load_self_tiled(self, rnd: BucketRound,
                        meta: ImageMeta) -> StagedRound:
        """Variant-1 ``load_self`` for tiles: stage an oversized image as
        device-resident halo tiles through the windowed
        :class:`repro.data.astro.AstroImage` provider — no code path here
        (or below) materializes the full frame on any host."""
        h, _ = _require_square(meta.shape)
        provider = astro.AstroImage(meta.image_id, h)
        t = self.engine.provider_threshold(provider)
        with trace.span("ph.stage"):
            tiles = self.engine.stage_tiles(provider, ctx=self.ctx)
        return StagedRound(rnd, tiles=tiles, threshold=t)

    def load_self(self, image_ids) -> tuple[np.ndarray, np.ndarray, dict]:
        """Variant 1 for a homogeneous id list (all at ``image_size``):
        executors materialize their own images; also computes the
        Variant-2 thresholds and Variant-3 costs.  The bucketed pipeline
        stages through :meth:`load_round`; this remains for direct
        ``run_round`` use."""
        size = self.image_size
        inert = np.inf if self.engine.config.filtration == "sublevel" \
            else -np.inf
        imgs, thresholds, costs = [], [], {}
        for i in image_ids:
            img, t = self._load_one(ImageMeta(int(i), (size, size)))
            imgs.append(img)
            thresholds.append(inert if t is None else t)
            costs[i] = self._measured_costs[(int(i), (size, size))]
        return np.stack(imgs), np.asarray(thresholds, np.float32), costs

    # -- round execution ---------------------------------------------------

    def run_staged(self, staged: StagedRound) -> dict[int, Diagram]:
        """Run one staged round; returns per-image host diagrams with the
        pad artifacts repaired (index remap + essential death).

        Synchronous: dispatch *and* the blocking result readback happen
        on the calling thread (one dispatch-path sync — counted).  The
        overlapped driver calls :meth:`begin_staged` instead and resolves
        on its harvest thread.  The resolution is a ``ph.harvest`` span."""
        self.engine.overlap_counters.bump("dispatch_syncs")
        pending = self.begin_staged(staged)
        with trace.span("ph.harvest"):
            return pending.resolve()

    def begin_staged(self, staged: StagedRound) -> PendingResult:
        """Dispatch one staged round without blocking for its results.

        Whole rounds launch the sharded program now (with D2H streaming
        under ``overlap.async_overflow``) and defer the overflow check,
        the rare regrow replay, and the pad repair into the returned
        :class:`PendingResult`; tiled rounds defer the whole tiled/delta
        call (its dispatch runs wherever ``resolve()`` does — the
        driver's harvest thread — while the driver stages later rounds).
        ``resolve()`` returns exactly :meth:`run_staged`'s per-image
        dict, bit-identically — it is the same code on another thread."""
        rnd = staged.rnd
        if rnd.kind == "tiled":
            meta = rnd.entries[0][1]
            tiles, threshold = staged.tiles, staged.threshold

            def tiled_finish():
                res = self._tiled(tiles, threshold)
                return {meta.image_id: jax.tree.map(np.asarray,
                                                    res.diagram)}

            return PendingResult(tiled_finish)

        finish = self._begin_sharded(staged)

        def whole_finish():
            diags = finish()
            out: dict[int, Diagram] = {}
            for k, (slot, meta) in enumerate(rnd.entries):
                d = Diagram(*(np.asarray(x[slot]) for x in diags))
                if staged.fixups[k] is not None:
                    d = unpad_diagram(d, staged.fixups[k], rnd.shape)
                out[meta.image_id] = d
            # On the open ph.harvest span: each real frame's swept
            # candidates, in slot order, and the chips the round held.
            slots = sorted(slot for slot, _ in rnd.entries)
            trace.note(candidates=[int(diags.n_candidates[s])
                                   for s in slots],
                       chips=self.num_executors)
            return out

        return PendingResult(whole_finish)

    def _tiled(self, image, threshold):
        """One tiled-image dispatch: through the engine's delta path when
        ``config.delta`` is enabled (bit-identical; retried/resumed rounds
        of the same frame become cache hits instead of recomputes —
        ``DiagramCache.put`` replaces in place, so a retry never
        double-inserts), else the sharded ``run_tiled`` path."""
        eng = self.engine
        dspec = eng.config.delta
        if dspec is not None and dspec.enabled:
            return eng.run_delta(image, threshold)
        return eng.run_tiled(image, threshold, ctx=self.ctx)

    def _begin_sharded(self, staged: StagedRound):
        """Launch one sharded whole-image dispatch with the engine's
        regrow deferred: returns ``finish() -> host diagram tree``.

        Under donation the round's device batch buffer is consumed by
        its dispatch; the rare overflow replay re-stages the batch from
        the retained host copy (thresholds are not donated — attempt 0's
        device array is reused)."""
        eng = self.engine
        batch, tvals = staged.batch, staged.tvals
        shape, dtype = batch.shape, batch.dtype
        n = shape[1] * shape[2]
        donate = eng.donate_batched()
        calls = [0]

        def dispatch(mf, mc):
            plan = eng.sharded_plan(self.ctx, shape, dtype, mf, mc,
                                    donate=donate)
            xb = batch
            if donate and calls[0]:
                eng.overlap_counters.bump("donation_replays")
                eng.overlap_counters.bump("h2d_transfers")
                xb = jax.device_put(staged.host_batch, self._spec)
            calls[0] += 1
            with self.ctx.mesh:
                return plan(xb, tvals)

        _, finish = eng.begin_regrow(
            dispatch, lambda d: bool(np.any(np.asarray(d.overflow))),
            n, "sharded", memo_key=("sharded", shape, str(dtype)),
            stream=eng._stream_results())

        def finish_host():
            diags, _ = finish()
            return jax.tree.map(np.asarray, diags)

        return finish_host

    def run_round(self, images: np.ndarray, thresholds: np.ndarray):
        """images: (M, H, W) with M == num_executors (padded by caller).

        Images larger than the engine's ``TileSpec.max_tile_pixels`` budget
        are transparently routed through the halo-tiled path: instead of one
        whole image per executor, each image spans the mesh tile-by-tile
        (the scenario the whole-image design cannot serve).  The bucketed
        pipeline schedules such images as their own tile-grid rounds; this
        batch-shaped entry point remains for direct use.
        """
        eng = self.engine
        if eng.should_tile(images.shape[1] * images.shape[2]):
            return self._run_round_tiled(images, thresholds)
        host = eng.cast_input_host(images)
        staged = self._stage_round(StagedRound(
            None, host_batch=host,
            host_tvals=np.asarray(thresholds,
                                  np.dtype(threshold_dtype(host.dtype)))))
        eng.overlap_counters.bump("dispatch_syncs")
        return self._begin_sharded(staged)()

    def _run_round_tiled(self, images: np.ndarray, thresholds: np.ndarray):
        """Oversized-image round: one image at a time, tiles spanning the
        mesh's data axes (regrow and plan caching live in ``run_tiled``)."""
        # Rounds may repeat identical rows (short-round padding, duplicate
        # datasets); a full tiled run per duplicate would be pure waste, so
        # every (threshold, image) is computed once per round — any
        # identical row reuses the first result, wherever it appears.
        seen: dict[tuple, int] = {}
        diags: list[Diagram] = []
        for i in range(images.shape[0]):
            key = (float(thresholds[i]),
                   hashlib.sha1(np.ascontiguousarray(
                       images[i]).tobytes()).hexdigest())
            dup = seen.get(key)
            if dup is not None and np.array_equal(images[i], images[dup]):
                diags.append(diags[dup])
                continue
            seen[key] = i
            diags.append(jax.tree.map(
                np.asarray,
                self._tiled(images[i], float(thresholds[i])).diagram))
        # Per-image regrow can leave different diagram capacities; pad the
        # rows to the round maximum before stacking into the (M, F) layout
        # a batched consumer expects.
        f = max(d.birth.shape[0] for d in diags)

        sublevel = self.engine.config.filtration == "sublevel"

        def padded(d: Diagram) -> Diagram:
            extra = f - d.birth.shape[0]
            if extra == 0:
                return d
            # Match the core's own pad rows: -inf under superlevel,
            # +inf in sublevel user space (diagrams negate on the way out).
            fill = (-np.inf if np.issubdtype(d.birth.dtype, np.floating)
                    else np.iinfo(d.birth.dtype).min)
            if sublevel:
                fill = -fill
            return Diagram(
                np.concatenate([d.birth, np.full(extra, fill,
                                                 d.birth.dtype)]),
                np.concatenate([d.death, np.full(extra, fill,
                                                 d.death.dtype)]),
                np.concatenate([d.p_birth, np.full(extra, -1, np.int32)]),
                np.concatenate([d.p_death, np.full(extra, -1, np.int32)]),
                d.count, d.n_unmerged, d.overflow, d.n_candidates)

        return jax.tree.map(lambda *xs: np.stack(xs), *map(padded, diags))


def _require_square(shape) -> tuple[int, int]:
    """The synthetic astro loader only renders square frames; reject
    rectangles before they are scheduled (the scheduler itself is
    shape-generic — a different pool may well accept them)."""
    h, w = shape
    if h != w:
        raise ValueError(f"astro frames are square, got {tuple(shape)}")
    return h, w


