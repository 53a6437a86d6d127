"""PHEngine: the single entry point for PixHomology computation.

The engine owns three things the call sites used to re-implement:

* a **compiled-plan cache** keyed by ``(kind, shape, dtype, capacities,
  config.plan_key())`` — repeated single-image, ``vmap``-batched, and
  ``shard_map``-sharded calls reuse one jitted executable instead of
  re-tracing (every plan carries a trace counter, so tests and benchmarks
  can assert reuse);

* **overflow auto-regrow** — the ``Diagram.overflow`` flag triggers
  re-dispatch at doubled ``max_features``/``max_candidates`` up to a
  configurable ceiling (default: the image pixel count, at which overflow
  is impossible), with per-call :class:`RegrowStats`;

* the **distributed pipeline** — ``run_distributed`` owns the end-to-end
  job: shape-bucketed scheduling of heterogeneous datasets, prefetch
  overlap, work-log fault tolerance, and failure injection all hang off
  the engine.

See ``src/repro/ph/README.md`` for the cache-keying and regrow policy.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Diagram, batched_pixhomology, diagram_to_array, \
    num_candidates as core_num_candidates, pixhomology
from repro.core.packed_keys import check_finite, key_scope, \
    resolve_merge_keys
from repro.distributed.context import shard_map_compat
from repro.kernels import backend
from repro.ph import trace
from repro.ph.config import FilterLevel, OverlapSpec, PHConfig, TileSpec
from repro.ph.overlap import OverlapCounters, PendingResult, start_d2h

# The engine's behavior when the config carries no overlap spec:
# synchronous transfers, no donation — the pre-overlap code path.
_OVERLAP_OFF = OverlapSpec(enabled=False)

# Donating an image batch whose buffer no diagram output can alias is
# intentional (XLA still owns — and may reuse/free early — the donated
# space); the per-compile advisory would otherwise spam every round.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def threshold_dtype(image_dtype):
    """Dtype for Variant-2 thresholds: the image dtype for floats, float32
    for integer images (so fractional thresholds and the -inf "no
    truncation" sentinel are not destroyed by an integer cast; comparisons
    in the core promote)."""
    return image_dtype if jnp.issubdtype(image_dtype, jnp.floating) \
        else jnp.float32


def _abstract(a) -> jax.ShapeDtypeStruct:
    dtype = a.dtype if hasattr(a, "dtype") else np.result_type(a)
    return jax.ShapeDtypeStruct(np.shape(a), dtype)


class Plan:
    """One cached compiled executable plus its trace/call counters.

    ``merge_keys`` records the *resolved* phase-C key encoding; packed
    plans trace, lower, and execute inside the int64
    :func:`repro.core.packed_keys.key_scope` — the scope must wrap the
    outermost jit call, which is exactly what ``__call__``/:meth:`lower`
    are.  ``impls`` records what the plan's program runs: the key
    encoding and, per kernel package it reaches, the resolved backend
    (:func:`repro.kernels.backend.resolve`).

    Thread safety: concurrent submitters (the serving daemon, the hammer
    regression test) may race into one plan.  The *first* call — the one
    that traces — is serialized under the plan lock so two threads cannot
    both pay (and double-count) the trace; once ``traces > 0`` the
    compiled executable is reached without the lock, so steady-state
    calls run concurrently.

    Tracing: every plan registers in :mod:`repro.ph.trace` under ``id``
    and notes that id on the innermost open span of each call; the first
    call keeps the abstract arguments (``avals``) for :meth:`stage_map`.
    They are taken from the call's arguments, not inside the traced
    function, where a ``shard_map`` body sees per-shard shapes.
    """

    __slots__ = ("fn", "key", "traces", "calls", "merge_keys", "impls",
                 "id", "avals", "_lock", "__weakref__")

    def __init__(self, fn: Callable, key: tuple, merge_keys: str = "rank",
                 impls: dict | None = None):
        self.fn = fn
        self.key = key
        self.traces = 0
        self.calls = 0
        self.merge_keys = merge_keys
        self.impls = impls if impls is not None else {}
        self.avals = None
        self._lock = threading.Lock()
        self.id = trace.register_plan(self)

    def __call__(self, *args):
        with self._lock:
            self.calls += 1
            cold = self.traces == 0
        trace.note(plan=self.id)
        if cold:
            with self._lock:
                if self.avals is None:
                    self.avals = jax.tree.map(_abstract, args)
                with key_scope(self.merge_keys):
                    return self.fn(*args)
        with key_scope(self.merge_keys):
            return self.fn(*args)

    def lower(self, *args):
        """``fn.lower(*args)`` under the plan's key scope (dryrun path)."""
        with key_scope(self.merge_keys):
            return self.fn.lower(*args)

    def stage_map(self) -> dict[str, str]:
        """``{HLO instruction name: ph.* stage}`` of this plan's compiled
        program (:func:`repro.ph.trace.stage_map`), for reading a device
        trace by stage.  Compiles the first call's arguments again (a
        persistent-cache hit where the cache is on); empty before the
        first call."""
        if self.avals is None:
            return {}
        return trace.stage_map(self.lower(*self.avals).compile().as_text())


@dataclasses.dataclass(frozen=True)
class RegrowStats:
    """What the overflow auto-regrow loop did for one run."""

    attempts: int                  # re-dispatches performed (0 = first try fit)
    final_max_features: int
    final_max_candidates: int
    overflow: bool                 # residual overflow after the final attempt

    @property
    def regrown(self) -> bool:
        return self.attempts > 0


@dataclasses.dataclass(frozen=True)
class PHResult:
    """Diagram plus the effective configuration that produced it."""

    diagram: Diagram
    config: PHConfig               # capacities reflect any regrow
    regrow: RegrowStats
    # Variant-2 threshold(s) actually applied: a scalar for run(), a (B,)
    # array for run_batch(), None when no filtering was in effect.
    threshold: Any = None
    # Delta-recompute accounting (repro.core.delta.DeltaStats) when the
    # result came through run_delta / run_sequence; None otherwise.
    delta: Any = None

    def to_array(self) -> np.ndarray:
        return diagram_to_array(self.diagram)


class PHEngine:
    """Config-driven PH computation with plan caching and auto-regrow.

    One engine per configuration family; engines are cheap to construct but
    the plan cache only pays off when reused, so share an engine across
    calls of the same workload.
    """

    def __init__(self, config: PHConfig | None = None):
        self.config = config if config is not None else PHConfig()
        if not isinstance(self.config, PHConfig):
            raise TypeError(f"config must be a PHConfig, "
                            f"got {type(self.config).__name__}")
        self._plans: dict[tuple, Plan] = {}
        # Largest regrown capacities seen per (kind, shape, dtype): later
        # calls start there instead of re-walking the doubling chain.
        self._grown: dict[tuple, tuple[int, int]] = {}
        # Autotune memo: effective (tuned) config per (shape, dtype), so
        # the disk-cache lookup happens once per shape family.
        self._tuned: dict[tuple, PHConfig] = {}
        # Delta frame store (repro.cache.DiagramCache), built lazily from
        # config.delta.cache_entries on the first run_delta call.
        self._delta_cache = None
        # Autotuned tile-grid memo per (shape, dtype) — like _tuned, one
        # disk-cache lookup per shape family.
        self._tuned_grids: dict[tuple, tuple[int, int] | None] = {}
        self._hits = 0
        self._misses = 0
        self.regrow_log: list[dict] = []
        # Overlap-engine accounting (H2D/D2H transfers, blocking syncs by
        # thread role, donation replays) — bumped by the engine, executor,
        # driver, and server; read by the bench and the perf gate.
        self.overlap_counters = OverlapCounters()
        # Guards the plan cache, the regrow memo, and every counter:
        # concurrent submitters (the serving daemon's clients, N threads
        # hammering run()) share one engine, and an unguarded cache miss
        # would let two threads build — and trace — the same plan twice.
        # Tracing/compute happen *outside* this lock (Plan serializes its
        # own first call), so the engine lock is never held across XLA.
        self._lock = threading.RLock()

    # -- plan cache --------------------------------------------------------

    def get_plan(self, key: tuple, builder: Callable[[Plan], Callable],
                 merge_keys: str = "rank") -> Plan:
        """Fetch or build the compiled plan for ``key`` (thread-safe: one
        plan object per key, however many threads race the miss).

        ``builder(plan)`` returns the callable; it receives the plan object
        so traced wrappers can bump ``plan.traces`` at trace time.
        ``merge_keys`` is the *resolved* key encoding — packed plans run
        their trace/lower/execute under the int64 key scope.
        """
        with self._lock:
            plan = self._plans.get(key)
            if plan is None:
                plan = Plan(None, key, merge_keys,
                            self._plan_impls(key[0], merge_keys))
                plan.fn = builder(plan)
                self._plans[key] = plan
                self._misses += 1
            else:
                self._hits += 1
            return plan

    def _plan_impls(self, kind: str, merge_keys: str) -> dict:
        """What a plan of ``kind`` runs under this config: the resolved
        key encoding plus the backend of each kernel package its program
        reaches.  The tiled seam merge passes no backend toggles to the
        phase-C reduction, so it takes the backend default."""
        cfg = self.config

        def res(kernel, use_pallas=cfg.use_pallas, interpret=cfg.interpret):
            return backend.resolve(kernel, use_pallas, interpret)

        impls = {"merge_keys": merge_keys}
        if kind == "distance":
            impls["ph_distance"] = res("ph_distance")
        elif kind in ("single", "batched", "sharded"):
            if cfg.phase_a_impl == "fused":
                impls["ph_phase_a"] = res("ph_phase_a")
            if cfg.phase_a_impl == "pooled" or cfg.candidate_mode == "paper":
                impls["maxpool"] = res("maxpool")
            if cfg.merge_impl == "boruvka" and cfg.phase_c_impl == "fused":
                impls["ph_phase_c"] = res("ph_phase_c")
        elif kind != "delta_ab" and cfg.phase_c_impl == "fused":
            impls["ph_phase_c"] = res("ph_phase_c", None, False)
        return impls

    def plan_stats(self) -> dict:
        """Plan-cache counters, plus ``impls``: per plan kind, what its
        programs run (:attr:`Plan.impls`; values that differ between
        plans of one kind, e.g. per dtype, are joined with ``|``)."""
        with self._lock:
            plans = list(self._plans.values())
            impls: dict[str, dict[str, set]] = {}
            for p in plans:
                per = impls.setdefault(p.key[0], {})
                for name, impl in p.impls.items():
                    per.setdefault(name, set()).add(impl)
            return {
                "plans": len(plans),
                "traces": sum(p.traces for p in plans),
                "calls": sum(p.calls for p in plans),
                "hits": self._hits,
                "misses": self._misses,
                "regrows": len(self.regrow_log),
                "impls": {kind: {name: "|".join(sorted(v))
                                 for name, v in per.items()}
                          for kind, per in impls.items()},
            }

    # -- overlap policy ----------------------------------------------------

    def overlap_spec(self) -> OverlapSpec:
        """Effective overlap policy — a disabled spec when the config
        carries none (synchronous transfers, the pre-overlap behavior)."""
        o = self.config.overlap
        return o if o is not None else _OVERLAP_OFF

    def donate_batched(self) -> bool:
        """Whether engine-owned padded batches dispatch through donating
        plans.  Only batches the engine (or executor/server) built from
        host arrays are ever donated — user-supplied device arrays may be
        aliased by the caller, and donation invalidates the buffer."""
        o = self.overlap_spec()
        return o.enabled and o.donate

    def _stream_results(self) -> bool:
        """Whether dispatches start async D2H copies on their results
        (overflow scalar included) instead of leaving the first
        ``np.asarray`` to schedule a blocking copy."""
        o = self.overlap_spec()
        return o.enabled and o.async_overflow

    def _merge_keys_for(self, dtype) -> str:
        """The resolved phase-C key encoding for ``dtype`` under this
        config (packed falls back to rank on > 32-bit dtypes or when the
        int64 scope is unavailable — bit-identical either way)."""
        return resolve_merge_keys(self.config.merge_keys, dtype)

    def _effective_config(self, shape2d, dtype) -> PHConfig:
        """The config with autotuned ``(strip_rows, phase_c_block,
        tournament_width)`` folded in for this image shape family,
        memoized per (shape, dtype).

        With ``config.autotune`` on this is a pure **disk-cache lookup**
        (:func:`repro.roofline.autotune.lookup`) — the engine never
        measures; a missing cache entry keeps the config's own fields.
        The effective config's :meth:`PHConfig.plan_key` keys the plan
        cache, so tuned parameters deterministically select compiled
        programs.
        """
        cfg = self.config
        if not cfg.autotune:
            return cfg
        key = (tuple(shape2d), str(dtype))
        with self._lock:
            got = self._tuned.get(key)
        if got is not None:
            return got
        from repro.roofline import autotune
        tp = autotune.lookup(tuple(shape2d), str(dtype),
                             path=cfg.autotune_cache)
        eff = cfg if tp.source == "default" else cfg.replace(
            strip_rows=tp.strip_rows,
            phase_c_block=tp.phase_c_block,
            tournament_width=tp.tournament_width)
        with self._lock:
            self._tuned[key] = eff
        return eff

    def _tuned_grid(self, shape2d, dtype) -> tuple[int, int] | None:
        """Autotuned tile grid for this shape family — a pure disk-cache
        lookup (:func:`repro.roofline.autotune.lookup`), memoized per
        (shape, dtype); ``None`` when autotune is off or the cache has no
        ``tile_grid`` for the family."""
        cfg = self.config
        if not cfg.autotune:
            return None
        key = (tuple(shape2d), str(dtype))
        with self._lock:
            if key in self._tuned_grids:
                return self._tuned_grids[key]
        from repro.roofline import autotune
        tg = autotune.lookup(tuple(shape2d), str(dtype),
                             path=cfg.autotune_cache).tile_grid
        with self._lock:
            self._tuned_grids[key] = tg
        return tg

    def _resolve_grid(self, shape2d, dtype, spec: TileSpec
                      ) -> tuple[int, int]:
        """Tile grid for one image: the spec's explicit grid, else the
        autotuned grid (validated — a stale cache entry that no longer
        divides the shape is ignored), else ``choose_grid`` from the
        tile-pixel budget.  The winner lands in every tiled/delta plan
        key, so tuning deterministically selects compiled programs."""
        from repro.core import tiling
        if spec.grid is not None:
            return tuple(spec.grid)
        tg = self._tuned_grid(shape2d, dtype)
        if tg is not None:
            try:
                tiling.validate_grid(tuple(shape2d), tg)
                return tg
            except ValueError:
                pass
        return tiling.choose_grid(tuple(shape2d), spec.max_tile_pixels)

    def _ph_kwargs(self, mf: int, mc: int, merge_keys: str,
                   cfg: PHConfig | None = None) -> dict:
        """Static kwargs of one compiled stage-graph program: capacities
        plus the config's stage signature knobs (phase A impl/strip rows,
        candidate mode, merge impl/keys, phase C impl/block/width, backend
        toggles).  ``merge_keys`` arrives resolved — the plan's key scope
        matches it.  ``cfg`` (default: the engine config) lets autotuned
        effective configs supply the tuned fields."""
        cfg = self.config if cfg is None else cfg
        return dict(max_features=mf, max_candidates=mc,
                    candidate_mode=cfg.candidate_mode,
                    merge_impl=cfg.merge_impl,
                    merge_keys=merge_keys,
                    phase_a_impl=cfg.phase_a_impl,
                    strip_rows=cfg.strip_rows,
                    phase_c_impl=cfg.phase_c_impl,
                    phase_c_block=cfg.phase_c_block,
                    tournament_width=cfg.tournament_width,
                    use_pallas=cfg.use_pallas, interpret=cfg.interpret,
                    filtration=cfg.filtration)

    def _local_plan(self, kind: str, shape, dtype, mf: int, mc: int,
                    truncated: bool, donate: bool = False) -> Plan:
        """Plan for the non-sharded entry points: ``kind`` selects the
        callee ("single" -> pixhomology, "batched" -> its vmap).

        ``donate`` compiles with ``donate_argnums=(0,)`` so the image
        batch's device buffer is reused for an output instead of being
        re-allocated per round.  Donation changes the executable's
        input/output aliasing, so it is part of the plan key; callers
        must own the donated buffer (the bucketed/serving paths build
        their padded batches from host arrays) and must re-stage it
        before any replay — the regrow dispatchers do.
        """
        callee = pixhomology if kind == "single" else batched_pixhomology
        mk = self._merge_keys_for(dtype)
        eff = self._effective_config(tuple(shape)[-2:], dtype)
        key = (kind, shape, str(dtype), mf, mc, truncated, donate,
               eff.plan_key())

        def build(plan: Plan):
            kw = self._ph_kwargs(mf, mc, mk, eff)

            def compute(x, tv=None):
                plan.traces += 1   # python side effect: runs per (re)trace
                return callee(x, tv, **kw)

            dn = (0,) if donate else ()
            if truncated:
                return jax.jit(lambda im, tv: compute(im, tv),
                               donate_argnums=dn)
            return jax.jit(lambda im: compute(im), donate_argnums=dn)

        return self.get_plan(key, build, mk)

    def sharded_plan(self, ctx, shape, dtype, mf: int, mc: int,
                     donate: bool = False) -> Plan:
        """shard_map'd batched PH over ``ctx.dp_axes`` (always thresholded:
        vanilla rounds pass -inf, which is a no-op for float images).

        Per-image work is embarrassingly parallel, so it is pinned inside
        shard_map — XLA's sharding propagation otherwise replicates the
        merge-scan carries and emits ~70 TB of all-gathers per batch
        (src/repro/ph/DESIGN.md §Perf PH-1: collective 1407 s -> ~0).

        ``donate`` as in :meth:`_local_plan`: the round's padded image
        batch buffer is donated to the executable (the staging ring owns
        it and retains the host copy for the rare regrow replay).
        """
        mk = self._merge_keys_for(dtype)
        eff = self._effective_config(tuple(shape)[-2:], dtype)
        key = ("sharded", ctx, shape, str(dtype), mf, mc, donate,
               eff.plan_key())

        def build(plan: Plan):
            from jax.sharding import PartitionSpec as P
            kw = self._ph_kwargs(mf, mc, mk, eff)
            dp = ctx.dp_axes
            out_specs = Diagram(P(dp, None), P(dp, None), P(dp, None),
                                P(dp, None), P(dp), P(dp), P(dp), P(dp))

            def compute(images, tvals):
                plan.traces += 1
                if images.shape[0] == 1:
                    # Per-device batch of one (the pipeline's M == dp_size
                    # rounds): vmap lowers the merge scan ~2.5x worse than
                    # the single-image program, so bypass it.
                    diag = pixhomology(images[0], tvals[0], **kw)
                    return jax.tree.map(lambda x: jnp.expand_dims(x, 0),
                                        diag)
                return batched_pixhomology(images, tvals, **kw)

            return jax.jit(shard_map_compat(
                compute, mesh=ctx.mesh,
                in_specs=(P(dp, None, None), P(dp)),
                out_specs=out_specs),
                donate_argnums=(0,) if donate else ())

        return self.get_plan(key, build, mk)

    def tiled_plan(self, shape, dtype, grid, mf: int, tf: int, tk: int,
                   truncated: bool, ctx=None) -> Plan:
        """Halo-tiled PH plan (``repro.core.tiling.tiled_pixhomology``).

        ``mf`` is the global diagram capacity, ``tf``/``tk`` the per-tile
        root/candidate capacities; ``ctx`` (optional) shards the per-tile
        phases over the mesh's data axes via ``shard_map``.
        """
        from repro.core.tiling import tiled_pixhomology
        mk = self._merge_keys_for(dtype)
        key = ("tiled", ctx, shape, str(dtype), grid, mf, tf, tk, truncated,
               self.config.plan_key())

        cfg = self.config

        def build(plan: Plan):
            def compute(x, tv=None):
                plan.traces += 1
                return tiled_pixhomology(
                    x, tv, grid=grid, max_features=mf,
                    tile_max_features=tf, tile_max_candidates=tk,
                    shard_ctx=ctx, merge_keys=mk,
                    phase_c_impl=cfg.phase_c_impl,
                    phase_c_block=cfg.phase_c_block,
                    filtration=cfg.filtration)

            if truncated:
                return jax.jit(lambda im, tv: compute(im, tv))
            return jax.jit(lambda im: compute(im))

        return self.get_plan(key, build, mk)

    def tiled_stacks_plan(self, shape, dtype, grid, mf: int, tf: int,
                          tk: int, truncated: bool, ctx=None) -> Plan:
        """Tiled PH plan over pre-staged tile stacks
        (``repro.core.tiling.tiled_pixhomology_stacks``) — the streaming
        path where no host-resident image exists."""
        from repro.core.tiling import tiled_pixhomology_stacks
        mk = self._merge_keys_for(dtype)
        key = ("tiled_stacks", ctx, shape, str(dtype), grid, mf, tf, tk,
               truncated, self.config.plan_key())

        cfg = self.config

        def build(plan: Plan):
            def compute(pv, pg, tv=None):
                plan.traces += 1
                return tiled_pixhomology_stacks(
                    pv, pg, tv, shape=shape, grid=grid, max_features=mf,
                    tile_max_features=tf, tile_max_candidates=tk,
                    shard_ctx=ctx, merge_keys=mk,
                    phase_c_impl=cfg.phase_c_impl,
                    phase_c_block=cfg.phase_c_block,
                    filtration=cfg.filtration)

            if truncated:
                return jax.jit(lambda pv, pg, tv: compute(pv, pg, tv))
            return jax.jit(lambda pv, pg: compute(pv, pg))

        return self.get_plan(key, build, mk)

    def delta_ab_plan(self, tile_shape, dtype, n_stack: int, tf: int,
                      tk: int, truncated: bool) -> Plan:
        """Batched per-tile phases A+B over a dirty-tile stack
        (:func:`repro.core.delta.phase_ab_stack`).  ``n_stack`` is the
        power-of-two dirty bucket, so the set of compiled batch shapes is
        logarithmic in the tile count."""
        from repro.core.delta import phase_ab_stack
        mk = self._merge_keys_for(dtype)
        cfg = self.config
        key = ("delta_ab", tuple(tile_shape), str(dtype), n_stack, tf, tk,
               truncated, cfg.plan_key())

        def build(plan: Plan):
            def compute(pv, pg, tv=None):
                plan.traces += 1
                return phase_ab_stack(pv, pg, tv, tile_max_features=tf,
                                      tile_max_candidates=tk, merge_keys=mk,
                                      filtration=cfg.filtration)

            if truncated:
                return jax.jit(lambda pv, pg, tv: compute(pv, pg, tv))
            return jax.jit(lambda pv, pg: compute(pv, pg))

        return self.get_plan(key, build, mk)

    def delta_merge_plan(self, shape, dtype, grid, n_stack: int, mf: int,
                         tf: int, tk: int, truncated: bool) -> Plan:
        """Scatter fresh dirty rows into the cached tile state and replay
        the seam merge (:func:`repro.core.delta.scatter_merge`); returns
        ``(new_state, TiledDiagram)``."""
        from repro.core.delta import scatter_merge
        mk = self._merge_keys_for(dtype)
        cfg = self.config
        key = ("delta_merge", tuple(shape), str(dtype), grid, n_stack, mf,
               tf, tk, truncated, cfg.plan_key())

        def build(plan: Plan):
            def compute(state, fresh, slots, tv=None):
                plan.traces += 1
                return scatter_merge(
                    state, fresh, slots, tv, shape=tuple(shape), grid=grid,
                    max_features=mf, tile_max_features=tf,
                    tile_max_candidates=tk, merge_keys=mk,
                    phase_c_impl=cfg.phase_c_impl,
                    phase_c_block=cfg.phase_c_block,
                    filtration=cfg.filtration)

            if truncated:
                return jax.jit(lambda s, f, sl, tv: compute(s, f, sl, tv))
            return jax.jit(lambda s, f, sl: compute(s, f, sl))

        return self.get_plan(key, build, mk)

    # -- capacity regrow ---------------------------------------------------

    def _ceilings(self, n: int) -> tuple[int, int]:
        cfg = self.config
        ceil_f = min(cfg.regrow_features_ceiling or n, n)
        ceil_c = min(cfg.regrow_candidates_ceiling or n, n)
        return ceil_f, ceil_c

    def initial_capacities(self, n: int) -> tuple[int, int]:
        """Effective first-attempt capacities for an n-pixel image (clamped
        to n so equivalent over-sized configs share one plan)."""
        return min(self.config.max_features, n), \
            min(self.config.max_candidates, n)

    def grow_capacities(self, mf: int, mc: int, n: int) -> tuple[int, int]:
        """One regrow step: double both capacities up to their ceilings.

        ``Diagram.overflow`` is a single flag, so both capacities grow
        together (padding is cheap relative to a second re-dispatch).
        Returns unchanged values when both ceilings are reached.
        """
        ceil_f, ceil_c = self._ceilings(n)
        return min(mf * self.config.regrow_factor, ceil_f), \
            min(mc * self.config.regrow_factor, ceil_c)

    def begin_regrow(self, dispatch: Callable[[int, int], Any],
                     overflowed: Callable[[Any], bool],
                     n: int, kind: str,
                     memo_key: tuple | None = None,
                     stream: bool = False
                     ) -> tuple[Any, Callable[[], tuple[Any, "RegrowStats"]]]:
        """Dispatch once at the memoized capacities and return
        ``(out, finish)`` with **no blocking device readback**.

        ``finish()`` performs the deferred overflow check and, on the
        rare overflow, the regrow-and-replay loop — returning the same
        ``(out, RegrowStats)`` the synchronous :meth:`run_with_regrow`
        produces (which is literally ``begin_regrow(...)`` followed by
        an immediate ``finish()``, so the two are bit-identical by
        construction; overflow semantics are deferred, never altered).

        With ``stream=True`` the dispatched output starts async
        device->host copies immediately (``copy_to_host_async``), so
        the overflow scalar — and usually the diagram itself — is
        already on the host by the time ``finish()`` looks at it.  The
        caller may dispatch further work between ``begin`` and
        ``finish`` (the speculative next round of the overlap engine);
        a dispatch that donated its input must rebuild it on replay,
        which the engine's own dispatch closures do.

        ``memo_key`` makes grown capacities sticky: a later call for the
        same (kind, shape, dtype) starts at the largest capacity already
        discovered instead of re-walking the doubling chain.

        Spans (:mod:`repro.ph.trace`): ``ph.dispatch`` around each
        dispatch, ``ph.wait`` around each overflow check, ``ph.regrow``
        around each replay."""
        cfg = self.config
        mf0, mc0 = self.initial_capacities(n)
        if cfg.auto_regrow and memo_key is not None:
            with self._lock:
                got = self._grown.get(memo_key)
            if got:
                mf0 = max(mf0, min(got[0], n))
                mc0 = max(mc0, min(got[1], n))

        def launch(mf, mc):
            with trace.span("ph.dispatch", max_candidates=mc):
                out = dispatch(mf, mc)
                if stream:
                    start_d2h(out, self.overlap_counters)
                return out

        def check(out):
            with trace.span("ph.wait"):
                return overflowed(out)

        out0 = launch(mf0, mc0)

        def finish(out=out0, mf=mf0, mc=mc0):
            attempts = 0
            over = check(out)  # drains the in-flight copy if streamed
            while over and cfg.auto_regrow and attempts < cfg.max_regrows:
                nmf, nmc = self.grow_capacities(mf, mc, n)
                if (nmf, nmc) == (mf, mc):
                    break   # at the ceiling: residual overflow is reported
                with self._lock:
                    self.regrow_log.append({"kind": kind, "from": (mf, mc),
                                            "to": (nmf, nmc)})
                mf, mc = nmf, nmc
                attempts += 1
                with trace.span("ph.regrow", max_candidates=mc):
                    out = launch(mf, mc)
                    over = check(out)
            if attempts and memo_key is not None:
                with self._lock:
                    got = self._grown.get(memo_key)
                    if got is None or got < (mf, mc):
                        self._grown[memo_key] = (mf, mc)
            return out, RegrowStats(attempts, mf, mc, bool(over))

        return out0, finish

    def run_with_regrow(self, dispatch: Callable[[int, int], Any],
                        overflowed: Callable[[Any], bool],
                        n: int, kind: str,
                        memo_key: tuple | None = None
                        ) -> tuple[Any, RegrowStats]:
        """Shared synchronous driver: dispatch, then regrow while overflow
        persists — :meth:`begin_regrow` plus an immediate ``finish()``."""
        _, finish = self.begin_regrow(dispatch, overflowed, n, kind,
                                      memo_key=memo_key)
        return finish()

    # -- data prep ---------------------------------------------------------

    def cast_input(self, image) -> jnp.ndarray:
        """Apply the config's dtype policy (None = keep the input dtype).

        The engine boundary rejects non-finite pixels: NaN cannot be
        ordered by any filtration (the packed bit-cast keys would silently
        scatter it through the key order), and ±inf collides with the
        inert pad/halo sentinels the padded dispatch paths rely on."""
        check_finite(image)
        x = jnp.asarray(image)
        if self.config.dtype is not None:
            x = x.astype(self.config.dtype)
        return x

    def cast_input_host(self, image) -> np.ndarray:
        """Host-side twin of :meth:`cast_input`: the same dtype policy
        (canonicalization included, so ``float64`` inputs land on the
        dtype the device dispatch will actually use) applied with numpy.
        Staging paths use this so building a padded round never bounces
        host -> device -> host — no device allocation happens until the
        round's one fused ``device_put``.  Rejects non-finite pixels
        exactly like :meth:`cast_input`."""
        x = np.asarray(image)
        check_finite(x)
        dt = self.config.dtype if self.config.dtype is not None else x.dtype
        np_dt = np.dtype(jax.dtypes.canonicalize_dtype(dt))
        if x.dtype != np_dt:
            x = x.astype(np_dt)
        return x

    def _auto_threshold(self, image) -> float | None:
        # The host conversion happens only past the VANILLA check: it is a
        # full device-to-host readback, pure waste when no filter applies.
        if self.config.filter_level is FilterLevel.VANILLA:
            return None
        from repro.data import astro
        with trace.span("ph.threshold"):
            host = np.asarray(image)
            if self.config.filtration == "sublevel":
                # The astro statistic keeps the brightest pixels of a
                # superlevel analysis; its exact sublevel mirror is the
                # negation on both sides (keep <= -t of -image == keep >= t).
                t, _ = astro.filter_threshold(-host,
                                              self.config.filter_level)
                return None if t is None else -t
            t, _ = astro.filter_threshold(host, self.config.filter_level)
            return t

    def auto_threshold(self, image) -> float | None:
        """The Variant-2 threshold ``config.filter_level`` implies for
        ``image`` (``None`` under VANILLA).  The serving daemon calls
        this on the submitter's thread so the coalescing tick never pays
        the host-side statistic."""
        return self._auto_threshold(image)

    # -- warm plan pool ----------------------------------------------------

    def warmup(self, bucket_shapes=None, *, batch_sizes=None, dtype=None,
               truncated: bool = True) -> dict:
        """Pre-trace and compile the plans a steady-state request stream
        will hit, so no request ever pays a trace (serving p50 latency
        becomes compute-only).

        ``bucket_shapes``: square sizes or ``(H, W)`` pairs; defaults to
        the config's ``serve.buckets``.  For every bucket this pushes a
        **worst-case dummy** (a checkerboard — the maximal
        feature/candidate load a bucket can produce) through the normal
        dispatch-with-regrow path, for the **single**-image plan plus one
        **batched** plan per entry of ``batch_sizes`` (default: the
        config's ``serve.batch_cap``, the fixed dispatch batch the daemon
        pads every tick to).  Trace, lowering, compile, *and* the
        overflow regrow chain all happen here: the sticky regrow memo
        records the grown capacity tier, so steady-state requests start
        at a tier whose plan already exists.  ``truncated`` warms the
        thresholded program variants (what padded serving batches always
        run; ``-inf`` thresholds make them exact no-ops for unfiltered
        images).

        Returns ``{"plans": ..., "traces": ..., "seconds": ...}`` — the
        *new* plans/traces this warmup added.  After it, the existing
        plan trace counters (:meth:`plan_stats`) let callers assert that
        steady state re-traces nothing; ``benchmarks/serve_bench.py``
        gates on exactly that.
        """
        spec = self.config.serve
        if bucket_shapes is None:
            if spec is None or spec.buckets is None:
                raise ValueError("warmup needs bucket_shapes (or a config "
                                 "serve spec with a fixed bucket set)")
            bucket_shapes = spec.buckets
        if batch_sizes is None:
            batch_sizes = (spec.batch_cap,) if spec is not None else ()
        before = self.plan_stats()
        t0 = time.perf_counter()
        for shape in bucket_shapes:
            shape = (int(shape), int(shape)) if isinstance(shape, int) \
                else tuple(shape)
            h, w = shape
            n = h * w
            # Stride-2 peak grid: under 8-connectivity the local maxima
            # of an image form an independent set of the king graph,
            # whose maximum size is ceil(h/2)*ceil(w/2) — exactly the
            # peaks planted here (distinct heights, so no plateaus merge
            # them).  No real image of this bucket produces more
            # features, so the regrow tier discovered here upper-bounds
            # the tier any steady-state dispatch will ask for.
            dummy = np.zeros(shape, np.dtype(dtype or "float32"))
            peaks = dummy[::2, ::2]
            peaks[...] = 1 + np.arange(peaks.size).reshape(peaks.shape)
            if self.config.filtration == "sublevel":
                # Same worst case, mirrored: the planted extrema must be
                # the filtration's feature points (local minima), and the
                # inert "no truncation" sentinel flips sign with it.
                dummy = -dummy
            inert = np.inf if self.config.filtration == "sublevel" \
                else -np.inf
            host = self.cast_input_host(dummy)
            x = self.cast_input(dummy)
            tv = jnp.asarray(inert, threshold_dtype(x.dtype))
            over = lambda d: bool(np.any(np.asarray(d.overflow)))  # noqa: E731
            for kind, b in [("single", None)] + [("batched", int(b))
                                                 for b in batch_sizes]:
                bshape = shape if b is None else (b, h, w)
                # Batched dispatches (what the serving tick runs) go
                # through donating plans when the overlap engine donates:
                # warming the non-donating twin would leave steady state
                # retracing.  Donated buffers are consumed per call, so
                # the donating warmup re-stages from the host dummy.
                donate = self.donate_batched() and kind == "batched"
                xb = x if b is None else (
                    None if donate else jnp.broadcast_to(x, bshape))
                tb = tv if b is None else jnp.broadcast_to(tv, (b,))

                def dispatch(mf, mc, kind=kind, bshape=bshape, xb=xb, tb=tb,
                             donate=donate):
                    plan = self._local_plan(kind, bshape, x.dtype, mf, mc,
                                            truncated, donate=donate)
                    if donate:
                        xb = jnp.asarray(np.broadcast_to(host, bshape))
                    return plan(xb, tb) if truncated else plan(xb)

                out, _ = self.run_with_regrow(
                    dispatch, over, n, kind,
                    memo_key=(kind, bshape, str(x.dtype)))
                jax.block_until_ready(out)
        after = self.plan_stats()
        return {"plans": after["plans"] - before["plans"],
                "traces": after["traces"] - before["traces"],
                "seconds": round(time.perf_counter() - t0, 4)}

    # -- public entry points ----------------------------------------------

    def run(self, image, truncate_value: float | None = None) -> PHResult:
        """0-dim PH of one 2D image (Algorithm 1) with auto-regrow.

        ``truncate_value`` overrides the config's ``filter_level`` (pass an
        explicit Variant-2 threshold); with the default ``None`` the
        threshold is derived from ``config.filter_level``.

        Records a ``ph.run`` span (:mod:`repro.ph.trace`) with the plan id
        that finished, ``pixels``, the final ``max_candidates`` tier, the
        regrow ``attempts`` and the ``candidates`` the merge swept (read
        with the overflow flag, in the call's one blocking readback).
        """
        with trace.span("ph.run") as rec:
            with trace.span("ph.cast"):
                x = self.cast_input(image)
            if x.ndim != 2:
                raise ValueError(f"expected 2D image, got shape {x.shape}")
            if truncate_value is None:
                truncate_value = self._auto_threshold(image)
            n = x.size
            truncated = truncate_value is not None
            shape, dtype = x.shape, x.dtype
            ran = {}

            def dispatch(mf, mc):
                plan = self._local_plan("single", shape, dtype, mf, mc,
                                        truncated)
                ran["plan"] = plan.id
                if truncated:
                    return plan(x, jnp.asarray(truncate_value,
                                               threshold_dtype(x.dtype)))
                return plan(x)

            def overflowed(d):
                over, ran["candidates"] = jax.device_get(
                    (d.overflow, d.n_candidates))
                return bool(over)

            diag, stats = self.run_with_regrow(
                dispatch, overflowed, n, "single",
                memo_key=("single", shape, str(dtype)))
            rec.attrs.update(plan=ran["plan"], pixels=n,
                             max_candidates=stats.final_max_candidates,
                             attempts=stats.attempts,
                             candidates=int(ran["candidates"]))
        return PHResult(diag, self.config.replace(
            max_features=stats.final_max_features,
            max_candidates=stats.final_max_candidates), stats,
            truncate_value)

    def _dedupe_batch(self, images, truncate_values):
        """Content-hash duplicate detection for :meth:`run_batch`.

        Returns ``None`` when dedupe cannot help (fewer than two images,
        non-2D rows, or no duplicates); otherwise ``(reps, inverse,
        rep_images, rep_tvs)`` where ``reps`` indexes the first occurrence
        of each distinct ``(bytes, shape, dtype, threshold)`` and
        ``inverse[i]`` maps row ``i`` to its representative's rank.
        """
        import hashlib
        arr = images if hasattr(images, "ndim") else None
        if arr is not None:
            if getattr(arr, "ndim", 0) != 3 or arr.shape[0] < 2:
                return None
            host = np.asarray(arr)
            seq = [host[i] for i in range(host.shape[0])]
        else:
            seq = [np.asarray(im) for im in images]
            if len(seq) < 2 or any(im.ndim != 2 for im in seq):
                return None
        if truncate_values is None:
            tvs = [None] * len(seq)
        elif np.isscalar(truncate_values):
            tvs = [float(truncate_values)] * len(seq)
        else:
            tvs = list(np.asarray(truncate_values, object))
            if len(tvs) != len(seq):
                return None   # let the dispatch path raise its own error
        keys = []
        for im, t in zip(seq, tvs):
            digest = hashlib.blake2b(
                np.ascontiguousarray(im).tobytes(), digest_size=16).digest()
            keys.append((im.shape, str(im.dtype), digest,
                         None if t is None else float(t)))
        first: dict = {}
        reps: list[int] = []
        inverse = np.empty(len(seq), np.int64)
        for i, k in enumerate(keys):
            got = first.get(k)
            if got is None:
                first[k] = got = len(reps)
                reps.append(i)
            inverse[i] = got
        if len(reps) == len(seq):
            return None
        rep_tvs = None if truncate_values is None \
            else [tvs[i] for i in reps]
        return reps, inverse, [seq[i] for i in reps], rep_tvs

    def run_batch(self, images, truncate_values=None, *,
                  bucket: tuple[int, int] | None = None,
                  dedupe: bool = True) -> PHResult:
        """vmap'd PH over an image batch, regrowing on *any* overflow.

        ``images``: a ``(B, H, W)`` array (one compiled batch — the fast
        path), or a sequence of 2D images whose shapes may be **mixed**.
        Mixed shapes are padded to one shape bucket — ``bucket``, or the
        elementwise maximum of each image's
        :func:`repro.pipeline.scheduler.bucket_shape` under
        ``config.bucket_rounding`` — with the inert fill, and the two pad
        artifacts are repaired host-side after compute
        (:mod:`repro.pipeline.padding`), so every row of the result is
        bit-identical to :meth:`run` on that image alone.  ``bucket``
        also forces uniform-shape batches into a fixed padded dispatch
        shape (what the serving daemon's warmed plans require).

        ``truncate_values``: optional per-image thresholds ((B,) array or
        sequence; ``None`` entries derive from ``config.filter_level``).
        Padded rows always run thresholded; when neither an explicit nor
        a filter-level threshold exists, the image minimum stands in
        (exact — it keeps every real pixel and excludes every pad pixel).

        ``dedupe`` (default on): exact content duplicates — same bytes,
        shape, dtype, and threshold — compute once and fan out to every
        requesting row host-side.  The dispatch batch shrinks to the
        distinct images, so callers that need a *fixed* dispatch shape
        (the serving daemon's warmed plans) must pass ``dedupe=False``.
        """
        return self.run_batch_async(images, truncate_values, bucket=bucket,
                                    dedupe=dedupe).resolve()

    def run_batch_async(self, images, truncate_values=None, *,
                        bucket: tuple[int, int] | None = None,
                        dedupe: bool = True) -> PendingResult:
        """Non-blocking :meth:`run_batch`: device compute is dispatched —
        and, with ``overlap.async_overflow``, result copies start
        streaming to the host — before this returns.  ``resolve()`` on
        the returned :class:`repro.ph.overlap.PendingResult` performs
        the deferred overflow check, the rare regrow-and-replay, and the
        host-side pad repair, producing exactly :meth:`run_batch`'s
        ``PHResult`` (the synchronous method literally calls this and
        resolves immediately, so bit-identity is by construction).  The
        serving daemon's tick thread dispatches through this and hands
        ``resolve()`` to its harvest thread.
        """
        if dedupe:
            plan = self._dedupe_batch(images, truncate_values)
            if plan is not None:
                reps, inverse, rep_images, rep_tvs = plan
                pending = self.run_batch_async(rep_images, rep_tvs,
                                               bucket=bucket, dedupe=False)

                def fanout():
                    res = pending.resolve()
                    host = jax.tree.map(np.asarray, res.diagram)
                    diag = jax.tree.map(lambda a: a[inverse], host)
                    thr = res.threshold
                    if thr is not None and not np.isscalar(thr):
                        thr = np.asarray(thr)[inverse]
                    return dataclasses.replace(res, diagram=diag,
                                               threshold=thr)

                return PendingResult(fanout)
        arr = images if hasattr(images, "ndim") else None
        if arr is not None and arr.ndim == 3 and (
                bucket is None or tuple(bucket) == tuple(arr.shape[1:])):
            return self._run_batch_uniform(arr, truncate_values)
        seq = [arr[i] for i in range(arr.shape[0])] if arr is not None \
            else list(images)
        if not seq:
            raise ValueError("run_batch needs at least one image")
        shapes = {tuple(np.shape(im)) for im in seq}
        if any(len(s) != 2 for s in shapes):
            raise ValueError(f"expected a (B, H, W) batch or a sequence of "
                             f"2D images, got shapes {sorted(shapes)}")
        if bucket is None and len(shapes) == 1:
            return self._run_batch_uniform(np.stack(
                [np.asarray(im) for im in seq]), truncate_values)
        return self._run_batch_bucketed(seq, truncate_values, bucket)

    def _run_batch_uniform(self, images, truncate_values=None
                           ) -> PendingResult:
        """One-compiled-shape (B, H, W) batch (the pre-serving path);
        dispatches and returns a :class:`PendingResult` whose
        ``resolve()`` finishes the deferred overflow/regrow work."""
        x = self.cast_input(images)
        if x.ndim != 3:
            raise ValueError(f"expected (B, H, W) batch, got shape {x.shape}")
        if truncate_values is None and \
                self.config.filter_level is not FilterLevel.VANILLA:
            host = np.asarray(images)
            truncate_values = np.asarray(
                [self._auto_threshold(host[i]) for i in range(host.shape[0])],
                np.float32)
        truncated = truncate_values is not None
        if truncated:
            tvals = jnp.asarray(truncate_values, threshold_dtype(x.dtype))
        n = x.shape[1] * x.shape[2]
        shape, dtype = x.shape, x.dtype

        def dispatch(mf, mc):
            plan = self._local_plan("batched", shape, dtype, mf, mc,
                                    truncated)
            if truncated:
                return plan(x, tvals)
            return plan(x)

        _, finish = self.begin_regrow(
            dispatch, lambda d: bool(np.any(np.asarray(d.overflow))),
            n, "batched", memo_key=("batched", shape, str(dtype)),
            stream=self._stream_results())

        def materialize(tvs=truncate_values):
            diag, stats = finish()
            return PHResult(diag, self.config.replace(
                max_features=stats.final_max_features,
                max_candidates=stats.final_max_candidates), stats, tvs)

        return PendingResult(materialize)

    def _run_batch_bucketed(self, seq, truncate_values,
                            bucket: tuple[int, int] | None) -> PendingResult:
        """Mixed-shape batch via one shape-bucketed padded dispatch;
        dispatches and returns a :class:`PendingResult` (the pad repair
        and row stacking happen at ``resolve()``)."""
        from repro.pipeline.padding import pad_fixup, pad_image, \
            pad_threshold, unpad_diagram
        from repro.pipeline.scheduler import bucket_shape
        # Host-side cast: no device allocation during batch building (the
        # one H2D transfer below stages the whole padded batch at once).
        imgs = [self.cast_input_host(im) for im in seq]
        if bucket is None:
            per = [bucket_shape(im.shape, self.config.bucket_rounding)
                   for im in imgs]
            bucket = (max(s[0] for s in per), max(s[1] for s in per))
        bucket = (int(bucket[0]), int(bucket[1]))
        if truncate_values is None:
            tvs: list = [None] * len(imgs)
        else:
            tvs = [None if t is None or not np.isfinite(t) else float(t)
                   for t in np.asarray(truncate_values, object).tolist()] \
                if not np.isscalar(truncate_values) \
                else [float(truncate_values)] * len(imgs)
        if len(tvs) != len(imgs):
            raise ValueError(f"{len(tvs)} thresholds for {len(imgs)} images")

        filt = self.config.filtration
        inert = np.inf if filt == "sublevel" else -np.inf
        batch = np.empty((len(imgs), *bucket), imgs[0].dtype)
        tvals = np.empty((len(imgs),), np.float64)
        fixups: list = [None] * len(imgs)
        for i, im in enumerate(imgs):
            if im.dtype != imgs[0].dtype:
                raise ValueError("mixed dtypes in one batch: "
                                 f"{im.dtype} vs {imgs[0].dtype}")
            t = tvs[i] if tvs[i] is not None else self._auto_threshold(im)
            if im.shape != bucket:
                t = pad_threshold(im, t, filt)
                fixups[i] = pad_fixup(im, filt)
            batch[i] = pad_image(im, bucket, filt)
            tvals[i] = inert if t is None else t

        dtype = batch.dtype
        shape = batch.shape
        n = bucket[0] * bucket[1]
        donate = self.donate_batched()
        xb = None if donate else jnp.asarray(batch)
        tvj = jnp.asarray(tvals, threshold_dtype(dtype))
        dispatched = [0]

        def dispatch(mf, mc):
            plan = self._local_plan("batched", shape, dtype, mf, mc, True,
                                    donate=donate)
            if donate:
                # A donated buffer is consumed by its dispatch: every
                # call (re)stages from the retained host batch.  Replays
                # after an overflow are the only second calls.
                if dispatched[0]:
                    self.overlap_counters.bump("donation_replays")
                dispatched[0] += 1
                return plan(jnp.asarray(batch), tvj)
            return plan(xb, tvj)

        _, finish = self.begin_regrow(
            dispatch, lambda d: bool(np.any(np.asarray(d.overflow))),
            n, "batched", memo_key=("batched", shape, str(dtype)),
            stream=self._stream_results())

        def materialize():
            diag, stats = finish()
            rows = []
            host = jax.tree.map(np.asarray, diag)
            for i in range(len(imgs)):
                d = Diagram(*(x[i] for x in host))
                if fixups[i] is not None:
                    d = unpad_diagram(d, fixups[i], bucket)
                rows.append(d)
            stacked = jax.tree.map(lambda *xs: np.stack(xs), *rows)
            return PHResult(stacked, self.config.replace(
                max_features=stats.final_max_features,
                max_candidates=stats.final_max_candidates), stats,
                tvals)

        return PendingResult(materialize)

    def num_candidates(self, image, truncate_value=None) -> int:
        """Count death-point candidates under this engine's config (for
        sizing ``max_candidates`` / ``max_candidates_per_tile`` before a
        run; forwards the config's candidate mode and backend toggles)."""
        cfg = self.config
        x = self.cast_input(image)
        if truncate_value is None:
            truncate_value = self._auto_threshold(image)
        return int(core_num_candidates(
            x, cfg.candidate_mode, truncate_value,
            use_pallas=cfg.use_pallas, interpret=cfg.interpret,
            phase_a_impl=cfg.phase_a_impl, strip_rows=cfg.strip_rows,
            merge_keys=cfg.merge_keys, filtration=cfg.filtration))

    # -- diagram distances -------------------------------------------------

    def _stack_diagrams(self, diagrams):
        """Normalize distance inputs to host ``(birth, death, p_birth)``
        stacks of one common capacity.

        Accepts a batched :class:`PHResult`/:class:`Diagram` (2D fields,
        straight from :meth:`run_batch`), a sequence of per-image
        results/diagrams (1D fields, possibly of *mixed* capacities —
        regrow makes these; shorter ones gain pad rows, which the
        distance kernels treat as diagonal points, i.e. exactly
        nothing), or a ready ``(birth, death, p_birth)`` array triple.
        NaN births/deaths are rejected here — the same boundary rule as
        image inputs; the ±inf pad sentinels are of course allowed.
        """
        if isinstance(diagrams, tuple) and len(diagrams) == 3 \
                and not isinstance(diagrams[0], (PHResult, Diagram)):
            birth, death, p_birth = (np.asarray(a) for a in diagrams)
        else:
            if isinstance(diagrams, (PHResult, Diagram)):
                diagrams = [diagrams]
            ds = [r.diagram if isinstance(r, PHResult) else r
                  for r in diagrams]
            if not ds:
                raise ValueError("distance_matrix needs at least one "
                                 "diagram")
            rows = []
            for d in ds:
                b = np.atleast_2d(np.asarray(d.birth))
                de = np.atleast_2d(np.asarray(d.death))
                pb = np.atleast_2d(np.asarray(d.p_birth))
                rows.extend((b[i], de[i], pb[i]) for i in range(b.shape[0]))
            f = max(r[0].shape[0] for r in rows)

            def _grow(a, fill, dt):
                out = np.full(f, fill, dt)
                out[:a.shape[0]] = a
                return out

            birth = np.stack([_grow(b, 0, b.dtype) for b, _, _ in rows])
            death = np.stack([_grow(d, 0, d.dtype) for _, d, _ in rows])
            p_birth = np.stack([_grow(p, -1, np.int32) for _, _, p in rows])
        if birth.ndim != 2:
            raise ValueError(f"expected stacked (B, F) diagrams, got "
                             f"shape {tuple(birth.shape)}")
        check_finite(birth, where="diagram births", allow_inf=True)
        check_finite(death, where="diagram deaths", allow_inf=True)
        return birth, death, p_birth.astype(np.int32)

    def distance_plan(self, b: int, f: int, dtype, n_dirs: int) -> Plan:
        """Plan for the ``(B, F)`` diagram-distance matrix — its own
        cached kind, so serving/bench loops over a fixed batch shape
        trace once.  The plan key carries the backend toggles (the
        Pallas/interpret choice changes the executable) and the resolved
        key encoding (the profile selection primitive differs)."""
        mk = self._merge_keys_for(dtype)
        cfg = self.config
        key = ("distance", b, f, str(dtype), n_dirs, mk,
               cfg.use_pallas, cfg.interpret)

        def build(plan: Plan):
            from repro.kernels.ph_distance import diagram_distances

            def compute(birth, death, p_birth):
                plan.traces += 1
                return diagram_distances(
                    birth, death, p_birth, n_dirs=n_dirs, merge_keys=mk,
                    width=cfg.tournament_width,
                    use_pallas=cfg.use_pallas, interpret=cfg.interpret)

            return jax.jit(compute)

        return self.get_plan(key, build, mk)

    def distance_matrix(self, diagrams, *, n_dirs: int = 16):
        """Pairwise distance matrices of a batch of diagrams.

        ``diagrams``: anything :meth:`_stack_diagrams` accepts — a
        batched result from :meth:`run_batch`, a list of :meth:`run`
        results (mixed capacities fine), raw :class:`Diagram` tuples, or
        a ``(birth, death, p_birth)`` array triple.  Returns
        ``(sw, bottleneck)``, both (B, B) jnp arrays: sliced-Wasserstein
        distance and the bottleneck lower bound — definitions and the
        capacity-pad inertness argument live in
        :mod:`repro.kernels.ph_distance.ref` and DESIGN.md §12.

        Diagrams are taken in this engine's ``config.filtration``
        convention.  Both distances are invariant under simultaneously
        negating every diagram (a point reflection: all projections
        negate, so per-direction sorted pairings — and the persistence
        profiles — are preserved), so sublevel diagrams are canonicalized
        to the internal superlevel space by exact negation before the
        kernels run; matrices of a sublevel run and of the superlevel
        run on the negated images then agree bit-for-bit (a tested
        invariant).
        """
        birth, death, p_birth = self._stack_diagrams(diagrams)
        if self.config.filtration == "sublevel":
            birth, death = -birth, -death
        dt = self.config.dtype if self.config.dtype is not None \
            else birth.dtype
        dt = np.dtype(jax.dtypes.canonicalize_dtype(dt))
        if not np.issubdtype(dt, np.floating):
            dt = np.dtype(np.float32)
        birth = birth.astype(dt, copy=False)
        death = death.astype(dt, copy=False)
        plan = self.distance_plan(birth.shape[0], birth.shape[1],
                                  dt, int(n_dirs))
        return plan(birth, death, p_birth)

    def should_tile(self, n_pixels: int) -> bool:
        """True when the config routes an ``n_pixels`` image through the
        tiled path (``tile`` configured and the image exceeds its
        ``max_tile_pixels`` budget)."""
        t = self.config.tile
        return t is not None and n_pixels > t.max_tile_pixels

    def provider_threshold(self, provider):
        """Variant-2 threshold for a tile provider, consistent across
        every streaming entry point: the provider's estimate with its
        sample budget tied to the tile budget (O(tile) residency), fixed
        by this engine's config.  ``None`` under VANILLA."""
        if self.config.filter_level is FilterLevel.VANILLA:
            return None
        if self.config.filtration == "sublevel":
            raise ValueError(
                "filter_level-derived thresholds for tile providers are "
                "superlevel statistics; under filtration='sublevel' pass "
                "an explicit truncate_value (or use FilterLevel.VANILLA)")
        if not hasattr(provider, "filter_threshold"):
            raise ValueError(
                f"filter_level={self.config.filter_level} needs a "
                f"threshold, but the tile provider has no "
                f"filter_threshold(); pass truncate_value")
        spec = self.config.tile if self.config.tile is not None \
            else TileSpec()
        try:
            return provider.filter_threshold(
                self.config.filter_level,
                sample=math.isqrt(spec.max_tile_pixels))
        except TypeError:   # provider without a sample knob
            return provider.filter_threshold(self.config.filter_level)

    def stage_tiles(self, provider, *, grid=None, ctx=None):
        """Stage a tile provider's halo-padded tiles on device (O(tile)
        host residency), choosing the grid from the config's
        :class:`TileSpec` when not given.  The returned
        ``repro.core.tiling.StagedTiles`` feeds :meth:`run_tiled` — this
        is the half the pipeline's prefetch thread runs ahead of time.
        """
        from repro.core import tiling
        spec = self.config.tile if self.config.tile is not None \
            else TileSpec()
        if grid is None:
            dt = self.config.dtype if self.config.dtype is not None \
                else getattr(provider, "dtype", np.float32)
            grid = self._resolve_grid(tuple(provider.shape),
                                      np.dtype(dt), spec)
        # Halo fill is the user-space inert extreme of the filtration
        # (the tiled core negates it to the internal -inf under sublevel).
        fill = np.inf if self.config.filtration == "sublevel" else None
        return tiling.load_tile_stacks(provider, tuple(grid), ctx=ctx,
                                       fill=fill)

    def run_tiled(self, image, truncate_value=None, *, grid=None,
                  ctx=None) -> PHResult:
        """Halo-tiled PH of one (possibly device-exceeding) 2D image.

        ``image`` is one of

        * a host-resident 2D array (convenience path),
        * a **tile provider** (``shape`` / ``dtype`` /
          ``halo_tile(t, grid, fill=...)``, e.g.
          :class:`repro.data.astro.AstroImage`) — tiles are generated and
          placed on device one at a time, so no host ever materializes the
          image (Variant-1 ``load_self`` for tiles), or
        * a ``repro.core.tiling.StagedTiles`` already staged by
          :meth:`stage_tiles` (the pipeline's prefetch path; pass the
          threshold explicitly, there is no image to derive it from).

        Bit-identical to :meth:`run` with ``candidate_mode="exact"`` while
        keeping per-tile working memory proportional to the tile size.
        ``grid`` overrides the config's :class:`TileSpec` grid (auto-chosen
        from ``max_tile_pixels`` when both are None); ``ctx`` places tile
        rows on the mesh's data axes via ``shard_map``.  Overflow regrows
        per level: tile capacities toward the tile pixel count on tile
        overflow, ``max_features`` toward the image pixel count on
        seam-merge overflow.
        """
        from repro.core import tiling
        cfg = self.config
        if cfg.candidate_mode != "exact":
            raise ValueError("run_tiled supports candidate_mode='exact' "
                             "only (the paper-literal distillation has no "
                             "tiled equivalence proof)")
        staged = image if isinstance(image, tiling.StagedTiles) else None
        provider = None
        if staged is None and hasattr(image, "halo_tile"):
            provider = image
            if truncate_value is None:
                truncate_value = self.provider_threshold(provider)
            staged = self.stage_tiles(provider, grid=grid, ctx=ctx)
        spec = cfg.tile if cfg.tile is not None else TileSpec()
        if staged is not None:
            if cfg.dtype is not None:       # apply the config dtype policy
                staged = dataclasses.replace(
                    staged, pvals=jnp.asarray(staged.pvals).astype(cfg.dtype))
            if grid is not None and tuple(grid) != tuple(staged.grid):
                raise ValueError(f"grid={tuple(grid)} does not match the "
                                 f"staged tiles' grid {staged.grid}")
            shape, grid = staged.shape, staged.grid
            dtype = jnp.asarray(staged.pvals).dtype
            x = None
        else:
            x = self.cast_input(image)
            if x.ndim != 2:
                raise ValueError(f"expected 2D image, got shape {x.shape}")
            if truncate_value is None:
                truncate_value = self._auto_threshold(image)
            if grid is None:
                grid = self._resolve_grid(x.shape, x.dtype, spec)
            shape, dtype = x.shape, x.dtype
        grid = tuple(grid)
        tiling.validate_grid(shape, grid)
        h, w = shape
        n = h * w
        tile_n = (h // grid[0]) * (w // grid[1])
        truncated = truncate_value is not None
        tvj = jnp.asarray(truncate_value, threshold_dtype(dtype)) \
            if truncated else None

        mf = min(cfg.max_features, n)
        tf = min(spec.max_features_per_tile, tile_n)
        tk = min(spec.max_candidates_per_tile, tile_n)
        # Regrow ceilings apply per level: the configured feature ceiling
        # bounds the global diagram (and per-tile roots), the candidate
        # ceiling bounds per-tile candidates — each clamped to the pixel
        # count it can never usefully exceed.
        ceil_mf, _ = self._ceilings(n)
        ceil_tf, ceil_tk = self._ceilings(tile_n)
        memo_key = ("tiled", tuple(shape), grid, str(dtype), ctx)
        if cfg.auto_regrow:
            with self._lock:
                got = self._grown.get(memo_key)
            if got:
                mf = max(mf, min(got[0], n))
                tf = max(tf, min(got[1], tile_n))
                tk = max(tk, min(got[2], tile_n))

        attempts = 0
        while True:
            with trace.span("ph.dispatch", max_candidates=tk):
                if staged is not None:
                    plan = self.tiled_stacks_plan(tuple(shape), dtype, grid,
                                                  mf, tf, tk, truncated, ctx)
                    out = plan(staged.pvals, staged.pgidx, tvj) \
                        if truncated else plan(staged.pvals, staged.pgidx)
                else:
                    plan = self.tiled_plan(shape, dtype, grid, mf, tf, tk,
                                           truncated, ctx)
                    out = plan(x, tvj) if truncated else plan(x)
                if self._stream_results():
                    start_d2h(out, self.overlap_counters)
            with trace.span("ph.wait"):
                tile_of = bool(out.tile_overflow)
                merge_of = bool(out.merge_overflow)
            if not (tile_of or merge_of) or not cfg.auto_regrow \
                    or attempts >= cfg.max_regrows:
                break
            nmf = min(mf * cfg.regrow_factor, ceil_mf) if merge_of else mf
            ntf, ntk = tf, tk
            if tile_of:
                ntf = min(tf * cfg.regrow_factor, ceil_tf)
                ntk = min(tk * cfg.regrow_factor, ceil_tk)
            if (nmf, ntf, ntk) == (mf, tf, tk):
                break   # at the ceilings: residual overflow is reported
            with self._lock:
                self.regrow_log.append({"kind": "tiled",
                                        "from": (mf, tf, tk),
                                        "to": (nmf, ntf, ntk)})
            mf, tf, tk = nmf, ntf, ntk
            attempts += 1
        if attempts:
            with self._lock:
                self._grown[memo_key] = (mf, tf, tk)

        # final_max_candidates reports the per-tile candidate capacity (the
        # knob that actually regrows on the tiled path).
        stats = RegrowStats(attempts, mf, tk, bool(tile_of or merge_of))
        eff = cfg.replace(
            max_features=mf,
            tile=spec.replace(grid=grid, max_features_per_tile=tf,
                              max_candidates_per_tile=tk))
        return PHResult(out.diagram, eff, stats, truncate_value)

    def run_delta(self, image, truncate_value=None, *, grid=None
                  ) -> PHResult:
        """Delta-recompute tiled PH of one frame against the engine's
        frame store — **bit-identical** to :meth:`run_tiled` on the same
        frame, at O(changed area) compute for near-duplicate frames.

        ``image`` accepts the same forms as :meth:`run_tiled` (host 2D
        array, tile provider, or ``StagedTiles``).  The frame's per-tile
        content-hash grid (:func:`repro.core.delta.frame_digests`) is
        classified against the :class:`repro.cache.DiagramCache`:

        * **full hit** — the cached :class:`PHResult` is returned without
          touching the device;
        * **partial hit** — phases A+B re-run for the dirty tiles only
          (padded to a power-of-two bucket), the fresh rows are scattered
          into the cached :class:`TileBoundaryState`, and the O(boundary)
          seam merge replays;
        * **miss** (or ``config.delta`` disabled/absent) — every tile is
          dirty; the same scatter program runs against an all-zeros base,
          so cold and warm paths share compiled programs bit for bit.

        ``PHResult.delta`` carries a :class:`repro.core.delta.DeltaStats`
        (tiles recomputed, hit kind).  Regrow mirrors :meth:`run_tiled`
        and shares its sticky capacity memo; a tile-capacity regrow
        invalidates the cached state (its arrays are shape-static), a
        merge-only regrow keeps the fresh phase-AB rows and re-runs just
        the merge program.
        """
        from repro.cache import DiagramCache, FrameCacheEntry
        from repro.core import delta as delta_mod, tiling
        cfg = self.config
        dspec = cfg.delta
        if dspec is None or not dspec.enabled:
            res = self.run_tiled(image, truncate_value, grid=grid)
            n_t = np.prod(res.config.tile.grid)
            return dataclasses.replace(res, delta=delta_mod.DeltaStats(
                int(n_t), int(n_t), "cold"))
        if cfg.candidate_mode != "exact":
            raise ValueError("run_delta supports candidate_mode='exact' "
                             "only (it rides the tiled path)")
        staged = image if isinstance(image, tiling.StagedTiles) else None
        if staged is None and hasattr(image, "halo_tile"):
            provider = image
            if truncate_value is None:
                truncate_value = self.provider_threshold(provider)
            staged = self.stage_tiles(provider, grid=grid)
        spec = cfg.tile if cfg.tile is not None else TileSpec()
        if staged is not None:
            if cfg.dtype is not None:
                staged = dataclasses.replace(
                    staged, pvals=jnp.asarray(staged.pvals).astype(cfg.dtype))
            if grid is not None and tuple(grid) != tuple(staged.grid):
                raise ValueError(f"grid={tuple(grid)} does not match the "
                                 f"staged tiles' grid {staged.grid}")
            shape, grid = staged.shape, staged.grid
            dtype = jnp.asarray(staged.pvals).dtype
            source = staged
        else:
            x = self.cast_input_host(image)   # host-side: hashing + dirty
            if x.ndim != 2:                   # stacks never bounce via HBM
                raise ValueError(f"expected 2D image, got shape {x.shape}")
            if truncate_value is None:
                truncate_value = self._auto_threshold(image)
            if grid is None:
                grid = self._resolve_grid(x.shape, x.dtype, spec)
            shape, dtype = x.shape, x.dtype
            source = x
        grid = tuple(grid)
        tiling.validate_grid(shape, grid)
        h, w = shape
        n = h * w
        n_tiles = grid[0] * grid[1]
        tile_n = (h // grid[0]) * (w // grid[1])
        tile_shape = (h // grid[0] + 2, w // grid[1] + 2)
        truncated = truncate_value is not None
        tvj = jnp.asarray(truncate_value, threshold_dtype(dtype)) \
            if truncated else None
        tv_key = float(truncate_value) if truncated else None

        digests, raw = delta_mod.frame_digests(
            source, grid, algo=dspec.hash_algo, with_bytes=dspec.verify,
            filtration=cfg.filtration)
        # Everything that must match for a cached state row to be
        # bit-reusable (threshold included: it filters inside phase B).
        context = (tuple(shape), grid, str(dtype), dspec.hash_algo, tv_key,
                   cfg.plan_key())
        with self._lock:
            if self._delta_cache is None:
                self._delta_cache = DiagramCache(dspec.cache_entries)
            cache = self._delta_cache

        mf = min(cfg.max_features, n)
        tf = min(spec.max_features_per_tile, tile_n)
        tk = min(spec.max_candidates_per_tile, tile_n)
        ceil_mf, _ = self._ceilings(n)
        ceil_tf, ceil_tk = self._ceilings(tile_n)
        # Shared with run_tiled so cold and delta runs of one frame family
        # agree on regrown capacities (equal capacities => equal plans).
        memo_key = ("tiled", tuple(shape), grid, str(dtype), None)
        if cfg.auto_regrow:
            with self._lock:
                got = self._grown.get(memo_key)
            if got:
                mf = max(mf, min(got[0], n))
                tf = max(tf, min(got[1], tile_n))
                tk = max(tk, min(got[2], tile_n))

        kind, entry, dirty_mask = cache.lookup(
            context, digests, capacities=(mf, tf, tk), tile_bytes=raw)
        if kind == "hit":
            return dataclasses.replace(
                entry.result,
                delta=delta_mod.DeltaStats(n_tiles, 0, "full"))
        if kind == "partial":
            dirty = np.flatnonzero(dirty_mask)
            base = entry.state
        else:
            dirty = np.arange(n_tiles)
            base = None

        attempts = 0
        while True:
            if base is None:
                base = delta_mod.empty_state(shape, grid, dtype, tf, tk)
            bucket = delta_mod.dirty_bucket(len(dirty), n_tiles)
            pv, pg, slots = delta_mod.dirty_stacks(source, grid, dirty,
                                                   bucket, cfg.filtration)
            ab = self.delta_ab_plan(tile_shape, dtype, bucket, tf, tk,
                                    truncated)
            fresh = ab(pv, pg, tvj) if truncated else ab(pv, pg)
            mg = self.delta_merge_plan(shape, dtype, grid, bucket, mf, tf,
                                       tk, truncated)
            new_state, out = mg(base, fresh, slots, tvj) if truncated \
                else mg(base, fresh, slots)
            if self._stream_results():
                start_d2h(out, self.overlap_counters)
            tile_of = bool(out.tile_overflow)
            merge_of = bool(out.merge_overflow)
            if not (tile_of or merge_of) or not cfg.auto_regrow \
                    or attempts >= cfg.max_regrows:
                break
            nmf = min(mf * cfg.regrow_factor, ceil_mf) if merge_of else mf
            ntf, ntk = tf, tk
            if tile_of:
                ntf = min(tf * cfg.regrow_factor, ceil_tf)
                ntk = min(tk * cfg.regrow_factor, ceil_tk)
            if (nmf, ntf, ntk) == (mf, tf, tk):
                break   # at the ceilings: residual overflow is reported
            with self._lock:
                self.regrow_log.append({"kind": "delta",
                                        "from": (mf, tf, tk),
                                        "to": (nmf, ntf, ntk)})
            if (ntf, ntk) != (tf, tk):
                # Tile capacities grew: the cached/base state arrays are
                # the wrong shape — recompute every tile from scratch.
                dirty = np.arange(n_tiles)
                base = None
                kind = "miss"
            mf, tf, tk = nmf, ntf, ntk
            attempts += 1
        if attempts:
            with self._lock:
                got = self._grown.get(memo_key)
                if got is None or got < (mf, tf, tk):
                    self._grown[memo_key] = (mf, tf, tk)

        stats = RegrowStats(attempts, mf, tk, bool(tile_of or merge_of))
        eff = cfg.replace(
            max_features=mf,
            tile=spec.replace(grid=grid, max_features_per_tile=tf,
                              max_candidates_per_tile=tk))
        hit = "partial" if kind == "partial" else "miss"
        dstats = delta_mod.DeltaStats(n_tiles, int(len(np.unique(dirty))),
                                      hit)
        result = PHResult(out.diagram, eff, stats, truncate_value, dstats)
        # put() on an existing (context, digests) key replaces in place, so
        # pipeline retries / resumed rounds never double-insert.
        cache.put(context, FrameCacheEntry(
            digests=digests, state=new_state, result=result,
            capacities=(mf, tf, tk), tile_bytes=raw))
        return result

    def run_sequence(self, frames, truncate_values=None, *, grid=None):
        """Generator: :meth:`run_delta` over an iterable of frames (the
        survey-stream entry point).  ``truncate_values`` is a scalar
        applied to every frame or a per-frame sequence; yields one
        :class:`PHResult` per frame as it completes, so a consumer can
        stream diagrams while later frames hash."""
        for i, frame in enumerate(frames):
            if truncate_values is None:
                tv = None
            elif np.isscalar(truncate_values):
                tv = truncate_values
            else:
                tv = truncate_values[i]
            yield self.run_delta(frame, tv, grid=grid)

    def delta_cache_stats(self) -> dict:
        """Snapshot of the delta frame store's counters (zeros before the
        first ``run_delta`` call)."""
        with self._lock:
            cache = self._delta_cache
        if cache is None:
            from repro.cache import CacheStats
            return CacheStats().snapshot()
        return cache.stats.snapshot()

    def run_distributed(self, images, *, ctx=None, image_size: int = 512,
                        strategy: str = "part_LPT",
                        work_log=None, failure_injector=None,
                        max_retries: int = 3, verbose: bool = False):
        """The paper's end-to-end distributed job, engine-owned.

        Builds a sharded executor over ``ctx`` (default: one data axis over
        every local device), schedules ``images`` with the Variant-3
        ``strategy`` into shape-bucketed rounds, applies the config's
        Variant-2 filter level, records completed work in ``work_log``,
        and auto-regrows capacities on overflow (grown capacities stick
        for subsequent rounds).

        ``images``: a heterogeneous dataset — each element is an image id
        (``int``, at ``image_size``), an ``(id, size)`` / ``(id, (H, W))``
        pair, or a :class:`repro.pipeline.scheduler.ImageMeta` (the
        synthetic astro loader renders square frames only; rectangular
        specs are rejected at schedule time).  Same-shape
        images share padded shape buckets (one cached sharded plan per
        bucket); images larger than the config's
        ``TileSpec.max_tile_pixels`` schedule as tile-grid rounds through
        :meth:`run_tiled`, loaded tile-by-tile so no host materializes
        them; the driver's loader thread stages round r+1 while round r
        computes (``config.prefetch_rounds``).

        Returns :class:`repro.pipeline.driver.PipelineResult`.
        """
        from repro.launch.mesh import auto_context
        from repro.pipeline.driver import run_pipeline
        from repro.pipeline.executor import ShardedPHExecutor
        executor = ShardedPHExecutor(self, ctx or auto_context(),
                                     image_size=image_size)
        return run_pipeline(executor, images, strategy=strategy,
                            work_log=work_log,
                            failure_injector=failure_injector,
                            max_retries=max_retries, verbose=verbose)
