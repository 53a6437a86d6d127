"""Pipeline driver: bucketed rounds, prefetch overlap, work-log tolerance.

Spark-equivalents (paper §4.2, §5.2): the driver only moves image *ids and
shapes* (negligible traffic, paper Variant 1); completed work is recorded
in an append-only JSONL work-log so a crashed/restarted run (or an injected
executor failure) re-schedules only the incomplete images — the Spark
lineage/checkpoint story.  Changing the executor count between rounds
re-schedules the remaining work (elastic scaling).

Streaming heterogeneous batches: the schedule is shape-bucketed
(:func:`repro.pipeline.scheduler.make_bucketed_schedule` — one padded
bucket shape per round, oversized images as tile-grid rounds), and a
background loader thread stages round r+1's shards on device while round r
computes (double buffering; ``PHConfig.prefetch_rounds``).  Failures keep
their semantics: a staged-but-unconsumed round is simply discarded and its
images re-scheduled from the work log.

Overlap engine (``PHConfig.overlap`` with ``async_harvest``): instead of
blocking on each round's results, the driver dispatches through the
pool's ``begin_staged`` and hands the deferred resolution to a harvest
thread, keeping up to ``OverlapSpec.staging_depth`` rounds in flight —
so in steady state the dispatch loop performs **zero** blocking device
readbacks (counter-verified: ``OverlapCounters.dispatch_syncs``).  The
failure injector now observes *dispatch sequence numbers* (identical to
completed-round indices in synchronous mode); on a failure, rounds whose
harvest already completed are recorded — they are real results — while
unresolved in-flight rounds are discarded and their images re-schedule
from the work log, exactly like a discarded prefetch slot.

``run_pipeline`` is the engine's distributed workhorse: call it through
:meth:`repro.ph.PHEngine.run_distributed`.  ``pool`` is any executor with
``num_executors`` / ``estimate_costs`` / ``load_round`` / ``run_staged``
plus the scheduling knobs ``bucket_rounding`` / ``pad_ok`` /
``prefetch_rounds`` / ``max_tile_pixels`` (normally
:class:`repro.pipeline.executor.ShardedPHExecutor`).
"""
from __future__ import annotations

import dataclasses
import json
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from repro.ph import trace
from repro.pipeline.scheduler import make_bucketed_schedule, normalize_images


@dataclasses.dataclass
class PipelineResult:
    diagrams: dict          # image_id -> dict summary
    rounds: int
    failures: int
    elapsed_s: float


class FailureInjector:
    """Deterministically fail chosen rounds once each (for tests/benchmarks)."""

    def __init__(self, fail_rounds=()):
        self.fail_rounds = set(fail_rounds)
        self.seen = set()

    def __call__(self, round_idx: int):
        if round_idx in self.fail_rounds and round_idx not in self.seen:
            self.seen.add(round_idx)
            raise RuntimeError(f"injected executor failure in round "
                               f"{round_idx}")


def _summarize(diag) -> dict:
    count = int(diag.count)
    return {
        "count": count,
        "overflow": bool(diag.overflow),
        "top_births": np.asarray(diag.birth[:5], np.float64).tolist(),
        "top_deaths": np.asarray(diag.death[:5], np.float64).tolist(),
        "persistence_sum": float(np.sum(
            np.clip(np.asarray(diag.birth[:count], np.float64)
                    - np.asarray(diag.death[:count], np.float64),
                    0, None))),
    }


def run_pipeline(pool, images, *, strategy: str = "part_LPT",
                 work_log: str | Path | None = None,
                 failure_injector=None, max_retries: int = 3,
                 verbose: bool = False) -> PipelineResult:
    """Run ``images`` through ``pool`` in scheduled rounds.

    The job is one ``ph.job`` span (:mod:`repro.ph.trace`): its rounds'
    ``ph.load`` and ``ph.stage`` (the executor's), ``ph.dispatch`` (the
    engine's regrow driver) and ``ph.harvest`` spans are its children,
    on the loader and harvest threads too.  ``ph.load_wait`` spans the
    dispatch loop's wait for each staged round (an inline ``ph.load``
    with prefetch off), so it reads how far the host loader sets the
    job's pace."""
    with trace.span("ph.job") as job:
        return _run(pool, images, job, strategy=strategy,
                    work_log=work_log, failure_injector=failure_injector,
                    max_retries=max_retries, verbose=verbose)


def _run(pool, images, job, *, strategy, work_log, failure_injector,
         max_retries, verbose) -> PipelineResult:
    t0 = time.time()
    metas = normalize_images(images,
                             default_size=getattr(pool, "image_size", 512))
    job.attrs["images"] = len(metas)
    log_path = Path(work_log) if work_log else None
    done: dict[int, dict] = {}

    # Resume from the work log (fault tolerance across driver restarts).
    if log_path and log_path.exists():
        for line in log_path.read_text().splitlines():
            rec = json.loads(line)
            done[rec["image_id"]] = rec["summary"]

    pending = [m for m in metas if m.image_id not in done]
    failures = 0
    rounds = 0
    attempt = 0
    last_error: RuntimeError | None = None
    prefetch = max(0, int(getattr(pool, "prefetch_rounds", 0)))
    ospec = getattr(pool, "overlap", None)
    overlapped = (ospec is not None and ospec.enabled
                  and ospec.async_harvest
                  and hasattr(pool, "begin_staged"))
    depth = ospec.staging_depth if overlapped else 0
    counters = getattr(getattr(pool, "engine", None),
                       "overlap_counters", None)

    def record(rnd, per_image):
        nonlocal rounds
        for img_id, diag in per_image.items():
            summary = _summarize(diag)
            done[img_id] = summary
            if log_path:
                with log_path.open("a") as f:
                    f.write(json.dumps(
                        {"image_id": img_id,
                         "summary": summary}) + "\n")
        rounds += 1
        if verbose:
            print(f"round {rounds}: {rnd.kind} {rnd.shape} "
                  f"{len(per_image)} images "
                  f"({len(done)}/{len(metas)})", flush=True)

    def resolve_on_harvest(pending_round):
        # Runs on the harvest thread: blocking readbacks are free here.
        if counters is not None:
            counters.bump("harvest_syncs")
        with trace.adopt(job), trace.span("ph.harvest"):
            return pending_round.resolve()

    def load_in_job(rnd):
        with trace.adopt(job):
            return pool.load_round(rnd)

    while pending and attempt <= max_retries:
        attempt += 1
        m = pool.num_executors
        # Variant-3 costs come from the executor (measured where a load
        # already ran, the render-free estimate otherwise).
        costs = pool.estimate_costs(pending)
        sched = make_bucketed_schedule(
            strategy, pending, m, costs,
            rounding=getattr(pool, "bucket_rounding", "exact"),
            pad=getattr(pool, "pad_ok", False),
            max_tile_pixels=getattr(pool, "max_tile_pixels", None))
        round_list = list(sched.rounds())
        loader = ThreadPoolExecutor(max_workers=1, thread_name_prefix="ph-load") \
            if prefetch and len(round_list) > 1 else None
        harvest = ThreadPoolExecutor(max_workers=1,
                                     thread_name_prefix="ph-harvest") \
            if overlapped else None
        staged_q: list = []     # FIFO of in-flight load futures
        harvest_q: list = []    # FIFO of (harvest future, round)
        next_load = 0
        # Dispatch sequence for the failure injector: in synchronous mode
        # it equals the completed-round counter at injection time, so
        # injector semantics are unchanged; under overlap it indexes
        # dispatch order (rounds ahead of the harvested count).
        seq = rounds

        def top_up():
            # The front future is the round about to be consumed; while a
            # round computes, at most `prefetch` later rounds stay staged.
            nonlocal next_load
            while (loader is not None and len(staged_q) < prefetch
                   and next_load < len(round_list)):
                staged_q.append(loader.submit(load_in_job,
                                              round_list[next_load]))
                next_load += 1

        try:
            for rnd in round_list:
                # Double buffering: the loader thread stages ahead while
                # this thread computes; with prefetch off, load inline.
                top_up()
                with trace.span("ph.load_wait"):
                    if staged_q:
                        staged = staged_q.pop(0).result()
                    else:
                        staged = pool.load_round(rnd)
                        next_load += 1
                top_up()
                if failure_injector:
                    failure_injector(seq)
                seq += 1
                if harvest is not None:
                    # Overlapped: dispatch now, resolve on the harvest
                    # thread; block only when the in-flight window would
                    # exceed the staging-ring depth.
                    harvest_q.append((harvest.submit(
                        resolve_on_harvest, pool.begin_staged(staged)),
                        rnd))
                    while len(harvest_q) > depth:
                        fut, rnd_done = harvest_q.pop(0)
                        record(rnd_done, fut.result())
                else:
                    record(rnd, pool.run_staged(staged))
            while harvest_q:
                fut, rnd_done = harvest_q.pop(0)
                record(rnd_done, fut.result())
        except RuntimeError as e:
            # Injected failures and JAX runtime/compile errors alike
            # (both subclass RuntimeError): retry, keep the cause.
            failures += 1
            last_error = e
            if verbose:
                print(f"FAILURE (attempt {attempt}): {e}; "
                      f"re-scheduling incomplete images", flush=True)
        finally:
            # Discard staged-but-unconsumed rounds (their images simply
            # re-schedule); surface nothing from the loader here.
            for fut in staged_q:
                try:
                    fut.result()
                except Exception:
                    pass
            # Harvest rounds already in flight: a completed round is a
            # real result (record it — its images must not re-schedule);
            # a failed or poisoned one is discarded like a prefetch slot
            # and its images re-schedule from the work log.
            while harvest_q:
                fut, rnd_done = harvest_q.pop(0)
                try:
                    record(rnd_done, fut.result())
                except Exception:
                    pass
            if harvest is not None:
                harvest.shutdown(wait=True)
            if loader is not None:
                loader.shutdown(wait=True)
        pending = [mm for mm in metas if mm.image_id not in done]

    job.attrs.update(rounds=rounds, failures=failures)
    if pending:
        raise RuntimeError(f"pipeline could not finish {len(pending)} images "
                           f"after {max_retries} retries") from last_error
    return PipelineResult(done, rounds, failures, time.time() - t0)
