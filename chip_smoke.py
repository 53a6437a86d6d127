"""Smoke run of the PixHomology engine on a TPU via its public entry points.

    python chip_smoke.py               # one chip, every phase below
    python chip_smoke.py --chips 4     # pipeline phase on all chips vs one
    python chip_smoke.py --rehearse    # same phases, tiny sizes, CPU, kernels
                                       # interpreted; never prints "ok"

One chip runs four phases on synthetic star fields (``repro.data.astro``):

* whole — ``PHEngine.run`` on a 4096 x 4096 frame (16.8 M pixels, ~57k
  stars at the paper's density), capacity regrow included;
* tiled — ``PHEngine.run_tiled`` on the same frame with a 2 x 2 grid; its
  diagram must equal the whole-frame diagram bit for bit;
* reference — a 1024 x 1024 frame with the default config; its diagram must
  equal ``persistence_oracle`` (a per-pixel union-find on the host) exactly;
* pipeline — ``run_distributed`` (part_LPT) on a mixed batch of 1024^2 and
  2048^2 frames plus one 4096^2 frame routed through tiles; every image must
  finish with no recovered failure.

The frame phases and the pipeline filter at ``filter_std`` (the paper's
Variant 2): unfiltered, sky noise alone makes ~1.6 M local maxima per 4096^2
frame.  Starting capacities are set per phase (see ``FULL``); everything
else is the default ``PHConfig``.  ``--chips 4`` runs only
the pipeline, over ``auto_context()`` on every chip and again on one chip
(``single_device_ctx()``) in the same process, and requires every image's
result to match bit for bit.

Each phase prints one JSON line: shape, object count, regrow attempts, final
capacities, the implementation every kernel resolved to, and seconds spent
compiling (set-up) and in the phase (smoke wall time).  These times are a
smoke record, not a benchmark.  The last line is the verdict, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite",
"count": 1}}``.  Without a TPU the script exits 2 before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Counts at 4096^2, filter_std: 173k roots, 116k candidates (~43k roots per
# 2048^2 tile); at 2048^2 33k candidates; at 1024^2 unfiltered 97k roots,
# 655k candidates.  The whole-frame and reference phases start one regrow
# tier below what their frame needs, so each regrows once on the chip; from
# the defaults they would walk up to five doubling tiers, each a fresh
# compile of a 16.8 M-pixel program.  The tiled and pipeline phases start
# at capacities that fit: every tiled regrow attempt recompiles the whole
# tiled program (~80 s on a v5e host).  The scan merge runs max_candidates
# sequential steps whatever the frame holds, so no phase starts higher than
# it needs.
FULL = dict(frame=4096, grid=(2, 2), ref=1024, sizes=(1024, 2048),
            per_size=4, tile_pixels=2048 * 2048, caps=(131072, 131072),
            pipe_caps=(262144, 65536), tile_caps=(65536, 65536),
            ref_caps=(65536, 524288))
TINY = dict(frame=256, grid=(2, 2), ref=64, sizes=(64, 128),
            per_size=4, tile_pixels=128 * 128, caps=(512, 512),
            pipe_caps=(1024, 1024), tile_caps=(256, 256),
            ref_caps=(256, 2048))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (set-up time)."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _phase(name, clock, fn):
    c0, t0 = clock.seconds, time.perf_counter()
    rec = fn()
    rec = {"phase": name, **rec,
           "setup_compile_s": round(clock.seconds - c0, 3),
           "smoke_wall_s": round(time.perf_counter() - t0, 3)}
    _emit(rec)
    return rec


def _result_record(res, engine, kind):
    d = res.diagram
    return {"objects": int(d.count), "overflow": bool(d.overflow),
            "regrow_attempts": res.regrow.attempts,
            "final_capacities": [res.regrow.final_max_features,
                                 res.regrow.final_max_candidates],
            "impls": engine.plan_stats()["impls"].get(kind, {})}


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _config(sz, interpret):
    """The tiled and pipeline phases share one engine configuration."""
    from repro.ph import FilterLevel, PHConfig, TileSpec
    return PHConfig(
        filter_level=FilterLevel.STD, interpret=interpret,
        max_features=sz["pipe_caps"][0], max_candidates=sz["pipe_caps"][1],
        tile=TileSpec(max_tile_pixels=sz["tile_pixels"],
                      max_features_per_tile=sz["tile_caps"][0],
                      max_candidates_per_tile=sz["tile_caps"][1]))


def one_chip(sz, clock, interpret):
    import numpy as np
    from repro.core import diagram_to_array, persistence_oracle
    from repro.data import astro
    from repro.launch.mesh import auto_context
    from repro.ph import PHConfig, PHEngine

    frame = astro.generate_image(0, sz["frame"])
    # One threshold for both frame phases (the tiled provider would
    # otherwise estimate its own from a sample window).
    t, _ = astro.filter_threshold(frame, "filter_std")
    eng = PHEngine(_config(sz, interpret))
    out = {}

    def whole():
        whole_eng = PHEngine(eng.config.replace(
            max_features=sz["caps"][0], max_candidates=sz["caps"][1]))
        res = whole_eng.run(frame, t)
        out["whole"] = diagram_to_array(res.diagram)
        rec = {"shape": list(frame.shape),
               **_result_record(res, whole_eng, "single")}
        _check(not rec["overflow"], "whole-frame diagram overflowed")
        return rec

    def tiled():
        # The tile-provider path the pipeline takes for oversized frames:
        # same engine, mesh and grid, so the pipeline reuses this plan.
        res = eng.run_tiled(astro.AstroImage(0, sz["frame"]), t,
                            grid=sz["grid"], ctx=auto_context())
        got = diagram_to_array(res.diagram)
        same = (got.shape == out["whole"].shape
                and bool(np.array_equal(got, out["whole"])))
        rec = {"shape": list(frame.shape), "grid": list(sz["grid"]),
               **_result_record(res, eng, "tiled_stacks"),
               "equals_whole_frame": same}
        _check(not rec["overflow"], "tiled diagram overflowed")
        _check(same, "tiled diagram differs from the whole-frame diagram")
        return rec

    def reference():
        # Unfiltered (the oracle has no threshold), through the Boruvka
        # merge, so the compiled phase-C kernel is checked here too.
        img = astro.generate_image(1, sz["ref"])
        ref_eng = PHEngine(PHConfig(
            interpret=interpret, merge_impl="boruvka",
            max_features=sz["ref_caps"][0],
            max_candidates=sz["ref_caps"][1]))
        res = ref_eng.run(img)
        got = diagram_to_array(res.diagram)
        t0 = time.perf_counter()
        want = persistence_oracle(img)
        same = got.shape == want.shape and bool(np.array_equal(got, want))
        rec = {"shape": list(img.shape),
               **_result_record(res, ref_eng, "single"),
               "oracle_host_s": round(time.perf_counter() - t0, 3),
               "equals_oracle": same}
        _check(same, "diagram differs from persistence_oracle")
        return rec

    recs = [_phase("whole", clock, whole), _phase("tiled", clock, tiled),
            _phase("reference", clock, reference),
            _phase("pipeline", clock,
                   lambda: _pipeline(eng, sz, None)[0])]
    return recs[0]["impls"].get("ph_phase_a"), \
        recs[0]["impls"].get("merge_keys")


def _batch(sz):
    imgs = [(i * len(sz["sizes"]) + j, s) for i in range(sz["per_size"])
            for j, s in enumerate(sz["sizes"])]
    return imgs + [(len(imgs), sz["frame"])]


def _pipeline(eng, sz, ctx):
    res = eng.run_distributed(_batch(sz), ctx=ctx, strategy="part_LPT")
    n = len(_batch(sz))
    rec = {"images": n, "finished": len(res.diagrams),
           "rounds": res.rounds, "recovered_failures": res.failures,
           "objects": sum(d["count"] for d in res.diagrams.values()),
           "overflowed": sum(d["overflow"] for d in res.diagrams.values()),
           "impls": eng.plan_stats()["impls"]}
    _check(rec["finished"] == n, "pipeline left images unfinished")
    _check(rec["recovered_failures"] == 0, "pipeline recovered failures")
    _check(rec["overflowed"] == 0, "pipeline diagrams overflowed")
    return rec, res.diagrams


def four_chips(sz, clock, interpret):
    from repro.distributed.context import single_device_ctx
    from repro.launch.mesh import auto_context
    from repro.ph import PHEngine

    got = {}

    def run(name, ctx):
        rec, diags = _pipeline(PHEngine(_config(sz, interpret)), sz, ctx)
        got[name] = diags
        return {"mesh_devices": ctx.mesh.size, **rec}

    _phase("pipeline_all_chips", clock, lambda: run("all", auto_context()))
    _phase("pipeline_one_chip", clock,
           lambda: run("one", single_device_ctx()))
    same = got["all"] == got["one"]
    _emit({"phase": "compare", "images": len(got["all"]),
           "bit_identical": same})
    _check(same, "four-chip results differ from the one-chip run")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pipeline over every chip and "
                         "compare it with a one-chip run")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on CPU with interpreted kernels "
                         "(never reports ok)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")

    import jax
    from repro.launch.compile_cache import setup_compile_cache

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {device}); run on a TPU "
              f"host, or pass --rehearse for the CPU dress rehearsal",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {device['count']}", file=sys.stderr)
        return 2
    cache = None if args.rehearse else setup_compile_cache()
    clock = CompileClock(jax)
    _emit({"phase": "start", "device": device, "compile_cache": cache,
           "rehearse": args.rehearse})

    sz = TINY if args.rehearse else FULL
    if args.chips == 4:
        four_chips(sz, clock, args.rehearse)
    else:
        phase_a, keys = one_chip(sz, clock, args.rehearse)
        _check(phase_a == ("interpret" if args.rehearse else "pallas"),
               f"phase A ran as {phase_a!r}, not the compiled kernel")
        _check(keys == "packed", f"merge keys resolved to {keys!r}")
    _emit({"phase": "done", "setup_compile_s": round(clock.seconds, 3)})
    if args.rehearse:
        print("chip_smoke: rehearsal passed (CPU; no device verdict)")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
