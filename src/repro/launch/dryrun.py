import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax-importing module (jax locks the
# device count at first init).  Everything else follows.
if os.environ.get("REPRO_DRYRUN_DEVICES"):           # test override (pre-jax)
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent without real
hardware: sharding mismatches, compile-time OOM and unsupported collectives
all fail here.  Artifacts (memory analysis, cost analysis, HLO-derived
roofline terms — see roofline/analysis.py) are written as JSON for
EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma_7b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --sweep [--multi-pod-too]   # all cells,
      one subprocess per cell (memory isolation, resumable via artifacts/)
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_path: Path,
             overrides: dict | None = None) -> dict:
    import jax
    from repro.configs.base import SHAPES, get_config
    from repro.launch import steps
    from repro.launch.mesh import make_context
    from repro.roofline import analysis

    t0 = time.time()
    ctx = make_context(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "devices": len(jax.devices())}

    try:
        if arch == "pixhomology":
            if overrides:
                rec["overrides"] = overrides
            rec.update(_run_pixhomology(ctx, shape_name, overrides))
        else:
            cfg = get_config(arch)
            if overrides:
                cfg = cfg.replace(**overrides)
                rec["overrides"] = overrides
            shape = SHAPES[shape_name]
            if shape.name == "long_500k" and not cfg.supports_long_context:
                rec["skipped"] = ("full-attention arch: quadratic at 500k; "
                                  "skipped per brief (DESIGN.md §4)")
                rec["seconds"] = time.time() - t0
                _write(out_path, rec)
                return rec
            bundle = steps.bundle_for(cfg, shape, ctx)
            with ctx.mesh:
                lowered = bundle.fn.lower(*bundle.args)
                rec["lower_ok"] = True
                compiled = lowered.compile()
                rec["compile_ok"] = True
                rec.update(_analyze(compiled, cfg, shape))
    except Exception as e:  # noqa: BLE001 — recorded, the sweep continues
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["seconds"] = round(time.time() - t0, 1)
    _write(out_path, rec)
    return rec


def _analyze(compiled, cfg, shape) -> dict:
    from repro.roofline import analysis

    out: dict = {}
    ma = compiled.memory_analysis()
    out["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    out["cost_analysis"] = {"flops": float(ca.get("flops", 0.0)),
                            "bytes_accessed":
                                float(ca.get("bytes accessed", 0.0))}
    text = compiled.as_text()
    summ = analysis.analyze_hlo(text)
    flops, bytes_ = analysis.blended_totals(
        summ, out["cost_analysis"]["flops"],
        out["cost_analysis"]["bytes_accessed"])
    out["hlo"] = {
        "flops": flops, "bytes": bytes_,
        "flops_ownparse": summ.flops, "bytes_ownparse": summ.bytes,
        "collective_bytes": summ.coll_bytes,
        "collectives_by_type": summ.coll_by_type,
        "n_while_loops": summ.n_whiles,
        "unresolved_trip_counts": summ.unresolved_trip_counts,
    }
    terms = analysis.roofline_terms(flops, bytes_, summ.coll_bytes)
    out["roofline"] = terms
    if cfg is not None:
        out["model_flops"] = analysis.model_flops(cfg, shape)
        out["params_total"] = analysis.total_params(cfg)
        out["params_active"] = analysis.active_params(cfg)
        out["useful_flops_ratio"] = (
            out["model_flops"]
            / max(flops * _n_devices_of(compiled), 1.0))
    return out


def _n_devices_of(compiled) -> int:
    import jax
    return len(jax.devices())


def _run_pixhomology(ctx, shape_name: str,
                     overrides: dict | None = None) -> dict:
    """The paper's own workload as a dry-run cell: a sharded image batch.

    ``overrides`` are :class:`PHConfig` field overrides (the hillclimb
    knobs — e.g. ``--override phase_c_impl=xla`` or
    ``--override phase_c_block=4096`` to compile-compare stage-C
    variants without touching code)."""
    import jax
    import jax.numpy as jnp
    from repro.ph import PHConfig, PHEngine

    if shape_name.startswith("ph_tiled"):
        return _run_pixhomology_tiled(shape_name)
    if shape_name.startswith("ph_hetero"):
        return _run_pixhomology_hetero(ctx, shape_name)

    presets = {"ph_batch_1k": (512, 1024, 1024, 16384, 8192),
               "ph_batch_4k": (512, 4096, 4096, 65536, 32768)}
    b, h, w, k, f = presets[shape_name]
    config = PHConfig(max_features=f, max_candidates=k,
                      use_pallas=False, auto_regrow=False)
    if overrides:
        config = config.replace(**overrides)
    engine = PHEngine(config)
    plan = engine.sharded_plan(ctx, (b, h, w), jnp.dtype(jnp.float32), f, k)
    sds = jax.ShapeDtypeStruct((b, h, w), jnp.float32)
    tsds = jax.ShapeDtypeStruct((b,), jnp.float32)
    with ctx.mesh:
        lowered = plan.lower(sds, tsds)
        compiled = lowered.compile()
    out = {"lower_ok": True, "compile_ok": True}
    out.update(_analyze(compiled, None, None))
    out.pop("model_flops", None)
    return out


def _run_pixhomology_hetero(ctx, shape_name: str) -> dict:
    """Heterogeneous pipeline cost model: one cached sharded plan per shape
    bucket.  The record shows each bucket's memory footprint and the pad
    overhead a mixed dataset pays when its shapes round up to pow2 buckets
    — the knob (`PHConfig.bucket_rounding`) the scheduler trades compile
    count against padded pixels with."""
    import jax
    import jax.numpy as jnp
    from repro.ph import PHConfig, PHEngine
    from repro.pipeline.scheduler import bucket_shape

    presets = {"ph_hetero_1k": ((320, 512, 1024), 16384, 8192)}
    sizes, k, f = presets[shape_name]
    engine = PHEngine(PHConfig(max_features=f, max_candidates=k,
                               use_pallas=False, auto_regrow=False))
    b = ctx.dp_size
    out: dict = {"lower_ok": True, "compile_ok": True, "buckets": {}}
    analyzed: dict = {}     # sizes sharing a bucket share one compile
    for size in sizes:
        hb, wb = bucket_shape((size, size), "pow2")
        name = f"{size}->bucket{hb}x{wb}"
        cell = analyzed.get((hb, wb))
        if cell is None:
            plan = engine.sharded_plan(ctx, (b, hb, wb),
                                       jnp.dtype(jnp.float32), f, k)
            with ctx.mesh:
                compiled = plan.lower(
                    jax.ShapeDtypeStruct((b, hb, wb), jnp.float32),
                    jax.ShapeDtypeStruct((b,), jnp.float32)).compile()
            cell = analyzed[(hb, wb)] = _analyze(compiled, None, None)
        out["buckets"][name] = {
            "memory": cell["memory"],
            "pad_overhead": round(hb * wb / (size * size) - 1.0, 4),
        }
    out["plan_cache"] = engine.plan_stats()
    return out


def _run_pixhomology_tiled(shape_name: str) -> dict:
    """Tiled-plan cost model: the per-tile phase programs are the unit of
    device residency, so their footprint must scale with the *tile* shape
    (plus the O(boundary) condensation table), never with the image area —
    that is what lets one image exceed a device.  The record reports the
    same tile compiled under two image sizes so the invariance is visible
    in the artifact."""
    import jax.numpy as jnp
    from repro.core.tiling import per_tile_cost

    # name -> (tile_h, tile_w, tiles at the small image, tiles at the big)
    presets = {"ph_tiled_1k": (256, 256, 16, 256),
               "ph_tiled_4k": (512, 512, 64, 1024)}
    th, tw, n_small, n_big = presets[shape_name]
    small = per_tile_cost((th, tw), jnp.float32, n_tiles=n_small)
    big = per_tile_cost((th, tw), jnp.float32, n_tiles=n_big)
    return {
        "lower_ok": True, "compile_ok": True,
        "tile_shape": [th, tw],
        "per_tile_small_image": small,
        "per_tile_big_image": big,
        "phase_a_peak_invariant": (
            small["phase_a"]["peak_bytes_est"]
            == big["phase_a"]["peak_bytes_est"]),
        "phase_b_peak_ratio": round(
            big["phase_b"]["peak_bytes_est"]
            / max(small["phase_b"]["peak_bytes_est"], 1), 3),
    }


def _write(path: Path, rec: dict):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1, default=float))


def sweep(multi_pod_too: bool, archs=None, shapes=None, force=False):
    """One subprocess per cell (memory isolation + resumability)."""
    from repro.configs.base import cells

    todo = []
    meshes = [False] + ([True] if multi_pod_too else [])
    for arch, shape_name, _skip in cells(archs, shapes):
        for mp in meshes:
            todo.append((arch, shape_name, mp))
    for shape_name in ["ph_batch_1k"]:
        for mp in meshes:
            todo.append(("pixhomology", shape_name, mp))
    todo.append(("pixhomology", "ph_tiled_1k", False))
    todo.append(("pixhomology", "ph_hetero_1k", False))

    results = []
    for i, (arch, shape_name, mp) in enumerate(todo):
        mesh_name = "2x16x16" if mp else "16x16"
        out = ARTIFACTS / f"{arch}__{shape_name}__{mesh_name}.json"
        if out.exists() and not force:
            rec = json.loads(out.read_text())
            status = ("skip" if rec.get("skipped")
                      else "ok" if rec.get("compile_ok") else "ERR")
            print(f"[{i+1}/{len(todo)}] cached {out.name}: {status}",
                  flush=True)
            results.append(rec)
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape_name, "--out", str(out)]
        if mp:
            cmd.append("--multi-pod")
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=3600)
        dt = time.time() - t0
        if out.exists():
            rec = json.loads(out.read_text())
        else:
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "error": f"subprocess died: {proc.stderr[-2000:]}"}
            _write(out, rec)
        status = ("skip" if rec.get("skipped")
                  else "ok" if rec.get("compile_ok") else "ERR")
        print(f"[{i+1}/{len(todo)}] {out.name}: {status} ({dt:.0f}s)",
              flush=True)
        if status == "ERR":
            print("    ", rec.get("error", "?")[:300], flush=True)
        results.append(rec)

    n_ok = sum(1 for r in results if r.get("compile_ok"))
    n_skip = sum(1 for r in results if r.get("skipped"))
    n_err = len(results) - n_ok - n_skip
    print(f"SWEEP DONE: {n_ok} ok, {n_skip} skipped, {n_err} errors",
          flush=True)
    return 1 if n_err else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--multi-pod-too", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--archs", nargs="*")
    ap.add_argument("--shapes", nargs="*")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides key=value (hillclimb knobs)")
    args = ap.parse_args()

    if args.sweep:
        sys.exit(sweep(args.multi_pod_too, args.archs, args.shapes,
                       args.force))

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    out = Path(args.out) if args.out else \
        ARTIFACTS / f"{args.arch}__{args.shape}__{mesh_name}.json"
    rec = run_cell(args.arch, args.shape, args.multi_pod, out,
                   overrides or None)
    ok = rec.get("compile_ok") or rec.get("skipped")
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback",)}, indent=1, default=float))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
