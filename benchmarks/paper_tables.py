"""Benchmarks mirroring each table/figure of the paper (run on this CPU
container at reduced image sizes; the methodology matches the paper's).

All PH computation goes through the ``repro.ph`` facade: one ``PHEngine``
per configuration (cached in ``ENGINES``), so repeated same-shape calls hit
the compiled-plan cache instead of re-tracing — ``benchmarks/run.py``
prints the aggregate cache statistics at the end.

table1  — Variant 2 filtering levels: dropped %, PixHomology time, oracle
          ("Ripser-role") time.                         (paper Table 1)
fig6    — partitioning strategies vs executor count: lockstep-round makespan
          on measured per-image costs.                  (paper Figure 6)
fig7    — PD equality: bottleneck distance PixHomology vs oracle on a crop.
                                                        (paper Figure 7/8)
fig9_10 — time + peak memory vs crop size, PixHomology vs oracle.
                                                        (paper Figures 9/10)
fig11   — DIPHA-style comparison: whole-image-per-executor (ours) vs
          patch-split-with-halo-merge (DIPHA's strategy) at equal executor
          counts.                                       (paper Figure 11)
"""
from __future__ import annotations

import json
import time
import tracemalloc
from pathlib import Path

import jax
import numpy as np

from repro.core import persistence_oracle
from repro.data import astro
from repro.ph import PHConfig, PHEngine, TileSpec
from repro.pipeline.scheduler import make_schedule

ARTIFACTS = Path(__file__).resolve().parent.parent / "artifacts"

# One engine per distinct config — the plan cache lives as long as the
# benchmark process, so every same-(shape, config) call reuses a plan.
ENGINES: dict[PHConfig, PHEngine] = {}


def _engine(**kw) -> PHEngine:
    # auto_regrow off: the tables time exactly one dispatch at the stated
    # capacities (the pre-engine methodology); overflow is still flagged.
    kw.setdefault("auto_regrow", False)
    cfg = PHConfig(**kw)
    eng = ENGINES.get(cfg)
    if eng is None:
        eng = ENGINES[cfg] = PHEngine(cfg)
    return eng


def print_rows(rows) -> None:
    """The repo skeleton's ``name,us_per_call,derived`` CSV contract —
    shared by ``benchmarks/run.py`` and the tiled smoke CLI so the CI
    artifact and the full-run output can never diverge."""
    print("name,us_per_call,derived")
    for r in rows:
        r = dict(r)
        name = r.pop("name")
        t_s = (r.get("pixhomology_s") or r.get("round_makespan_s")
               or r.get("ours_batch_s") or r.get("value") or 0.0)
        derived = ";".join(f"{k}={v}" for k, v in r.items())
        print(f"{name},{t_s * 1e6:.1f},{derived}")


def plan_cache_summary() -> dict:
    """Aggregate plan-cache stats over every engine the benchmarks built."""
    total = {"engines": len(ENGINES), "plans": 0, "traces": 0, "calls": 0,
             "hits": 0, "misses": 0, "regrows": 0}
    for eng in ENGINES.values():
        for k, v in eng.plan_stats().items():
            if k in total:
                total[k] += v
    return total


def _timeit(fn, repeats=3):
    fn()                           # compile / warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), out


def _run_blocked(engine: PHEngine, img, t=None):
    res = engine.run(img, t)
    jax.block_until_ready(res.diagram)
    return res


def table1_filtering(size=256, n_images=4, rows=None):
    """Variant-2 filtering levels (paper table 1)."""
    if rows is None:
        rows = []
    # One engine for all levels: the threshold is passed explicitly, so the
    # filter levels share a single compiled plan (traced once).
    engine = _engine(max_features=8192, max_candidates=32768)
    for level in ("vanilla", "filter_light", "filter_std", "filter_heavy"):
        ph_times, or_times, drops = [], [], []
        for i in range(n_images):
            img = astro.generate_image(i, size)
            # Threshold derived once outside the timed region (the paper
            # times the PH computation, not the host-side statistics).
            t, frac = astro.filter_threshold(img, level)
            drops.append(frac * 100)
            dt, _ = _timeit(lambda: _run_blocked(engine, img, t))
            ph_times.append(dt)
            t0 = time.perf_counter()
            persistence_oracle(img)      # oracle has no filtering path
            or_times.append(time.perf_counter() - t0)
        rows.append({
            "name": f"table1/{level}",
            "dropped_pct": round(float(np.mean(drops)), 2),
            "pixhomology_s": round(float(np.mean(ph_times)), 4),
            "oracle_s": round(float(np.mean(or_times)), 4),
        })
    return rows


def fig6_partitioning(n_images=96, size=128, rows=None):
    """Strategy comparison under the lockstep-round makespan model, using
    measured per-image PixHomology costs (paper fig 6)."""
    if rows is None:
        rows = []
    # Measure true per-image cost once (single-image calls, shared plan).
    engine = _engine(max_features=4096, max_candidates=16384)
    costs = {}
    est = {}
    for i in range(n_images):
        img = astro.generate_image(i, size)
        t, _ = astro.filter_threshold(img, "filter_std")
        if i == 0:
            _run_blocked(engine, img, t)  # warm the plan once
        t0 = time.perf_counter()
        _run_blocked(engine, img, t)
        costs[i] = time.perf_counter() - t0
        est[i] = astro.estimate_cost_from_id(i, size)
    ids = list(range(n_images))
    for m in (2, 4, 8, 12, 16, 18):
        for strat in ("part_executors", "part_images", "part_LPT"):
            # LPT schedules on the *estimate* (Variant 3), is judged on the
            # measured cost — exactly the paper's setup.
            sched = make_schedule(strat, ids, m, est, seed=1)
            rows.append({
                "name": f"fig6/{strat}/m={m}",
                "round_makespan_s": round(sched.makespan(costs), 4),
                "queue_makespan_s": round(sched.queue_makespan(costs), 4),
            })
    return rows


def fig7_equality(size=50, rows=None):
    """Bottleneck distance between PixHomology and the oracle (paper fig 7:
    distance 0; we additionally get exact pixel-coordinate equality)."""
    if rows is None:
        rows = []
    img = astro.generate_image(11, 256)[100:100 + size, 80:80 + size]
    res = _engine(max_features=size * size,
                  max_candidates=size * size).run(img)
    got = res.to_array()
    want = persistence_oracle(img)
    exact = got.shape == want.shape and np.array_equal(got, want)
    # bottleneck distance == max row-wise birth/death deviation under exact
    # row matching (0 when exact)
    bd = 0.0 if exact else float(np.max(np.abs(got[:, :2] - want[:, :2])))
    rows.append({"name": "fig7/bottleneck_distance", "value": bd,
                 "exact_match": bool(exact),
                 "features": int(res.diagram.count)})
    return rows


def fig9_10_scaling(rows=None, sizes=(20, 50, 100, 200, 400, 800)):
    """Time + peak heap vs crop size: PixHomology vs classical oracle."""
    if rows is None:
        rows = []
    big = astro.generate_image(21, max(sizes))
    for s in sizes:
        img = big[:s, :s]
        engine = _engine(max_features=min(s * s, 16384),
                         max_candidates=min(s * s, 65536))
        dt, _ = _timeit(lambda: _run_blocked(engine, img))

        tracemalloc.start()
        persistence_oracle(img)
        _, or_peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        t0 = time.perf_counter()
        persistence_oracle(img)
        or_t = time.perf_counter() - t0

        # PixHomology device memory: fixed-size arrays ~ 5 int32/f32 planes
        # + diagram capacities (analytic; device allocator is pooled).
        ph_mem = s * s * 4 * 6
        rows.append({
            "name": f"fig9_10/size={s}",
            "pixhomology_s": round(dt, 4),
            "oracle_s": round(or_t, 4),
            "pixhomology_mem_mb": round(ph_mem / 1e6, 2),
            "oracle_peak_mb": round(or_peak / 1e6, 2),
        })
    return rows


def perf_merge_impl(rows=None, size=512):
    """Beyond-paper: sequential merge scan vs Boruvka parallel merge.

    Wall time on CPU already shows the depth effect (the scan's K steps
    serialize); on TPU the gap widens (vector units idle during the scan).
    Outputs are bit-identical (tests/test_parallel_merge.py).
    """
    if rows is None:
        rows = []
    img = astro.generate_image(31, size)
    t, _ = astro.filter_threshold(img, "filter_std")
    for impl in ("scan", "boruvka"):
        engine = _engine(max_features=16384, max_candidates=65536,
                         merge_impl=impl)
        dt, _ = _timeit(lambda: _run_blocked(engine, img, t))
        rows.append({"name": f"perf/merge_{impl}/size={size}",
                     "pixhomology_s": round(dt, 4)})
    return rows


def tiled_vs_whole(rows=None, size=256, grids=((1, 1), (2, 2), (4, 4)),
                   out_path=None):
    """Beyond-paper: halo-tiled PH vs the whole-image path on one image.

    Every grid is bit-identical to the whole-image diagram (asserted); the
    ``tiled_vs_whole_x`` column is the per-grid wall-time ratio, and the
    per-tile cost model shows working memory shrinking with the grid — the
    property that lets one image exceed a device.  Emits ``BENCH_tiled.json``
    so the perf trajectory accumulates across commits.
    """
    import jax.numpy as jnp
    from repro.core.tiling import per_tile_cost

    if rows is None:
        rows = []
    img = astro.generate_image(41, size)
    whole = _engine(max_features=8192, max_candidates=32768)
    t_whole, res_whole = _timeit(lambda: _run_blocked(whole, img))
    want = res_whole.to_array()
    rows.append({"name": f"tiled/whole/size={size}",
                 "pixhomology_s": round(t_whole, 4),
                 "tiled_vs_whole_x": 1.0,
                 "features": int(res_whole.diagram.count)})
    bench = [dict(rows[-1], grid=None)]
    for grid in grids:
        eng = _engine(max_features=8192,
                      tile=TileSpec(grid=tuple(grid),
                                    max_features_per_tile=8192,
                                    max_candidates_per_tile=32768))

        def run_tiled():
            res = eng.run_tiled(img)
            jax.block_until_ready(res.diagram)
            return res

        dt, res = _timeit(run_tiled)
        np.testing.assert_array_equal(res.to_array(), want)
        tr, tc = size // grid[0], size // grid[1]
        cost = per_tile_cost((tr, tc), jnp.float32,
                             n_tiles=grid[0] * grid[1],
                             tile_max_features=min(8192, tr * tc),
                             tile_max_candidates=min(32768, tr * tc))
        row = {"name": f"tiled/grid={grid[0]}x{grid[1]}/size={size}",
               "pixhomology_s": round(dt, 4),
               "tiled_vs_whole_x": round(dt / t_whole, 3),
               "per_tile_peak_mb": round(
                   (cost["phase_a"]["peak_bytes_est"]
                    + cost["phase_b"]["peak_bytes_est"]) / 1e6, 3),
               "exact_match": True}
        rows.append(row)
        bench.append(dict(row, grid=list(grid)))

    out_path = Path(out_path) if out_path else ARTIFACTS / "BENCH_tiled.json"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(
        {"size": size, "rows": bench}, indent=1, default=float))
    return rows


def _dipha_style_patches(img: np.ndarray, m: int):
    """DIPHA's strategy: split ONE image into m row-bands with 1-px halo,
    compute local PH per band, then merge boundary components via the
    global union-find on the seam candidates (the cross-node traffic)."""
    h = img.shape[0]
    bands = np.array_split(np.arange(h), m)
    t_total = 0.0
    seam_pixels = 0
    engine = _engine(max_features=8192, max_candidates=32768)
    for b in bands:
        lo, hi = b[0], b[-1] + 1
        lo_h, hi_h = max(0, lo - 1), min(h, hi + 1)
        patch = img[lo_h:hi_h]
        _run_blocked(engine, patch)      # warm this band shape
        t0 = time.perf_counter()
        _run_blocked(engine, patch)
        t_total = max(t_total, time.perf_counter() - t0)   # parallel bands
        seam_pixels += 2 * img.shape[1]
    # seam merge: oracle union-find on the seam rows (host-side, serial)
    t0 = time.perf_counter()
    seams = np.concatenate([img[max(0, b[-1] - 1):b[-1] + 2]
                            for b in bands[:-1]], axis=0)
    persistence_oracle(seams)
    t_merge = time.perf_counter() - t0
    return t_total + t_merge, seam_pixels


def fig11_dipha(size=384, n_images=8, rows=None):
    """Whole-image distribution (ours) vs patch-split (DIPHA-style)."""
    if rows is None:
        rows = []
    imgs = np.stack([astro.generate_image(i, size) for i in range(n_images)])
    engine = _engine(max_features=8192, max_candidates=32768)
    for m in (2, 4, 8):
        # ours: m executors each take whole images; time = ceil(n/m) rounds
        _run_blocked(engine, imgs[0])
        per_img = []
        for i in range(n_images):
            s0 = time.perf_counter()
            _run_blocked(engine, imgs[i])
            per_img.append(time.perf_counter() - s0)
        rounds = -(-n_images // m)
        ours = sum(sorted(per_img, reverse=True)[:rounds])  # lockstep bound
        dipha_t, seam = _dipha_style_patches(imgs[0], m)
        dipha_total = dipha_t * -(-n_images // 1) / 1  # sequential images
        rows.append({
            "name": f"fig11/m={m}",
            "ours_batch_s": round(ours, 4),
            "dipha_style_batch_s": round(dipha_total, 4),
            "dipha_seam_pixels_per_image": seam,
        })
    return rows
