"""Diagram-distance benchmark: batched SW + bottleneck (BENCH_distance.json).

For each ``(batch, size)`` row this computes the persistence diagrams of
a batch of synthetic astro-like frames through :class:`repro.ph.PHEngine`
and times the pairwise distance-matrix stage, reporting the correctness
invariants the perf gate asserts:

* ``distance_bit_identical`` — the Pallas kernel (interpret mode off-TPU:
  CI's parity path) and the XLA reference produce **bit-equal** (B, B)
  matrices for both distances;
* ``sublevel_bit_identical`` — a ``filtration="sublevel"`` engine run on
  the frames and a superlevel run on the negated frames yield bit-equal
  distance matrices (the dual-filtration contract, end to end through
  the diagram computation);
* ``pad_inert_bn`` / ``pad_inert_sw_rel`` — recomputing at doubled
  capacity (pure pad rows appended) leaves the bottleneck bound
  bit-identical and moves sliced Wasserstein by at most float-rounding
  (the sum over the augmented sorted vectors reassociates; the *value*
  is provably unchanged — see ``repro/kernels/ph_distance/ref.py``);
* ``steady_traces`` — repeated matrix calls at one shape reuse a single
  cached "distance" plan (trace exactly once).

Timings (``xla_s``, ``pallas_interpret_s``, ``prep_s``) are reported for
the trajectory record but deliberately not gated across machines.

  PYTHONPATH=src python -m benchmarks.distance_bench \
      --batches 8 --sizes 64 --out BENCH_distance.json

CI runs a smoke of this every push, uploads the artifact, and gates it
against ``benchmarks/baselines/BENCH_distance.json``.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.kernels.ph_distance import ops as dist_ops
from repro.kernels.ph_distance import ref as dist_ref
from repro.ph import PHConfig, PHEngine

ARTIFACTS = Path(__file__).resolve().parent.parent / "artifacts"


def _frames(batch: int, size: int, seed: int = 7) -> np.ndarray:
    """Synthetic astro-like frames: smooth background + point sources."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    out = np.empty((batch, size, size), np.float32)
    for b in range(batch):
        img = rng.normal(0.0, 0.05, (size, size)).astype(np.float32)
        for _ in range(max(3, size // 16)):
            cy, cx = rng.uniform(0, size, 2)
            amp = rng.uniform(0.5, 3.0)
            sig = rng.uniform(1.0, size / 16)
            img += amp * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2)
                                / (2 * sig * sig)).astype(np.float32)
        out[b] = img
    return out


def bench_row(batch: int, size: int, n_dirs: int, repeats: int) -> dict:
    frames = _frames(batch, size)
    eng = PHEngine(PHConfig())
    res = eng.run_batch(frames)
    birth, death, p_birth = eng._stack_diagrams(res)

    # Backend parity (the structural invariant CI gates).
    t0 = time.perf_counter()
    prep = (dist_ref.diagram_projections(birth, death, p_birth,
                                         n_dirs=n_dirs)
            + (dist_ref.persistence_profiles(birth, death, p_birth),))
    pts, diag, prof = [np.asarray(a) for a in prep]
    prep_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    sw_x, bn_x = [np.asarray(a) for a in
                  dist_ops.pairwise_distances(pts, diag, prof,
                                              use_pallas=False)]
    xla_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sw_p, bn_p = [np.asarray(a) for a in
                  dist_ops.pairwise_distances(pts, diag, prof,
                                              use_pallas=True,
                                              interpret=True)]
    pallas_s = time.perf_counter() - t0
    bit_identical = (np.array_equal(sw_x, sw_p)
                     and np.array_equal(bn_x, bn_p))

    # Dual-filtration contract, end to end.
    sub = PHEngine(PHConfig(filtration="sublevel"))
    ssw, sbn = [np.asarray(a) for a in
                sub.distance_matrix(sub.run_batch(frames))]
    xsw, xbn = [np.asarray(a) for a in
                eng.distance_matrix(eng.run_batch(-frames))]
    sublevel_ok = (np.array_equal(ssw, xsw) and np.array_equal(sbn, xbn))

    # Capacity-pad inertness at doubled F.
    f = birth.shape[1]
    grow = lambda a, fill: np.concatenate(  # noqa: E731
        [a, np.full_like(a, fill)], axis=1)
    sw2, bn2 = [np.asarray(a) for a in dist_ops.diagram_distances(
        grow(birth, -np.inf), grow(death, -np.inf),
        grow(p_birth, -1), n_dirs=n_dirs)]
    sw1, bn1 = [np.asarray(a) for a in dist_ops.diagram_distances(
        birth, death, p_birth, n_dirs=n_dirs)]
    pad_inert_bn = np.array_equal(bn1, bn2)
    denom = max(float(np.abs(sw1).max()), 1e-30)
    pad_inert_sw_rel = float(np.abs(sw1 - sw2).max()) / denom

    # Plan-cache behavior: after one warm call, repeats at the same
    # shape re-trace nothing (the "distance" plan kind is cached).
    eng.distance_matrix(res)
    before = eng.plan_stats()["traces"]
    for _ in range(repeats):
        eng.distance_matrix(res)
    steady_traces = eng.plan_stats()["traces"] - before

    return {"name": f"distance/b{batch}_s{size}",
            "batch": batch, "size": size, "capacity": f,
            "n_dirs": n_dirs,
            "prep_s": round(prep_s, 6),
            "xla_s": round(xla_s, 6),
            "pallas_interpret_s": round(pallas_s, 6),
            "distance_bit_identical": bool(bit_identical),
            "sublevel_bit_identical": bool(sublevel_ok),
            "pad_inert_bn": bool(pad_inert_bn),
            "pad_inert_sw_rel": pad_inert_sw_rel,
            "steady_traces": int(steady_traces)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", type=int, nargs="+", default=[8])
    ap.add_argument("--sizes", type=int, nargs="+", default=[64])
    ap.add_argument("--n-dirs", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="output path (default artifacts/BENCH_distance"
                         ".json)")
    args = ap.parse_args()

    rows = []
    for batch in args.batches:
        for size in args.sizes:
            row = bench_row(batch, size, args.n_dirs, args.repeats)
            print(json.dumps(row))
            rows.append(row)

    out = Path(args.out) if args.out else ARTIFACTS / "BENCH_distance.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rows, indent=2) + "\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
