"""CLI: pairwise diagram-distance matrices over a batch of frames.

Computes persistence diagrams for a batch of synthetic astro frames (or
any ``.npy`` stack) through :class:`repro.ph.PHEngine`, then the
(B, B) sliced-Wasserstein and bottleneck-bound matrices through the
``ph_distance`` kernel package, and prints a JSON report::

  PYTHONPATH=src python -m repro.launch.ph_distances \
      --images 8 --size 256 --filtration sublevel --n-dirs 32

``--npy`` replaces the synthetic frames with a (B, H, W) array from
disk; ``--out`` writes the matrices alongside the report.  All engine
knobs ride :meth:`repro.ph.PHConfig.from_flags`, so the distance CLI
accepts the same ``--filtration`` / backend toggles as ``ph_run``.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.launch.compile_cache import setup_compile_cache
from repro.ph import PHConfig, PHEngine


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--images", type=int, default=8)
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--npy", help="load a (B, H, W) .npy stack instead of "
                                  "synthetic frames")
    ap.add_argument("--n-dirs", dest="n_dirs", type=int, default=16,
                    help="sliced-Wasserstein projection directions")
    ap.add_argument("--filter", default="vanilla",
                    choices=["vanilla", "filter_light", "filter_std",
                             "filter_heavy"])
    ap.add_argument("--filtration", default="superlevel",
                    choices=["superlevel", "sublevel"],
                    help="filtration direction the diagrams are computed "
                         "under (distances canonicalize internally, so "
                         "matrices of dual runs on negated frames match "
                         "bit-for-bit)")
    ap.add_argument("--max-features", type=int, default=8192)
    ap.add_argument("--max-candidates", type=int, default=32768)
    ap.add_argument("--use-pallas", dest="use_pallas", action="store_true",
                    default=None,
                    help="force the Pallas distance kernel (it has no "
                         "Mosaic lowering: with --interpret it runs in the "
                         "Pallas interpreter, on TPU the XLA path runs)")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", help="write {sw, bottleneck} matrices as .npz")
    args = ap.parse_args()
    setup_compile_cache()

    config = PHConfig.from_flags(args)
    engine = PHEngine(config)

    if args.npy:
        frames = np.load(args.npy)
        if frames.ndim != 3:
            raise SystemExit(f"--npy needs a (B, H, W) stack, got shape "
                             f"{frames.shape}")
    else:
        from repro.data.astro import generate_image
        frames = np.stack([generate_image(i, args.size)
                           for i in range(args.images)])

    res = engine.run_batch(frames)
    sw, bn = engine.distance_matrix(res, n_dirs=args.n_dirs)
    sw, bn = np.asarray(sw), np.asarray(bn)

    iu = np.triu_indices(sw.shape[0], k=1)
    report = {
        "config": json.loads(config.to_json()),
        "images": int(sw.shape[0]),
        "n_dirs": args.n_dirs,
        "sw": {"mean": float(sw[iu].mean()) if iu[0].size else 0.0,
               "max": float(sw.max())},
        "bottleneck": {"mean": float(bn[iu].mean()) if iu[0].size else 0.0,
                       "max": float(bn.max())},
        "plan_cache": engine.plan_stats(),
    }
    if args.out:
        np.savez(args.out, sw=sw, bottleneck=bn)
        report["out"] = args.out
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
