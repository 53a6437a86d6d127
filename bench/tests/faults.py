"""Faults planted in the program under test, for the check to catch."""
from __future__ import annotations

import dataclasses

import numpy as np


def plant(name: str, cells: dict) -> None:
    from repro.ph import engine as engine_mod
    from repro.pipeline import driver
    Engine = engine_mod.PHEngine

    if name == "answer_altered":
        # One death value of every diagram is changed where it is made.
        run = Engine.run

        def altered(self, image, truncate_value=None):
            res = run(self, image, truncate_value)
            d = res.diagram
            death = np.asarray(d.death).copy()
            death[1] = np.nextafter(death[1], np.float32(np.inf))
            return dataclasses.replace(res, diagram=d._replace(death=death))

        Engine.run = altered
        summarize = driver._summarize

        def summary_altered(diag):
            out = summarize(diag)
            out["count"] += 1
            return out

        driver._summarize = summary_altered
    elif name == "half_batch":
        # Every job returns results for only half of its frames.
        run_distributed = Engine.run_distributed

        def half(self, images, **kw):
            res = run_distributed(self, images, **kw)
            keep = sorted(res.diagrams)[: len(res.diagrams) // 2]
            res.diagrams = {k: res.diagrams[k] for k in keep}
            return res

        Engine.run_distributed = half
    elif name == "control":
        from bench import control
        for cell in cells.values():
            control.install(cell["config"])
            break
    else:
        raise ValueError(f"unknown fault {name!r}")
