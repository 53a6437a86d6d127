"""The program's own spans and device stages, read for a traced run.

The engine records host spans (``repro.ph.trace``: ``ph.run`` with its
``ph.cast``/``ph.dispatch``/``ph.wait``/``ph.regrow`` children,
``ph.threshold``) and names each device stage with a ``ph.*`` named scope,
which every compiled instruction carries in its ``op_name``.  From these:

* the window's spans: program spans that ended inside
  ``[run.t0, run.units[-1].done]``;
* the stage of each traced device operation: the ``stage_map()`` of the
  plans the window's ``ph.run`` spans name; an instruction name that two
  of those plans map to different stages is left unattributed;
* the innermost program span open at each host instant, to name the
  chip's idle time (spans are placed in a stretch by its ``offset``).

A program without these spans (no ``repro.ph.trace``) yields nothing, and
each metric built on them is then left out of the result.
"""
from __future__ import annotations

import numpy as np

from bench import trace as bench_trace

# Innermost spans that are host work the chip waits on.
HOST_WORK = ("ph.threshold", "ph.cast", "ph.dispatch", "ph.regrow")

_maps: dict[int, dict] = {}     # plan id -> its stage map


def _program():
    try:
        from repro.ph import trace
    except ImportError:
        return None
    return trace


def window_spans(run) -> list:
    """Program spans that ended inside the window, oldest first."""
    trace = _program()
    if trace is None or not run.units:
        return []
    lo, hi = run.t0 * 1e9, run.units[-1].done * 1e9
    return [s for s in trace.spans() if lo <= s.end_ns <= hi]


def instruction(op: str) -> str:
    """``%fusion.3 = f32[8] fusion(...)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%") if " = " in op else op


def stage_map(run) -> dict[str, str]:
    """``{instruction name: stage}`` over the plans the window ran."""
    trace = _program()
    if trace is None:
        return {}
    ids = sorted({s.attrs["plan"] for s in window_spans(run)
                  if s.name == "ph.run" and "plan" in s.attrs})
    out: dict[str, str] = {}
    clash: set[str] = set()
    for pid in ids:
        if pid not in _maps:
            plan = trace.plan(pid)
            _maps[pid] = plan.stage_map() if plan is not None else {}
        for name, stage in _maps[pid].items():
            if out.setdefault(name, stage) != stage:
                clash.add(name)
    for name in clash:
        del out[name]
    return out


def op_stages(run) -> np.ndarray | None:
    """The stage of each of ``run.trace.names`` (``None`` where none), or
    ``None`` where no stage map could be read."""
    if run.trace is None:
        return None
    smap = stage_map(run)
    if not smap:
        return None
    return np.array([smap.get(instruction(n)) for n in run.trace.names],
                    object)


def stage_busy_s(run) -> dict | None:
    """Seconds some operation of each stage ran (its intervals' union),
    and ``"busy"``: all operations, summed over stretches and chips."""
    stages = op_stages(run)
    if stages is None:
        return None
    out = {"busy": 0.0}
    for st in run.trace.stretches:
        for ids, iv, _ in st.ops.values():
            out["busy"] += bench_trace.union_length(iv) / 1e9
            of = stages[ids] if len(ids) else np.zeros(0, object)
            for stage in set(of) - {None}:
                out[stage] = out.get(stage, 0.0) + \
                    bench_trace.union_length(iv[of == stage]) / 1e9
    return out


def innermost(spans) -> tuple[np.ndarray, list]:
    """Host-clock ``(k + 1,)`` boundaries and ``k`` labels: the innermost
    span open over each piece (the one opened last), ``None`` where no
    span is open."""
    if not spans:
        return np.zeros(0), []
    starts = np.array([s.start_ns for s in spans], float)
    ends = np.array([s.end_ns for s in spans], float)
    edges = np.unique(np.concatenate([starts, ends]))
    mids = (edges[:-1] + edges[1:]) / 2
    open_ = (starts[None, :] <= mids[:, None]) & \
        (ends[None, :] > mids[:, None])
    latest = np.where(open_, starts[None, :], -np.inf).max(axis=1)
    # Among spans opened at the same instant, the one that ends first.
    last = open_ & (starts[None, :] == latest[:, None])
    pick = np.argmin(np.where(last, ends[None, :], np.inf), axis=1)
    labels = [spans[j].name if last[i, j] else None
              for i, j in enumerate(pick)]
    return edges, labels


def _covered(g: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Length of the sorted, disjoint intervals ``g`` below each ``x``."""
    if len(g) == 0:
        return np.zeros_like(x)
    length = g[:, 1] - g[:, 0]
    before = np.concatenate([[0.0], np.cumsum(length)])
    i = np.searchsorted(g[:, 0], x, side="right")
    last = np.clip(i - 1, 0, None)
    part = np.clip(x - g[last, 0], 0, length[last])
    return np.where(i > 0, before[last] + part, 0.0)


def idle_by_span(run) -> dict | None:
    """Seconds the chips idled in the traced stretches, by the innermost
    program span open on the host meanwhile (``None``: no span open)."""
    if run.trace is None:
        return None
    edges, labels = innermost(window_spans(run))
    out: dict = {}
    for st in run.trace.stretches:
        for _, iv, _ in st.ops.values():
            g = bench_trace.gaps(iv, st.lo, st.hi) - st.offset
            total = float(np.sum(g[:, 1] - g[:, 0]))
            if len(edges):
                per = np.diff(_covered(g, edges))
                for label, sec in zip(labels, per):
                    out[label] = out.get(label, 0.0) + sec / 1e9
                total -= float(per.sum())
            out[None] = out.get(None, 0.0) + total / 1e9
    return out
