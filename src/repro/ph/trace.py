"""Host spans of the PH engine and the plans they ran.

Every engine call records a handful of spans on the host, always on:

* :func:`span` opens ``jax.profiler.TraceAnnotation(name)``, so a profiler
  session shows the span on its host plane, on the device trace's clock,
  and records ``(name, span_id, parent_id, call_id, start_ns, end_ns,
  attrs)`` on ``time.perf_counter_ns()``.
* Parents come from a per-thread stack: a span opened inside another is
  its child.  A top-level span starts a new call, whose id is its own
  span id; its children carry that ``call_id``.  Work handed to another
  thread joins the call under :func:`adopt` (the pipeline's loader and
  harvest threads do).
* :func:`note` adds counts to the innermost open span of the calling
  thread; with no span open it does nothing.
* Finished spans live in one process-wide ring of :data:`RING` entries
  (:func:`spans` reads them).  Nothing is written to disk.

Spans sit at call granularity only: none is opened inside jitted code.
Device stages are named by ``jax.named_scope("ph.<stage>")`` in the core
instead; :func:`stage_map` reads them back from a compiled program's HLO,
and every :class:`repro.ph.engine.Plan` registers here (:func:`plan`), so
a reader can turn the ``plan`` attribute of a span into the instruction
-> stage map of the program that ran.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import re
import threading
import time
import weakref

import jax

RING = 4096

# Device stages, in program order (the ``ph.*`` named scopes of the core).
STAGES = ("ph.keys", "ph.phase_a", "ph.snap", "ph.phase_b", "ph.candidates",
          "ph.select", "ph.merge", "ph.diagram", "ph.seam")


@dataclasses.dataclass
class Span:
    name: str
    span_id: int
    parent_id: int          # 0 for a top-level span
    call_id: int            # span id of the top-level span of the call
    start_ns: int
    end_ns: int = 0         # 0 while open
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_ids = itertools.count(1)
_done: collections.deque = collections.deque(maxlen=RING)
_local = threading.local()
_plans: weakref.WeakValueDictionary = weakref.WeakValueDictionary()
_plan_ids = itertools.count(1)


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record one span around the ``with`` body; yields its :class:`Span`."""
    stack = _stack()
    up = stack[-1] if stack else None
    sid = next(_ids)
    rec = Span(name, sid, up.span_id if up else 0,
               up.call_id if up else sid, 0, attrs=attrs)
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(name):
            rec.start_ns = time.perf_counter_ns()
            try:
                yield rec
            finally:
                rec.end_ns = time.perf_counter_ns()
    finally:
        stack.pop()
        _done.append(rec)


@contextlib.contextmanager
def adopt(parent: Span | None):
    """Open this thread's spans under ``parent``, a span of another
    thread, for the ``with`` body (nothing is recorded for ``parent``)."""
    if parent is None:
        yield
        return
    stack = _stack()
    stack.append(parent)
    try:
        yield
    finally:
        stack.pop()


def note(**attrs) -> None:
    """Add ``attrs`` to the innermost open span of this thread."""
    stack = _stack()
    if stack:
        stack[-1].attrs.update(attrs)


def spans(since_ns: int = 0) -> list[Span]:
    """Finished spans still in the ring that started at or after
    ``since_ns`` (``perf_counter_ns``), oldest first."""
    return [s for s in list(_done) if s.start_ns >= since_ns]


def register_plan(p) -> int:
    """Give a plan its id and keep a weak reference to it."""
    pid = next(_plan_ids)
    _plans[pid] = p
    return pid


def plan(plan_id: int):
    """The live plan with this id, or ``None``."""
    return _plans.get(plan_id)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_STAGE = re.compile(r"(?:^|/)(%s)(?=/|$)"
                    % "|".join(re.escape(s) for s in STAGES))


def stage_map(hlo_text: str) -> dict[str, str]:
    """``{instruction name: stage}`` of a compiled program's HLO text: the
    outermost ``ph.*`` component of each instruction's ``op_name``.
    Instructions outside every stage scope are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        s = _STAGE.search(m.group(2))
        if s is not None:
            out[m.group(1)] = s.group(1)
    return out
