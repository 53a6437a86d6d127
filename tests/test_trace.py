"""Host spans (``repro.ph.trace``), the device stage scopes read back from
compiled programs, and the swept-candidate count every diagram carries."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import astro
from repro.ph import FilterLevel, OverlapSpec, PHConfig, PHEngine, Plan, \
    TileSpec, trace


def test_nesting_ids_parents_and_notes():
    t0 = time.perf_counter_ns()
    with trace.span("a", k=1) as a:
        with trace.span("b") as b:
            trace.note(n=5)
            with trace.span("c") as c:
                pass
        trace.note(m=2)
    with trace.span("d") as d:
        pass
    trace.note(ignored=1)            # no span open: nothing happens
    got = {s.name: s for s in trace.spans(t0)}
    assert set(got) == {"a", "b", "c", "d"}
    assert (a.parent_id, b.parent_id, c.parent_id) == \
        (0, a.span_id, b.span_id)
    assert a.call_id == b.call_id == c.call_id == a.span_id
    assert d.call_id == d.span_id != a.call_id and d.parent_id == 0
    assert a.attrs == {"k": 1, "m": 2} and b.attrs == {"n": 5}
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= \
        b.end_ns <= a.end_ns
    # Finished order: innermost first.
    names = [s.name for s in trace.spans(t0)]
    assert names.index("c") < names.index("b") < names.index("a")


def test_span_closes_on_error():
    t0 = time.perf_counter_ns()
    with pytest.raises(ValueError):
        with trace.span("outer"):
            with trace.span("inner"):
                raise ValueError("x")
    got = {s.name: s for s in trace.spans(t0)}
    assert got["inner"].end_ns and got["outer"].end_ns
    with trace.span("after") as after:
        pass
    assert after.parent_id == 0          # the failed spans left the stack


def test_threads_keep_their_own_stacks_and_adopt_joins_a_call():
    t0 = time.perf_counter_ns()
    seen = {}
    go = threading.Barrier(2)

    def worker(tag, parent=None):
        with trace.adopt(parent):
            with trace.span(f"w{tag}") as s:
                go.wait()            # both threads hold a span open
                seen[tag] = s
                with trace.span(f"w{tag}.child") as c:
                    seen[f"{tag}c"] = c

    with trace.span("main") as main:
        t1 = threading.Thread(target=worker, args=(1,))
        t2 = threading.Thread(target=worker, args=(2, main))
        t1.start(), t2.start()
        t1.join(), t2.join()
    # A thread without a parent starts a call of its own; an adopted one
    # joins the parent's call; neither sees the other's open span.
    assert seen[1].parent_id == 0 and seen[1].call_id == seen[1].span_id
    assert seen["1c"].parent_id == seen[1].span_id
    assert seen[2].parent_id == main.span_id
    assert seen[2].call_id == seen["2c"].call_id == main.call_id
    assert {s.name for s in trace.spans(t0)} == {
        "main", "w1", "w2", "w1.child", "w2.child"}


def test_ring_keeps_the_newest_spans():
    for i in range(trace.RING + 10):
        with trace.span("fill", i=i):
            pass
    kept = trace.spans()
    assert len(kept) == trace.RING
    assert kept[-1].attrs["i"] == trace.RING + 9
    assert kept[0].attrs["i"] == 10


def test_stage_map_reads_the_outermost_stage():
    hlo = "\n".join([
        'ENTRY %main (p: f32[4]) -> f32[4] {',
        '  %p = f32[4]{0} parameter(0)',
        '  %fusion.6 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(f)/ph.merge/while/body/ph.select/add" '
        'source_file="x.py" source_line=3}',
        '  ROOT %while.15 = (s32[]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(f)/jit(g)/ph.phase_b/while"}',
        '  %copy.1 = f32[4]{0} copy(%p)',
        '  %add.2 = f32[4]{0} add(%p, %p), '
        'metadata={op_name="jit(f)/ph.mergeish/add"}',
        '}'])
    assert trace.stage_map(hlo) == {"fusion.6": "ph.merge",
                                    "while.15": "ph.phase_b"}


@pytest.fixture(scope="module")
def frame():
    return astro.generate_image(3, 96)


def test_run_records_its_spans_and_candidates(frame):
    eng = PHEngine(PHConfig(filter_level=FilterLevel.STD, max_features=64,
                            max_candidates=16))
    t0 = time.perf_counter_ns()
    res = eng.run(frame)
    spans = trace.spans(t0)
    by = {s.name: s for s in spans}
    assert {"ph.run", "ph.threshold", "ph.cast", "ph.dispatch", "ph.wait",
            "ph.regrow"} <= set(by)
    run = by["ph.run"]
    for s in spans:
        assert s.call_id == run.span_id
    for name in ("ph.threshold", "ph.cast", "ph.regrow"):
        assert by[name].parent_id == run.span_id
    kids = [s for s in spans if s.parent_id == run.span_id]
    # The first dispatch and check, then one ph.regrow per replay, each
    # with a dispatch and a check of its own.
    assert [s.name for s in kids if s.name != "ph.regrow"].count(
        "ph.dispatch") == 1
    regrows = [s for s in spans if s.name == "ph.regrow"]
    assert len(regrows) == res.regrow.attempts >= 1
    for r in regrows:
        assert sorted(s.name for s in spans if s.parent_id == r.span_id) \
            == ["ph.dispatch", "ph.wait"]
    a = run.attrs
    assert a["candidates"] == eng.num_candidates(frame) > 16
    assert a["max_candidates"] == res.regrow.final_max_candidates
    assert a["attempts"] == res.regrow.attempts
    assert a["pixels"] == frame.size
    assert trace.plan(a["plan"]).key[3:5] == (res.regrow.final_max_features,
                                             res.regrow.final_max_candidates)
    assert int(res.diagram.n_candidates) == a["candidates"]


def test_n_candidates_agrees_across_paths(frame):
    tv = PHEngine(PHConfig(filter_level=FilterLevel.STD)).auto_threshold(
        frame)
    h, w = frame.shape
    counts = {}
    for merge, phase_c in (("scan", "fused"), ("boruvka", "fused"),
                           ("boruvka", "xla")):
        eng = PHEngine(PHConfig(max_features=2048, max_candidates=2048,
                                merge_impl=merge, phase_c_impl=phase_c,
                                tile=TileSpec(grid=(2, 2))))
        counts[merge, phase_c, "whole"] = int(
            eng.run(frame, tv).diagram.n_candidates)
        counts[merge, phase_c, "batched"] = np.asarray(eng.run_batch(
            np.stack([frame, frame[::-1]]), [tv, tv],
            dedupe=False).diagram.n_candidates).tolist()
        from repro.distributed.context import single_device_ctx
        plan = eng.sharded_plan(single_device_ctx(), (1, h, w),
                                jnp.dtype(jnp.float32), 2048, 2048)
        counts[merge, phase_c, "sharded"] = int(plan(
            jnp.asarray(frame)[None], jnp.full((1,), tv, jnp.float32)
        ).n_candidates[0])
        td = eng.run_tiled(frame, tv)
        counts[merge, phase_c, "tiled"] = int(td.diagram.n_candidates)
    want = PHEngine(PHConfig()).num_candidates(frame, tv)
    for (merge, phase_c, path), got in counts.items():
        if path == "batched":
            assert got == [want, want], (merge, phase_c)
        elif path == "tiled":
            # The seam merge sweeps every tile's pre-label candidates.
            assert got >= want, (merge, phase_c)
        else:
            assert got == want, (merge, phase_c, path)
    assert len({v for (_, _, p), v in counts.items() if p == "tiled"}) == 1


def test_every_stage_is_in_a_cpu_stage_map(frame):
    eng = PHEngine(PHConfig(max_features=2048, max_candidates=2048,
                            tile=TileSpec(grid=(2, 2))))
    t0 = time.perf_counter_ns()
    eng.run(frame, 100.0)
    eng.run_tiled(frame, 100.0)
    ids = {s.attrs["plan"] for s in trace.spans(t0) if "plan" in s.attrs}
    kinds = {trace.plan(pid).key[0] for pid in ids}
    assert kinds == {"single", "tiled"}
    seen = set()
    for pid in ids:
        smap = trace.plan(pid).stage_map()
        assert smap and set(smap.values()) <= set(trace.STAGES)
        seen |= set(smap.values())
    assert seen == set(trace.STAGES)
    assert Plan(None, ("never called",)).stage_map() == {}


@pytest.mark.parametrize("overlap", [False, True])
def test_distributed_job_spans_join_one_call(overlap):
    cfg = PHConfig(max_features=1024, max_candidates=1024,
                   filter_level=FilterLevel.STD, prefetch_rounds=1,
                   overlap=OverlapSpec() if overlap else None)
    eng = PHEngine(cfg)
    t0 = time.perf_counter_ns()
    res = eng.run_distributed(range(3), image_size=32)
    assert len(res.diagrams) == 3 and res.rounds == 3
    spans = trace.spans(t0)
    jobs = [s for s in spans if s.name == "ph.job"]
    assert len(jobs) == 1
    job = jobs[0]
    assert job.attrs == {"images": 3, "rounds": 3, "failures": 0}
    mine = [s for s in spans if s.call_id == job.span_id]
    by_id = {s.span_id: s for s in mine}
    names = [s.name for s in mine]
    for name in ("ph.load", "ph.stage", "ph.dispatch", "ph.harvest",
                 "ph.wait", "ph.load_wait"):
        assert names.count(name) == 3, (name, names)
    for s in mine:
        if s is job:
            continue
        parent = by_id[s.parent_id].name
        want = {"ph.load": "ph.job", "ph.stage": "ph.load",
                "ph.dispatch": "ph.job", "ph.harvest": "ph.job",
                "ph.wait": "ph.harvest", "ph.threshold": "ph.load",
                "ph.load_wait": "ph.job"}
        assert parent == want[s.name], (s.name, parent)
    # Each round's harvest carries its one frame's swept candidates and
    # the round's chips.
    harvests = [s.attrs for s in mine if s.name == "ph.harvest"]
    assert all(set(a) == {"candidates", "chips"} and a["chips"] == 1
               for a in harvests), harvests
    want = []
    for i in range(3):
        img = astro.generate_image(i, 32)
        want.append(eng.num_candidates(img, eng.auto_threshold(img)))
    assert sorted(c for a in harvests for c in a["candidates"]) == \
        sorted(want)
    # Rounds 2 and 3 load on the prefetch thread, while the main thread
    # dispatches: their load spans overlap other spans of the job.
    assert sum(s.name == "ph.threshold" for s in mine) == 3
