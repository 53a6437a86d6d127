"""The paper's distributed job over four devices against the plain
reference: every row of every frame, every per-frame summary, and the
swept-candidate counts each round's ``ph.harvest`` span carries.

The job runs in a subprocess with four virtual CPU devices
(``--xla_force_host_platform_device_count=4``), so this process keeps its
single device.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]

JOB = r"""
import json, sys, time
import numpy as np
sys.path[:0] = sys.argv[1:3]
import jax
from bench import reference
from repro.data import astro
from repro.launch.mesh import auto_context
from repro.ph import FilterLevel, PHConfig, PHEngine, trace
from repro.pipeline.executor import ShardedPHExecutor

IDS, SIZE = list(range(10)), 48
rounds = []
begin = ShardedPHExecutor.begin_staged


def begin_staged(self, staged):
    pending = begin(self, staged)
    rounds.append((staged.rnd, pending))
    return pending


ShardedPHExecutor.begin_staged = begin_staged
eng = PHEngine(PHConfig(filter_level=FilterLevel.STD, max_features=1024,
                        max_candidates=1024))
t0 = time.perf_counter_ns()
res = eng.run_distributed([(i, SIZE) for i in IDS], ctx=auto_context(),
                          strategy="part_LPT")
harvests = [s for s in trace.spans(t0) if s.name == "ph.harvest"]
frames = {}
for rnd, pending in rounds:
    for image_id, d in pending.resolve().items():
        c = int(d.count)
        rows = np.stack([np.asarray(x[:c], np.float64) for x in
                         (d.birth, d.death, d.p_birth, d.p_death)], 1)
        img = astro.generate_image(image_id, SIZE)
        t = eng.auto_threshold(img)
        want = reference.diagram(img, t)
        frames[image_id] = {
            "rows": rows.tolist(), "want": want.tolist(),
            "overflow": bool(d.overflow),
            "summary": res.diagrams[image_id],
            "want_summary": reference.summary(want),
            "num_candidates": eng.num_candidates(img, t)}
print(json.dumps({
    "devices": len(jax.devices()), "rounds": res.rounds,
    "slots": [sorted((s, m.image_id) for s, m in rnd.entries)
              for rnd, _ in rounds],
    "notes": [{k: h.attrs.get(k) for k in ("candidates", "chips")}
              for h in harvests],
    "frames": frames}))
"""


def _job() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", JOB, str(ROOT), str(ROOT / "src")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_distributed_job_matches_the_reference():
    got = _job()
    assert got["devices"] == 4
    # 10 frames over four chips: two full rounds, then two frames.
    assert got["rounds"] == 3
    assert [len(r) for r in got["slots"]] == [4, 4, 2]
    frames = got["frames"]
    assert sorted(frames, key=int) == [str(i) for i in range(10)]
    for image_id, f in frames.items():
        assert not f["overflow"], image_id
        rows, want = np.array(f["rows"]), np.array(f["want"])
        assert len(want) > 1, image_id
        assert rows.shape == want.shape, image_id
        assert np.array_equal(rows, want), image_id
        assert f["summary"] == {**f["want_summary"], "overflow": False}, \
            image_id
    # One note per round, in slot order, on the round's harvest span.
    assert len(got["notes"]) == 3
    for note, slots in zip(got["notes"], got["slots"]):
        assert note["chips"] == 4
        assert note["candidates"] == [
            frames[str(image_id)]["num_candidates"]
            for _, image_id in slots]
