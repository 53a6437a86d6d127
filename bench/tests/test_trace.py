"""The trace reduction on a hand-built trace and on one recorded on the
chip, and the phase-A byte count."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace
from bench.harness import kernel

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
KERNEL = ("%phase_a.1 = (s32[64,64]{1,0}, s32[64,64]{1,0}) custom-call("
          "f32[64,66]{1,0} %a), custom_call_target=\\\"tpu_custom_call\\\"")


def _plane(pid, name, line, events):
    """``events``: (name, start_us, duration_us)."""
    names = sorted({e[0] for e in events})
    meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names))
    evs = "".join(f"events {{ metadata_id: {names.index(n) + 1} "
                  f"offset_ps: {int(s * 1e6)} duration_ps: {int(d * 1e6)} }}\n"
                  for n, s, d in events)
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
            f'{meta}}}\n')


def _profile(text):
    import jax
    return jax.profiler.ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def hand():
    """Two chips over a traced stretch of [0, 100] us:

    chip 0 ops: fusion [10, 30], kernel [20, 40], copy [50, 70],
                fusion [90, 110] (cut at 100): busy 30 + 20 + 10 = 60 us;
    chip 1 ops: copy [0, 50]: busy 50 us;
    driver spans: run [5, 60], to_host [60, 100], on a host clock that
    reads 1000 us less than the trace's."""
    text = (_plane(1, "/host:CPU", "main", [("bench.trace_start", 0, 0),
                                            ("bench.trace_stop", 100, 0),
                                            ("other", 0, 100)])
            + _plane(2, "/device:TPU:0", "XLA Ops",
                     [("fusion.3", 10, 20), (KERNEL, 20, 20),
                      ("copy.1", 50, 20), ("fusion.3", 90, 20)])
            + _plane(3, "/device:TPU:1", "XLA Ops", [("copy.1", 0, 50)])
            + _plane(4, "/device:TPU:2", "XLA Ops", [("copy.1", 0, 100)]))
    spans = [("run", -995_000, -940_000), ("to_host", -940_000, -900_000)]
    return trace.Summary.from_profiles([_profile(text)], [0, 1], spans,
                                       marks=[-1_000_000])


def test_busy_and_window(hand):
    assert hand.window_s == pytest.approx(100e-6)
    shares = hand.busy_share()
    assert shares == {"/device:TPU:0": pytest.approx(0.6),
                      "/device:TPU:1": pytest.approx(0.5)}
    assert hand.busy_s == pytest.approx(55e-6)


def test_kernel_time_and_top_ops(hand):
    assert hand.op_time(kernel(ROOT, "phase_a").is_call) == \
        (1, pytest.approx(20e-6))
    top = dict(hand.top_ops())
    assert top == {"copy.1": pytest.approx(70e-6),
                   "fusion.3": pytest.approx(30e-6),
                   "phase_a.1 (custom-call)": pytest.approx(20e-6)}


def test_idle_gaps_named_by_host_span(hand):
    got = [(n, pytest.approx(s)) for n, s in hand.idle_gaps()]
    # Chip 0 idles over [0, 10] and [40, 50], both mostly inside "run",
    # and over [70, 90] inside "to_host"; chip 1 over [50, 100].
    assert got == [("to_host@1", 50e-6), ("to_host@0", 20e-6),
                   ("run@0", 10e-6), ("run@0", 10e-6)]


def test_metric_readers(hand):
    from bench.harness import Run, metric_reader
    run = Run(ROOT, "TPU v5 lite", 1.0, trace=hand)
    assert metric_reader(ROOT, "device_idle_pct").read(run) == \
        pytest.approx(45.0)
    assert metric_reader(ROOT, "chip_busy_spread_pct").read(run) == \
        pytest.approx(10.0)
    run.counters = {"frame_shape": (1024, 1024), "frame_dtype": "float32"}
    least = 1024 * 1024 * 12 / 819e9
    assert metric_reader(ROOT, "phase_a_roofline").read(run) == \
        pytest.approx(100 * least / 20e-6)


def test_short_name():
    hlo = ("%while.15 = (s32[16]{0:T(1024)S(1)}, pred[]{:T(512)}) "
           "while((s32[16]{0}, pred[]) %tuple.4), condition=%c, body=%b")
    assert trace.short_name(hlo) == "while.15 (while)"
    assert trace.short_name("%fusion.2 = f32[8,8]{1,0} fusion(f32[8,8] "
                            "%p), kind=kLoop") == "fusion.2 (fusion)"
    assert trace.short_name("copy.1") == "copy.1"


def test_union_and_gaps_against_a_loop():
    rng = np.random.default_rng(0)
    s = rng.integers(0, 1000, 200)
    iv = np.stack([s, s + rng.integers(1, 50, 200)], 1).astype(float)
    covered = np.zeros(1100, bool)
    for a, b in iv.astype(int):
        covered[a:b] = True
    assert trace.union_length(iv) == covered.sum()
    g = trace.gaps(iv, 0.0, 1100.0)
    assert np.sum(g[:, 1] - g[:, 0]) == (~covered).sum()


def test_phase_a_bytes():
    k = kernel(ROOT, "phase_a")
    assert k.bytes_moved((4096, 4096), "float32") == 4096 * 4096 * 12
    assert k.bytes_moved((1024, 1024), "float32") == 1024 * 1024 * 12


def test_stretch_ends_where_a_buffer_overflowed():
    text = (_plane(1, "/host:CPU", "main", [("bench.trace_start", 0, 0),
                                            ("bench.trace_stop", 100, 0)])
            + _plane(2, "/device:TPU:0", "XLA Ops", [("copy.1", 10, 20)])
            + _plane(3, "/device:TPU:0", "XLA TraceMe",
                     [(trace.DROPPED, 40, 60)]))
    s = trace.Summary.from_profiles([_profile(text)], [0])
    assert s.window_s == pytest.approx(40e-6)
    assert s.busy_share()["/device:TPU:0"] == pytest.approx(0.5)


def test_stretches_add_up():
    """Two sessions, [0, 100] and [500, 540] us on the trace clock, with
    the driver's clock 7 us behind the trace's in both:

    first: copy [20, 60], and a kernel call running on past the stop
    marker (cut: busy, but no whole call); second: the kernel [510, 530].
    Busy 40 + 20 + 20 = 80 of 140 us.  The longest gap, [0, 20] in
    the first (driver [-7, 13]), lies in the driver's "threshold" span;
    the second's [500, 510] in "run"."""
    first = (_plane(1, "/host:CPU", "main", [("bench.trace_start", 0, 0),
                                             ("bench.trace_stop", 100, 0)])
             + _plane(2, "/device:TPU:0", "XLA Ops",
                      [("copy.1", 20, 40), (KERNEL, 80, 50)]))
    second = (_plane(1, "/host:CPU", "main", [("bench.trace_start", 500, 0),
                                              ("bench.trace_stop", 540, 0)])
              + _plane(2, "/device:TPU:0", "XLA Ops", [(KERNEL, 510, 20)]))
    spans = [("threshold", -10_000, 15_000), ("run", 15_000, 600_000)]
    s = trace.Summary.from_profiles([_profile(first), _profile(second)], [0],
                                    spans, marks=[-7_000, 493_000])
    assert s.window_s == pytest.approx(140e-6)
    assert s.busy_s == pytest.approx(80e-6)
    assert s.op_time(kernel(ROOT, "phase_a").is_call) == \
        (1, pytest.approx(20e-6))
    assert dict(s.top_ops())["phase_a.1 (custom-call)"] == \
        pytest.approx(40e-6)
    assert [n for n, _ in s.idle_gaps()] == ["threshold@0", "run@0",
                                             "run@0", "run@0"]
    assert [d for _, d in s.idle_gaps()] == pytest.approx(
        [20e-6, 20e-6, 10e-6, 10e-6])


def test_tracer_samples_the_window():
    """On this host's devices: the stretches that begin inside the window
    are traced, each in a session of its own, and the rest skipped."""
    import time
    import jax
    import jax.numpy as jnp
    tracer = trace.Tracer([[0, 0.3], [0.5, 0.8], [60, 61]])
    tracer.start()
    t = time.perf_counter()
    with tracer.span("run"):
        while len(tracer.stats.get("stretches", [])) < 2 and \
                time.perf_counter() - t < 30:
            jnp.ones(8).sum().block_until_ready()
    tracer.stop()
    s = tracer.summary(jax.devices()[:1])
    assert len(tracer.marks) == 2
    (a0, b0, _), (a1, b1, _) = tracer.stats["stretches"]
    # Each keeps its length, the second starting late if need be.
    assert 0 <= a0 and b0 - a0 == pytest.approx(0.3, abs=0.05)
    assert 0.5 <= a1 and b1 - a1 == pytest.approx(0.3, abs=0.05)
    assert s is not None and s.window_s == pytest.approx(0.6, abs=0.1)
    assert [name for name, _, _ in tracer.spans] == ["run"]


@pytest.fixture(scope="module")
def recorded():
    """One 128² frame through ``PHEngine.run`` (capacities 1024), traced on
    a TPU v5e inside a ``bench.traced`` span."""
    import jax
    return jax.profiler.ProfileData.from_file(
        str(DATA / "frame128.xplane.pb"))


def test_recorded_trace_against_a_loop(recorded):
    span, ops = None, []
    for plane in recorded.planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name == "/host:CPU" and ev.name == "bench.traced":
                    span = (ev.start_ns, ev.end_ns)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((ev.name, ev.start_ns, ev.end_ns))
    lo, hi = span
    s = trace.Summary.from_profiles([recorded], [0], windows=[span])
    # Busy by walking the clipped intervals in start order.
    busy, reach = 0.0, lo
    for _, a, b in sorted(ops, key=lambda o: o[1]):
        a, b = max(a, lo, reach), min(b, hi)
        if b > a:
            busy += b - a
            reach = b
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.busy_share()["/device:TPU:0"] == pytest.approx(busy / (hi - lo))
    calls = [(a, b) for n, a, b in ops
             if n.startswith("%phase_a") and "custom-call(" in n]
    assert len(calls) == 1                 # one frame, one kernel call
    k = kernel(ROOT, "phase_a")
    assert s.op_time(k.is_call) == (1, pytest.approx(
        (calls[0][1] - calls[0][0]) / 1e9))
    from bench.harness import Run, metric_reader
    run = Run(ROOT, "TPU v5 lite", 1.0, trace=s,
              counters={"frame_shape": (128, 128), "frame_dtype": "float32"})
    least = 128 * 128 * 12 / 819e9
    assert metric_reader(ROOT, "phase_a_roofline").read(run) == \
        pytest.approx(100 * least * 1e9 / (calls[0][1] - calls[0][0]))
