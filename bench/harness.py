"""The benchmark harness: one run of one cell, driven by ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, entry point,
metric or kernel lives in a file of its own that this module finds by the
name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``  the deployment (the ``file`` key),
* ``bench/traffic/<traffic>.json`` the traffic mix; its ``entry`` names
* ``bench/entries/<entry>.py``     the driver of one program entry point,
* ``bench/metrics/<metric>.py``    the reader of one metric,
* ``bench/kernels/<kernel>.py``    one kernel's bytes and operations.

A run: set-up (device check, inputs from the seed, every program of the
cell compiled or loaded and warmed once), the measured window of
``--seconds``, then the correctness check against the plain reference,
and one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cells(root: Path = ROOT) -> dict[str, dict]:
    """Every cell of ``BENCHMARK.json``, resolved to its files' contents."""
    spec = load_spec(root)
    configs = {c["name"]: c for c in spec["configs"]}
    out = {}
    for w in spec["workloads"]:
        conf = configs[w["config"]]
        traffic = json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        out[w["name"]] = {
            "workload": w,
            "config": json.loads((root / conf["file"]).read_text()),
            "traffic": traffic,
            "entry": root / "bench" / "entries" / f"{traffic['entry']}.py",
            "end_to_end": [m for m in spec["end_to_end"]
                           if w["name"] in m.get("workloads", [w["name"]])],
            "per_layer": _per_layer(spec, w["name"]),
        }
    return out


def _per_layer(spec: dict, cell: str) -> list[dict]:
    reported = {m["name"] for m in spec["end_to_end"]
                if cell in m.get("workloads", [cell])}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def load_module(path: Path):
    """Import one file of the benchmark by its path."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, name: str):
    return load_module(root / "bench" / "metrics" / f"{name}.py")


def kernel(root: Path, name: str):
    return load_module(root / "bench" / "kernels" / f"{name}.py")


def engine_config(config: dict):
    """The program's ``PHConfig`` from a configuration's ``ph`` fields."""
    from repro.ph import FilterLevel, PHConfig
    fields = dict(config["ph"])
    fields["filter_level"] = FilterLevel(fields["filter_level"])
    return PHConfig(**fields)


class CompileClock:
    """Compile events (``jax.monitoring``) with the time each ended.

    A backend compile event is recorded for every program JAX builds or
    loads from the persistent cache, so events inside the window count
    programs the set-up did not warm."""

    def __init__(self, jax):
        self.events: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.events.append((time.perf_counter(), name, secs))

    def seconds(self) -> float:
        return sum(s for _, _, s in self.events)

    def compiles(self, t0: float, t1: float) -> int:
        return sum(1 for t, n, _ in self.events
                   if n == BACKEND_COMPILE and t0 <= t <= t1)


@dataclasses.dataclass
class Unit:
    """One finished unit of work in the window (a frame, a job)."""
    mpix: float
    done: float             # perf_counter when its result was on the host


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    root: Path
    device_kind: str
    setup_s: float
    t0: float = 0.0
    units: list = dataclasses.field(default_factory=list)
    window_compiles: int = 0
    peak_bytes: int = 0
    counters: dict = dataclasses.field(default_factory=dict)
    trace: object = None    # bench.trace.Summary of the traced stretches


class Cell:
    """Base of an entry driver (``bench/entries/<entry>.py`` defines
    ``Driver(Cell)``): ``setup`` builds and warms, ``window`` runs until
    ``seconds`` have passed and returns the finished units, ``check``
    compares with the reference once the window has closed."""

    def __init__(self, cell: dict, seed: int, devices: list, spans):
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.seed = int(seed)
        self.devices = devices
        self.span = spans          # span(name) -> context manager
        self.setup_parts: dict[str, float] = {}
        self.counters: dict[str, float] = {}
        self.attempted = 0
        self.failed = 0

    def setup(self) -> None:
        raise NotImplementedError

    def window(self, seconds: float) -> list[Unit]:
        raise NotImplementedError

    def check(self) -> list[Check]:
        raise NotImplementedError

    def after_trace(self) -> None:
        """Counts that only traced runs read, taken after the window."""


def _parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _devices(jax, chips: int, require_tpu: bool) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX sees {devs[0].platform} devices "
                       f"{[d.device_kind for d in devs]}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devs)}")
    return devs[:chips]


def _compile_cache(jax, root: Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``.  Every program is kept, so
    only a checkout's first run of a cell compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(argv, *, t_start: float, root: Path = ROOT,
        require_tpu: bool = True) -> int:
    """One run of one cell; returns the exit code."""
    err = sys.stderr
    args = _parse(argv)
    table = cells(root)
    if args.workload not in table:
        print(f"unknown workload {args.workload!r}; cells: "
              f"{sorted(table)}", file=err)
        return 2
    cell = table[args.workload]
    chips = int(cell["workload"]["chips"])

    import jax
    from bench import trace as trace_mod

    try:
        devices = _devices(jax, chips, require_tpu)
    except NoDevice as e:
        print(f"bench: {e}", file=err)
        return 3
    cache = _compile_cache(jax, root)
    clock = CompileClock(jax)
    t_init = time.perf_counter()

    tracer = trace_mod.Tracer(
        cell["traffic"].get("trace_stretches_s", []) if args.trace else [])
    driver = load_module(cell["entry"]).Driver(
        cell, args.seed, devices, tracer.span)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    parts = {"init_s": t_init - t_start, **driver.setup_parts,
             "compile_s": clock.seconds(), "setup_s": setup_s}
    print(json.dumps({"setup": parts, "compile_cache": cache}), file=err,
          flush=True)

    rec = Run(root, devices[0].device_kind, setup_s)
    rec.t0 = time.perf_counter()
    tracer.start()
    rec.units = driver.window(args.seconds)
    t_end = time.perf_counter()
    tracer.stop()
    rec.window_compiles = clock.compiles(rec.t0, t_end)
    rec.peak_bytes = _peak_bytes(devices)
    if args.trace:
        rec.trace = tracer.summary(devices)
        print(json.dumps({"trace": tracer.stats}), file=err, flush=True)
        driver.after_trace()
    rec.counters = driver.counters
    checks = driver.check()
    correct = driver.failed == 0 and bool(rec.units) and \
        all(c.ok for c in checks)

    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        value = metric_reader(root, m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": rec.peak_bytes}
    result = {"correct": correct, "attempted": driver.attempted,
              "failed": driver.failed, "metrics": metrics, "device": device}
    if args.trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value} (limit {c.limit})"
              f"{'' if c.ok else '  FAILED'}", file=err)
    err.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(t_start: float) -> int:
    return run(sys.argv[1:], t_start=t_start)
