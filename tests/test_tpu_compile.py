"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode (the parity suites) cannot see what Mosaic refuses: blocks
that break the (8, 128) tiling, gathers and scatters, sorts, 64-bit values,
or i64 index maps under the packed-key x64 scope.  Each test here compiles
one kernel at the widths the engine runs on a 4096 x 4096 frame, for a v5e
chip that is described, not attached, and checks that the kernel is in the
compiled HLO as a ``tpu_custom_call``.  No chip time is spent.

The topology is described inside a module-scoped fixture, so importing this
file never loads the TPU library; where it cannot be described the tests
skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import packed_keys as pk
from repro.kernels import backend
from repro.kernels.ph_distance import ref as dist_ref
from repro.kernels.ph_phase_a import kernel as pha_kernel
from repro.kernels.ph_phase_c import kernel as phc_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dtype,keys,strip_rows", [
    ("float32", "packed", 8), ("float32", "rank", 4),
    ("uint8", "packed", 16)])
def test_phase_a_kernel_compiles_for_v5e(one_chip, dtype, keys, strip_rows):
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.dtype(dtype),
                             sharding=one_chip)
    with pk.key_scope(keys):     # the packed path traces under x64
        hlo = _compiled_hlo(
            lambda im: pha_kernel.phase_a(im, strip_rows=strip_rows), x)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("key_dtype,keys", [("int32", "rank"),
                                            ("int64", "packed")])
def test_phase_c_kernel_compiles_for_v5e(one_chip, key_dtype, keys):
    e, nv = 32768, 8192
    with pk.key_scope(keys):
        key = jax.ShapeDtypeStruct((e,), jnp.dtype(key_dtype),
                                   sharding=one_chip)
        ends = jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip)
        hlo = _compiled_hlo(
            lambda k, a, b: phc_kernel.best_edge_reduce(
                k, a, b, nv, block_edges=1024), key, ends, ends)
    assert "tpu_custom_call" in hlo


def test_distance_runs_its_named_xla_path_on_v5e(one_chip, monkeypatch):
    # The Pallas distance kernel sorts inside its body, which Mosaic
    # cannot lower: on TPU the dispatch names the XLA reference instead,
    # and that reference compiles for the chip at real widths.
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert backend.resolve("ph_distance") == backend.XLA
    assert backend.resolve("ph_phase_a") == backend.PALLAS
    b, k, f = 8, 16, 8192
    tbl = jax.ShapeDtypeStruct((b, k, f), jnp.float32, sharding=one_chip)
    prof = jax.ShapeDtypeStruct((b, f), jnp.float32, sharding=one_chip)
    hlo = _compiled_hlo(dist_ref.distance_matrix, tbl, tbl, prof)
    assert "tpu_custom_call" not in hlo


def _bench(name):
    """A module of the benchmark (``bench/`` at the repo root)."""
    import importlib
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module(name)


def test_phase_a_custom_call_keeps_its_benchmark_name(one_chip):
    # The benchmark finds the kernel in a device trace by the compiled
    # instruction name; ``pallas_call(name="phase_a")`` pins it.
    from repro.ph import trace
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.float32, sharding=one_chip)
    with pk.key_scope("packed"):
        hlo = _compiled_hlo(lambda im: pha_kernel.phase_a(im), x)
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    names = _bench("bench.trace").short_name(calls[0].strip())
    assert _bench("bench.kernels.phase_a").is_call(names)
    assert set(trace.stage_map(hlo).values()) == {"ph.phase_a", "ph.snap"}


def test_whole_frame_program_ops_map_to_stages(one_chip, monkeypatch):
    """The 4096² whole-frame plan the benchmark runs: every ``while`` and
    every fusion with an ``op_name`` lies in a ``ph.*`` stage (only the
    compiler's own relayout fusions carry none)."""
    import re
    from repro.ph import FilterLevel, PHConfig, PHEngine, trace
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cap = 262144
    eng = PHEngine(PHConfig(filter_level=FilterLevel.STD, max_features=cap,
                            max_candidates=cap))
    plan = eng._local_plan("single", (4096, 4096), jnp.dtype(jnp.float32),
                           cap, cap, True)
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.float32, sharding=one_chip)
    tv = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    hlo = plan.lower(x, tv).compile().as_text()
    smap = trace.stage_map(hlo)
    ops = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*? (while|fusion)\(")
    seen = {"while": 0, "fusion": 0}
    for line in hlo.splitlines():
        m = ops.match(line)
        if m is None:
            continue
        name, op = m.groups()
        seen[op] += 1
        if op == "while" or "op_name=" in line:
            assert name in smap, line[:200]
    assert seen["while"] >= 3 and seen["fusion"] > 0
    assert set(smap.values()) == set(trace.STAGES) - {"ph.seam"}
    assert smap[next(n for n in smap if n.startswith("phase_a"))] == \
        "ph.phase_a"
