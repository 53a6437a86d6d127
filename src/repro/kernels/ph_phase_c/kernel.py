"""Pallas kernel for the phase-C per-basin best-edge reduction.

One Boruvka round's segmented reduction — every cluster finds its best
incident saddle edge — executed block-by-block over the edge axis with
the per-cluster accumulator resident in VMEM:

* the two passes of ``ref.best_edge_reduce`` (best key, then the highest
  edge index among best-key ties) are one lexicographic max of the
  triple ``(key, edge index)`` per cluster, so the kernel makes a single
  pass over the edges;
* the grid iterates ``ceil(E / block_edges)`` edge blocks; each block's
  endpoints and keys arrive in SMEM, and a scalar loop folds every live
  edge into the accumulator rows of both endpoints.  The accumulator
  outputs use a constant ``index_map``, so the ``(nv / 1024, 8, 128)``
  tables stay in VMEM across the whole grid (initialized at
  ``program_id == 0``).  An update loads the one (8, 128) tile that
  holds the cluster (a dynamic index on the untiled leading axis) and
  selects the cluster's lane — Mosaic has no scatter, and this costs
  O(live edges), not O(edges x clusters) like a one-hot compare;
* keys travel as 32-bit words compared lexicographically: int32 ranks
  as one word, packed int64 keys as ``(high word, low word with its sign
  bit flipped)`` — the flip turns the unsigned low half into a signed
  int32 of the same order, and 64-bit values cannot enter a TPU kernel.

Bit-identity with ``ref.best_edge_reduce`` needs no tolerance argument:
lexicographic max is associative and commutative with the pad triple as
identity, so the blocked accumulation order cannot change any output bit
(``tests/test_kernels_phase_c.py`` checks it anyway, across dtypes, tie
storms, and non-divisible block sizes).  The kernel compiles for TPU
v5e at E = 32768, nv = 8192 with both key encodings
(tests/test_tpu_compile.py).

VMEM working set: (words + 1) accumulator tables of 4·nv bytes each,
double-buffered — 96 KiB at the default ``max_features = 8192`` with
packed keys, 24 MiB at nv = 2^20 (the wrapper raises the scoped VMEM
limit to fit) — plus the SMEM edge blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packed_keys import key_pad
from repro.kernels.backend import I32_ZERO

_TILE = 8 * 128                 # clusters per accumulator tile (one vreg)
_I32_MIN = int(np.iinfo(np.int32).min)
_SIGN = np.int32(_I32_MIN)


def _to_words(key):
    """32-bit words of ``key`` whose lexicographic signed order is the
    key order: (key,) for int32, (high, low ^ sign bit) for int64."""
    if key.dtype == jnp.int32:
        return (key,)
    hi = (key >> 32).astype(jnp.int32)
    lo = (key & 0xFFFFFFFF).astype(jnp.uint32).view(jnp.int32) ^ _SIGN
    return hi, lo


def _from_words(words, dtype):
    if dtype == jnp.int32:
        return words[0]
    hi, lo = words
    lo = (lo ^ _SIGN).view(jnp.uint32).astype(jnp.int64)
    return (hi.astype(jnp.int64) << 32) | lo


def _lex_gt(a, b):
    """``a > b`` for equal-length tuples of int32 arrays, lexicographic."""
    gt = a[-1] > b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        gt = (x > y) | ((x == y) & gt)
    return gt


def _reduce_kernel(*refs, n_words: int, block: int):
    ra_ref, rb_ref = refs[0], refs[1]
    key_refs = refs[2:2 + n_words]
    acc_refs = refs[2 + n_words:]           # n_words key tables + win table
    base = pl.program_id(0) * block

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for r in acc_refs[:-1]:
            r[...] = jnp.full(r.shape, _I32_MIN, jnp.int32)
        acc_refs[-1][...] = jnp.full(acc_refs[-1].shape, -1, jnp.int32)

    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def fold(v, cand):
        t = v >> 10                         # v // _TILE
        hit = (sub == ((v >> 7) & 7)) & (lane == (v & 127))
        cur = tuple(r[t] for r in acc_refs)
        take = hit & _lex_gt(cand, cur)
        for r, c, x in zip(acc_refs, cand, cur):
            r[t] = jnp.where(take, c, x)

    def body(j):
        a = ra_ref[j]

        @pl.when(a >= 0)                     # dead lanes carry endpoint -1
        def _live():
            cand = tuple(k[j] for k in key_refs) + (base + j,)
            fold(a, cand)
            fold(rb_ref[j], cand)

        return j + 1

    # A while loop with an int32 counter: fori_loop counts in int64 when
    # traced under x64, and Mosaic cannot lower that.
    jax.lax.while_loop(lambda j: j < block, body, I32_ZERO)


def best_edge_reduce(key, ra, rb, nv: int, *, block_edges: int = 1024,
                     interpret: bool = False):
    """Blocked Pallas twin of ``ref.best_edge_reduce`` (same signature
    plus the block size).  ``key`` is pre-masked (pad sentinel on dead
    lanes); ``ra``/``rb`` must be in ``[0, nv)`` on every lane."""
    e = key.shape[0]
    # A 1D SMEM block must match XLA's tiling of the edge arrays, T(1024),
    # or span the whole array.
    block = min(-(-max(1, block_edges) // 1024) * 1024, e)
    nb = -(-e // block)
    extra = nb * block - e
    pad = key_pad(key.dtype)
    alive = key > pad
    ra = jnp.where(alive, ra, -1).astype(jnp.int32)
    rb = jnp.where(alive, rb, -1).astype(jnp.int32)
    words = _to_words(key)
    if extra:
        ra, rb = (jnp.concatenate([r, jnp.full(extra, -1, jnp.int32)])
                  for r in (ra, rb))
        words = tuple(jnp.concatenate([w, jnp.zeros(extra, jnp.int32)])
                      for w in words)

    n_words = len(words)
    nt = -(-nv // _TILE)
    table = jax.ShapeDtypeStruct((nt, 8, 128), jnp.int32)
    edge_spec = pl.BlockSpec((block,), lambda i: (i,),
                             memory_space=pltpu.SMEM)
    acc_spec = pl.BlockSpec((nt, 8, 128),
                            lambda i: (I32_ZERO, I32_ZERO, I32_ZERO))
    vmem = 2 * (n_words + 1) * nt * _TILE * 4 + (4 << 20)
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, n_words=n_words, block=block),
        grid=(nb,),
        in_specs=[edge_spec] * (2 + n_words),
        out_specs=[acc_spec] * (n_words + 1),
        out_shape=[table] * (n_words + 1),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(vmem, 32 << 20)),
        interpret=interpret,
        name="phase_c",
    )(ra, rb, *words)
    flat = [o.reshape(-1)[:nv] for o in out]
    return _from_words(flat[:-1], key.dtype), flat[-1]
