"""Bloom Filter Probe — the paper's Level-2 bloom primitive, TPU-native.

CPU version (Appendix D benchmarks 13/14): k multiply-shift hashes, k
dependent bit tests.  TPU adaptation: the filter's uint32 words stream
through VMEM in blocks; each (query, hash) pair tests its bit against the
word block it falls in via a predicated compare — the same
gather-to-dataflow rewrite as hash_probe.  Output accumulates the number
of set bits per (query, hash); membership = all k bits set (combined in
ops.py).

Hash family: h_j(x) = (a_j * x) >> (32 - s) over n_bits = 2^s bits.
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import BLOCK_1D, resolve_interpret


def _bloom_kernel(words_ref, queries_ref, coeffs_ref, hits_ref, *,
                  block_w: int, s: int):
    wj = pl.program_id(1)

    @pl.when(wj == 0)
    def init():
        hits_ref[...] = jnp.zeros_like(hits_ref)

    words = words_ref[...]                     # [block_w] uint32
    queries = queries_ref[...]                 # [block_q]
    coeffs = coeffs_ref[...]                   # [k] uint32 (odd)

    xu = queries.astype(jnp.uint32)
    hv = (xu[:, None] * coeffs[None, :]) >> jnp.uint32(32 - s)  # [q, k]
    word_idx = (hv >> jnp.uint32(5)).astype(jnp.int32)
    bit_idx = (hv & jnp.uint32(31)).astype(jnp.uint32)

    base = wj * block_w
    w_iota = base + jax.lax.broadcasted_iota(
        jnp.int32, (queries.shape[0], coeffs.shape[0], block_w), 2)
    in_block = word_idx[:, :, None] == w_iota
    bits = (words[None, None, :] >> bit_idx[:, :, None]) & jnp.uint32(1)
    hit = (in_block & (bits == 1)).any(axis=2)
    hits_ref[...] += hit.astype(jnp.int32)


def bloom_probe_kernel(words: jax.Array, queries: jax.Array,
                       coeffs: jax.Array, *, s: int,
                       block_q: int = BLOCK_1D, block_w: int = BLOCK_1D,
                       interpret: Optional[bool] = None) -> jax.Array:
    """words: [W] uint32 filter (W = 2^s / 32); queries: [Q];
    coeffs: [k] uint32 odd hash multipliers.

    Returns hits [Q, k]: 1 where hash j's bit is set for query q.
    """
    w, q = words.shape[0], queries.shape[0]
    assert w == (1 << s) // 32 and w % block_w == 0 and q % block_q == 0
    k = coeffs.shape[0]
    kernel = functools.partial(_bloom_kernel, block_w=block_w, s=s)
    return pl.pallas_call(
        kernel,
        grid=(q // block_q, w // block_w),
        in_specs=[
            pl.BlockSpec((block_w,), lambda qi, wj: (wj,)),
            pl.BlockSpec((block_q,), lambda qi, wj: (qi,)),
            pl.BlockSpec((k,), lambda qi, wj: (0,)),
        ],
        out_specs=pl.BlockSpec((block_q, k), lambda qi, wj: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((q, k), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(words, queries, coeffs)
