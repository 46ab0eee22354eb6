"""Scan — the paper's Level-2 scan primitives (equal + range), TPU-native.

The paper's SIMD-AVX scan (Appendix D benchmarks 5/6) maps directly onto
the VPU: a predicated compare over 8x128 lanes per cycle.  Where the CPU
version breaks on first match, the TPU version evaluates the whole block
branchlessly and reduces — on the VPU the "wasted" comparisons are free
relative to a divergent early exit (the same argument as sorted_search).

Two outputs per key block: the per-query match position (argmax of the
equal-predicate, for Get) and the per-query count of range matches (for
selectivity / range sizing).  Grid: (query_blocks, key_blocks); key blocks
stream HBM->VMEM; running state accumulates in the outputs (innermost grid
dim sequential).
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import BLOCK_1D, resolve_interpret

NOT_FOUND = 2147483647  # int32 max; plain int so kernels don't capture it


def _scan_kernel(keys_ref, queries_ref, lo_ref, hi_ref, pos_ref, cnt_ref, *,
                 block_k: int):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def init():
        pos_ref[...] = jnp.full_like(pos_ref, NOT_FOUND)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    keys = keys_ref[...]                          # [block_k]
    queries = queries_ref[...]                    # [block_q]
    lo = lo_ref[...]
    hi = hi_ref[...]
    base = kj * block_k
    idx = base + jax.lax.broadcasted_iota(jnp.int32,
                                          (queries.shape[0], block_k), 1)

    eq = keys[None, :] == queries[:, None]        # equality predicate tile
    first = jnp.where(eq, idx, NOT_FOUND).min(axis=1)
    pos_ref[...] = jnp.minimum(pos_ref[...], first)

    in_range = (keys[None, :] >= lo[:, None]) & (keys[None, :] < hi[:, None])
    cnt_ref[...] += in_range.sum(axis=1).astype(jnp.int32)


def scan_filter_kernel(keys: jax.Array, queries: jax.Array,
                       lo: jax.Array, hi: jax.Array, *,
                       block_q: int = BLOCK_1D, block_k: int = BLOCK_1D,
                       interpret: Optional[bool] = None):
    """keys: [N] unsorted; queries/lo/hi: [Q].

    Returns (pos, count): pos[q] = first index with keys[i] == queries[q]
    (NOT_FOUND if absent); count[q] = #{i : lo[q] <= keys[i] < hi[q]}.
    """
    n, q = keys.shape[0], queries.shape[0]
    assert n % block_k == 0 and q % block_q == 0, (n, q)
    kernel = functools.partial(_scan_kernel, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(q // block_q, n // block_k),
        in_specs=[
            pl.BlockSpec((block_k,), lambda qi, kj: (kj,)),
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
        ],
        out_specs=[
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q,), jnp.int32),
            jax.ShapeDtypeStruct((q,), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(keys, queries, lo, hi)
