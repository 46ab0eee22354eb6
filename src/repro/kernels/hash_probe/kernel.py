"""Hash Probe — the paper's Level-2 hash primitive, TPU-native.

The CPU version (Appendix D benchmark 11) is a dependent random memory
access: hash, then chase the bucket pointer.  TPUs have no cheap scalar
pointer chase — random access inside VMEM is the one paper primitive with
no direct analogue (DESIGN.md §5).  The adaptation keeps the *algorithmic
content* of hashing (restricting each probe to one bucket) but replaces the
pointer dereference with dataflow the VPU executes densely: the bucketized
table [NB, CAP], flattened to bucket-major slots, streams through VMEM
block by block, and a probe matches a slot iff it lies in bucket hash(q)'s
slot range AND its key == q.  The hash does not
reduce comparisons on a single core the way it does on a CPU — it pays off
when buckets are sharded across chips/grid rows so each query block only
meets its resident shard (the distributed hash-partitioning the Data
Calculator's Hash element describes).

Multiply-shift family (Dietzfelbinger [25], as in the paper):
    h(x) = (a * x) >> (32 - s),  buckets = 2^s, a odd (32-bit wrap).
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import BLOCK_1D, resolve_interpret

NOT_FOUND = 2147483647  # int32 max; plain int so kernels don't capture it


def multiply_shift(x: jax.Array, a: int, s: int) -> jax.Array:
    """Bucket id in [0, 2^s): 32-bit multiply-shift hash."""
    xu = x.astype(jnp.uint32)
    return (xu * jnp.uint32(a | 1)) >> jnp.uint32(32 - s)


def _probe_kernel(tkeys_ref, tvals_ref, queries_ref, pos_ref, val_ref, *,
                  cap: int, block_k: int, a: int, s: int):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def init():
        pos_ref[...] = jnp.full_like(pos_ref, NOT_FOUND)
        val_ref[...] = jnp.zeros_like(val_ref)

    tkeys = tkeys_ref[...]                 # [block_k] flat bucket-major slots
    tvals = tvals_ref[...]                 # [block_k]
    queries = queries_ref[...]             # [block_q]
    # the probed bucket owns flat slots [bucket * cap, bucket * cap + cap):
    # a 2-D [block_q, block_k] range compare, the layout Mosaic lowers
    lo = multiply_shift(queries, a, s).astype(jnp.int32) * cap  # [block_q]
    slot = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (queries.shape[0], block_k), 1)
    match = (slot >= lo[:, None]) & (slot < lo[:, None] + cap) & \
        (tkeys[None, :] == queries[:, None])
    hit_pos = jnp.where(match, slot, NOT_FOUND).min(axis=1)
    hit_val = jnp.where(match, tvals[None, :], 0).sum(axis=1)
    better = hit_pos < pos_ref[...]
    pos_ref[...] = jnp.where(better, hit_pos, pos_ref[...])
    val_ref[...] = jnp.where(better, hit_val, val_ref[...])


def hash_probe_kernel(table_keys: jax.Array, table_values: jax.Array,
                      queries: jax.Array, *, cap: int, a: int, s: int,
                      block_q: int = BLOCK_1D, block_k: int = BLOCK_1D,
                      interpret: Optional[bool] = None):
    """table_keys/values: [NB * CAP] flattened bucket-major (NB = 2^s; empty
    and padding slots hold a sentinel key that never matches); queries: [Q].

    Returns (pos, val): pos = flat slot index of the match (NOT_FOUND if
    absent), val = matched value (0 if absent).
    """
    n, q = table_keys.shape[0], queries.shape[0]
    assert n >= (1 << s) * cap and n % block_k == 0 and q % block_q == 0
    kernel = functools.partial(_probe_kernel, cap=cap, block_k=block_k,
                               a=a, s=s)
    return pl.pallas_call(
        kernel,
        grid=(q // block_q, n // block_k),
        in_specs=[
            pl.BlockSpec((block_k,), lambda qi, kj: (kj,)),
            pl.BlockSpec((block_k,), lambda qi, kj: (kj,)),
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
        ],
        out_specs=[
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q,), jnp.int32),
            jax.ShapeDtypeStruct((q,), table_values.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(table_keys, table_values, queries)
