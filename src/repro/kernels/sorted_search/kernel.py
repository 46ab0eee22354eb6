"""Sorted Search — the paper's Level-2 access primitive, TPU-native.

Hardware adaptation (DESIGN.md §5): on a CPU the optimal sorted search is a
branching binary search (the paper's log-linear Level-2 model).  On the TPU
VPU, data-dependent branching serializes and random VMEM indexing wastes
the 8x128 lanes, so the idiomatic equivalent is a *branchless compare-count
search*: rank(q) = sum_i [keys_i <= q], computed as a tiled all-compare
over VMEM-resident key blocks.  O(N) comparisons instead of O(log N) — but
they run 8x128 per cycle with zero divergence, which beats bisection for
any node that fits VMEM (exactly the node sizes the Data Calculator's
elements describe).  This is the paper's "cross-pollination" story: a new
Level-2 implementation slots under the same Level-1 primitive.

Grid: (num_query_blocks, num_key_blocks); key blocks stream through VMEM
while the per-query rank accumulates in the int32 output (innermost grid
dim is sequential on TPU, so read-modify-write of o_ref is safe).
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import BLOCK_1D, resolve_interpret


def _search_kernel(keys_ref, queries_ref, o_ref, *, block_k: int):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def init():
        o_ref[...] = jnp.zeros_like(o_ref)

    keys = keys_ref[...]                      # [block_k]
    queries = queries_ref[...]                # [block_q]
    # all-pairs compare on the VPU: [block_q, block_k] predicate tile
    le = keys[None, :] <= queries[:, None]
    o_ref[...] += le.sum(axis=1).astype(jnp.int32)


def sorted_search_kernel(keys: jax.Array, queries: jax.Array, *,
                         block_q: int = BLOCK_1D, block_k: int = BLOCK_1D,
                         interpret: Optional[bool] = None) -> jax.Array:
    """keys: [N] sorted ascending; queries: [Q].

    Returns rank[q] = #{i : keys[i] <= q} — the searchsorted-right index.
    N and Q must divide by the block sizes (ops.py pads with +inf keys /
    repeated queries).
    """
    n, q = keys.shape[0], queries.shape[0]
    assert n % block_k == 0 and q % block_q == 0, (n, q)

    kernel = functools.partial(_search_kernel, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(q // block_q, n // block_k),
        in_specs=[
            pl.BlockSpec((block_k,), lambda qi, kj: (kj,)),
            pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
        ],
        out_specs=pl.BlockSpec((block_q,), lambda qi, kj: (qi,)),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(keys, queries)
