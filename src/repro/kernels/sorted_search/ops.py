"""Jit'd public wrapper for the sorted-search kernel: padding + lookup.

``sorted_search`` returns searchsorted-right ranks; ``sorted_get`` layers a
point lookup on top (the Data Calculator's Get over an ODP terminal node).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.runtime import BLOCK_1D, resolve_interpret
from repro.kernels.sorted_search.kernel import sorted_search_kernel


def _pad1(x: jax.Array, mult: int, value) -> jax.Array:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad,), value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def sorted_search(keys: jax.Array, queries: jax.Array,
                  block_q: int = BLOCK_1D, block_k: int = BLOCK_1D,
                  interpret: Optional[bool] = None) -> jax.Array:
    """searchsorted(keys, queries, side='right') via the Pallas kernel.

    keys must be sorted ascending.  Padding keys are +inf-like (dtype max),
    so they never count toward a rank; padded queries are sliced away.
    """
    interpret = resolve_interpret(interpret)
    n, q = keys.shape[0], queries.shape[0]
    if jnp.issubdtype(keys.dtype, jnp.floating):
        big = jnp.inf
    else:
        big = jnp.iinfo(keys.dtype).max
    keys_p = _pad1(keys, block_k, big)
    queries_p = _pad1(queries, block_q, queries[0] if q else 0)
    ranks = sorted_search_kernel(keys_p, queries_p, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    # dtype-max padding keys satisfy key <= q when q is also dtype max;
    # clamp to the true length
    return jnp.minimum(ranks[:q], n)


def sorted_get(keys: jax.Array, values: jax.Array, queries: jax.Array,
               interpret: Optional[bool] = None):
    """Point Get over a sorted columnar node: (found mask, values).

    The Data Calculator's ``SortedSearch(ColumnStore) + RandomAccess(value)``
    sequence as one fused TPU op.
    """
    ranks = sorted_search(keys, queries, interpret=interpret)
    idx = jnp.clip(ranks - 1, 0, keys.shape[0] - 1)
    found = keys[idx] == queries
    return found, jnp.where(found, values[idx], 0)
