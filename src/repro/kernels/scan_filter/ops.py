"""Jit'd wrapper for the scan kernel: padding + Get/RangeCount helpers."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.runtime import BLOCK_1D, resolve_interpret
from repro.kernels.scan_filter.kernel import NOT_FOUND, scan_filter_kernel


def _pad1(x: jax.Array, mult: int, value) -> jax.Array:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def scan_filter(keys: jax.Array, queries: jax.Array,
                lo: jax.Array, hi: jax.Array,
                block_q: int = BLOCK_1D, block_k: int = BLOCK_1D,
                interpret: Optional[bool] = None):
    """(first-match pos | NOT_FOUND, range count) over an unsorted node."""
    interpret = resolve_interpret(interpret)
    n, q = keys.shape[0], queries.shape[0]
    if jnp.issubdtype(keys.dtype, jnp.floating):
        big = jnp.inf
    else:
        big = jnp.iinfo(keys.dtype).max
    keys_p = _pad1(keys, block_k, big)   # never equal, never in range
    queries_p = _pad1(queries, block_q, big)
    lo_p = _pad1(lo, block_q, big)
    hi_p = _pad1(hi, block_q, big)
    pos, cnt = scan_filter_kernel(keys_p, queries_p, lo_p, hi_p,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
    # dtype-max padding keys match dtype-max queries: mask out-of-range hits
    pos = jnp.where(pos >= n, NOT_FOUND, pos)
    return pos[:q], cnt[:q]


def scan_get(keys: jax.Array, values: jax.Array, queries: jax.Array,
             interpret: Optional[bool] = None):
    """Point Get over an unsorted node (the paper's UDP terminal)."""
    pos, _ = scan_filter(keys, queries, queries, queries,
                         interpret=interpret)
    found = pos != NOT_FOUND
    idx = jnp.where(found, pos, 0)
    return found, jnp.where(found, values[idx], 0)
