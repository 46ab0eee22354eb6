"""Jit'd wrapper for the bloom-probe kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.runtime import BLOCK_1D, resolve_interpret

#: deterministic odd multipliers (the paper draws them randomly per run)
DEFAULT_COEFFS = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
                           0x165667B1], np.uint32) | np.uint32(1)


def _pad1(x: jax.Array, mult: int, value) -> jax.Array:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("s", "num_hashes", "block_q",
                                             "block_w", "interpret"))
def bloom_probe(words: jax.Array, queries: jax.Array, s: int,
                num_hashes: int = 2, block_q: int = BLOCK_1D,
                block_w: int = BLOCK_1D,
                interpret: Optional[bool] = None) -> jax.Array:
    """Membership mask for ``queries`` against a 2^s-bit bloom filter."""
    from repro.kernels.bloom_probe.kernel import bloom_probe_kernel
    interpret = resolve_interpret(interpret)
    q = queries.shape[0]
    w = words.shape[0]
    block_w = min(block_w, w)
    coeffs = jnp.asarray(DEFAULT_COEFFS[:num_hashes])
    queries_p = _pad1(queries, block_q, queries[0] if q else 0)
    hits = bloom_probe_kernel(words, queries_p, coeffs, s=s,
                              block_q=block_q, block_w=block_w,
                              interpret=interpret)
    return (hits[:q] == 1).all(axis=1)
