"""Jit'd wrapper for the hash-probe kernel: padding + Get helper."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.hash_probe.kernel import NOT_FOUND, hash_probe_kernel
from repro.kernels.hash_probe.ref import EMPTY_KEY
from repro.kernels.runtime import BLOCK_1D, resolve_interpret

#: default multiply-shift coefficient (odd, from a fixed PRNG draw — the
#: paper draws a randomly per run; determinism helps tests)
DEFAULT_A = 0x9E3779B1  # Knuth's 32-bit golden ratio, odd


def _pad1(x: jax.Array, mult: int, value) -> jax.Array:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("a", "s", "block_q", "block_k",
                                             "interpret"))
def hash_probe(table_keys: jax.Array, table_values: jax.Array,
               queries: jax.Array, s: int, a: int = DEFAULT_A,
               block_q: int = BLOCK_1D, block_k: int = BLOCK_1D,
               interpret: Optional[bool] = None):
    """(found mask, values) for point probes against a bucketized table."""
    interpret = resolve_interpret(interpret)
    q = queries.shape[0]
    cap = table_keys.shape[1]
    # flat bucket-major slots; padding slots lie past every bucket's range
    keys_p = _pad1(table_keys.reshape(-1), block_k, EMPTY_KEY)
    vals_p = _pad1(table_values.reshape(-1), block_k, 0)
    queries_p = _pad1(queries, block_q, jnp.asarray(NOT_FOUND - 1,
                                                    queries.dtype))
    pos, val = hash_probe_kernel(keys_p, vals_p, queries_p, cap=cap,
                                 a=a, s=s, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    found = pos[:q] != NOT_FOUND
    return found, jnp.where(found, val[:q], 0)
