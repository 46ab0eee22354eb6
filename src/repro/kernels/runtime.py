"""Backend selection for the Pallas kernels.

The kernel wrappers historically hardcoded ``interpret=True`` (the Pallas
interpreter runs anywhere, so CPU CI stayed deterministic) — which also
meant a real TPU silently ran the interpreter.  ``default_interpret``
auto-detects: compile to Mosaic only when a TPU backend is attached,
interpret otherwise.  Every wrapper takes ``interpret: Optional[bool]``
with ``None`` meaning "resolve via this module"; passing an explicit bool
still forces either mode (tests pin ``interpret=True`` where they must be
deterministic on CPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

#: default width of the kernels' 1-D blocks: XLA tiles a 1-D 32-bit array
#: on a TPU as T(1024), and Mosaic refuses a block that does not match
#: ("XLA layout ({0:T(1024)}) does not match Mosaic layout")
BLOCK_1D = 1024


@functools.lru_cache(maxsize=None)
def default_interpret() -> bool:
    """True (interpret) unless a real TPU backend is attached.  A backend
    that fails to initialize raises: it must never quietly fall back to
    the interpreter on the machine that was meant to run the kernels."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Map the wrappers' ``interpret=None`` default to the detected mode."""
    return default_interpret() if interpret is None else bool(interpret)
