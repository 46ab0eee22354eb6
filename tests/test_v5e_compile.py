"""Compile guards for a TPU v5e, run without the chip.

The fused scorers of the served path and the four access-primitive
Pallas kernels are compiled for a described (not attached) ``v5e:2x2``
topology at the sizes the service and the kernel benchmark use.  Nothing
runs: a passing compile shows the chip's compiler accepts the program —
layouts, Mosaic lowering, device memory — and says nothing about results
or speed, which ``chip_smoke.py`` checks on the chip.

The topology is described inside a module fixture, never at import, so
collecting this file does not load the TPU library.  The persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core import devicecost
from repro.core.hardware import hw1

#: one v5e chip's HBM
V5E_HBM_BYTES = 16 * 2**30
#: the largest fused chunk, in records (cells for a sweep)
R = devicecost._MAX_FUSED_RECORDS
#: the sweep width of the service's bulk sweeps
W = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("w_axis,with_knn", [
    (None, False),
    (None, True),
    (W, False),       # the knn sweep compile takes minutes: left out
], ids=["score", "score-knn", "sweep"])
def test_fused_scorer_compiles_for_v5e(one_chip, w_axis, with_knn):
    """The largest fused chunk: R records flat, or W x R/W sweep cells."""
    banks = {k: _spec(one_chip, v.shape, v.dtype)
             for k, v in devicecost.build_table(hw1()).banks.items()}
    if w_axis is None:
        fn, rows, n = devicecost._score_jit, (), R
        n_pad = R // devicecost.TILE          # every design one tile
    else:
        fn, rows = devicecost._sweep_jit, (w_axis,)
        n, n_pad = devicecost.sweep_chunk(w_axis), 4096   # 4096 designs
    compiled = fn.lower(
        banks, _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, rows + (n,), jnp.float32),
        _spec(one_chip, rows + (n,), jnp.float32),
        _spec(one_chip, (n // devicecost.TILE,), jnp.int32),
        n_pad, with_knn).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES


def _sorted_search(chip):
    from repro.kernels.sorted_search.ops import sorted_search
    return (lambda k, q: sorted_search(k, q, interpret=False),
            [_spec(chip, (1 << 16,), jnp.int32),
             _spec(chip, (1 << 12,), jnp.int32)])


def _scan_filter(chip):
    from repro.kernels.scan_filter.ops import scan_filter
    return (lambda k, q, lo, hi: scan_filter(k, q, lo, hi, interpret=False),
            [_spec(chip, (1 << 16,), jnp.int32)]
            + [_spec(chip, (1 << 12,), jnp.int32)] * 3)


def _hash_probe(chip):
    from repro.kernels.hash_probe.ops import hash_probe
    s, cap = 10, 16
    return (lambda tk, tv, q: hash_probe(tk, tv, q, s=s, interpret=False),
            [_spec(chip, (1 << s, cap), jnp.int32)] * 2
            + [_spec(chip, (1 << 12,), jnp.int32)])


def _bloom_probe(chip):
    from repro.kernels.bloom_probe.ops import bloom_probe
    s = 16
    return (lambda w, q: bloom_probe(w, q, s=s, num_hashes=3,
                                     interpret=False),
            [_spec(chip, ((1 << s) // 32,), jnp.uint32),
             _spec(chip, (1 << 12,), jnp.int32)])


@pytest.mark.parametrize("case", [_sorted_search, _scan_filter, _hash_probe,
                                  _bloom_probe],
                         ids=lambda case: case.__name__.lstrip("_"))
def test_kernel_compiles_to_mosaic_for_v5e(one_chip, case):
    """At the kernel benchmark's widths: 65,536 keys, 4,096 queries, a
    2^10 x 16 hash table, a 2^16-bit bloom filter."""
    fn, args = case(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_refine_loop_compiles_for_v5e(one_chip):
    """The search's compiled refinement loop, on the template with the
    most surrogate terms (a bloom-filtered hash over a B+ level)."""
    from repro.core import relax
    template = relax.RelaxTemplate(("Hash", "B+", "ODP"), bloom=True)
    banks = {k: _spec(one_chip, v.shape, v.dtype)
             for k, v in devicecost.build_table(hw1()).banks.items()}
    scalar = _spec(one_chip, (), jnp.float32)
    compiled = relax._refine_fn(template, 2, 0.35).lower(
        _spec(one_chip, (template.n_knobs,), jnp.float32), banks,
        scalar, scalar, scalar).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
