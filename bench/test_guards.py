"""Device guards: peaks by device kind, and no result off a TPU (CPU)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.peaks import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_known_device_kind_has_its_published_peaks():
    v5e = peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 8.19e11
    assert v5e["bf16_flops_per_s"] == 1.97e14


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        peaks(kind)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except ValueError:
        return True
    return False


def test_run_refuses_a_backend_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb_a.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_run_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb_a.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)


def test_device_metrics_are_never_taken_off_a_tpu():
    import jax
    bench = run.load_benchmark()
    cell = bench["workloads"][0]
    with pytest.raises(RuntimeError, match="TPU"):
        run.execute(bench, cell, 1, 1.0, True, jax.devices(), None)


def test_every_metric_has_its_reader_and_every_cell_its_files():
    bench = run.load_benchmark()
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]
    for c in bench["workloads"]:
        for path in (f"configs/{c['config']}.json",
                     f"traffic/{c['traffic']}.json"):
            assert os.path.exists(os.path.join(ROOT, "bench", path))
    # a reader that finds nothing to read returns nothing, never 0
    empty = {"counters": {"answered": 0, "batches": 0, "score_calls": 0,
                          "packed_spec_hits": 0, "packed_spec_misses": 0},
             "trace": None, "answered": 0, "peaks": None,
             "work_bytes": None}
    for m in bench["per_layer"]:
        assert run.read_metric(m["name"], empty) is None, m["name"]
