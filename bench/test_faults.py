"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (``bench.run.execute``) at a size a CPU test can hold, once sound
and once with one fault planted in the program: a hardware swap that
leaves the profile unchanged, half a sweep left out with the mean of the
rest in its place, an answer altered where the fused scorer produces it,
and, on four devices, the exchange of the shard pool's parts left out.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL = {
    "ycsb_c.whatif": {"rate_per_s": 25.0, "warm_designs": 16,
                      "warm_records_per_design": 32, "warm_seconds": 0.3,
                      "check_questions": 64},
    "ycsb_a.sweep": {"designs": 64, "prebuilt_per_client": 1,
                     "warm_designs": 16, "check_cells_per_sweep": 32},
    # the sweep mix over both Zipf constants, on four devices
    "ycsb_a.sweep_4chip": {"designs": 512, "prebuilt_per_client": 1,
                           "warm_designs": 16, "check_cells_per_sweep": 32,
                           "zipf_alphas": [0.5, 0.99]},
    "ycsb_a.search": {"searches": 1, "budget": 48, "population": 8,
                      "generations": 12},
}


def small_run(name: str, seconds: float = 1.0) -> dict:
    import jax
    from bench import traffic as tr
    bench = run.load_benchmark()
    config, traffic = name.split(".")
    cell = {"name": name, "config": config, "traffic": traffic,
            "chips": 4 if traffic.endswith("4chip") else 1}
    params = dict(tr.load_traffic(traffic.replace("_4chip", "")),
                  **SMALL[name])
    devices = jax.devices()
    return run.execute(bench, dict(cell, chips=min(cell["chips"],
                                                   len(devices))),
                       2**31 + 5, seconds, False, devices, None, params)


def _altered(fn):
    """The fused scorer's answers, each off by one part in a thousand."""
    def wrapped(*args, **kwargs):
        return np.asarray(fn(*args, **kwargs)) * (1.0 + 1e-3)
    return wrapped


def _half_left_out(fn):
    """A sweep whose second half of designs is left out, each of its
    cells given the mean of the scored half."""
    def wrapped(*args, **kwargs):
        out = np.array(fn(*args, **kwargs), dtype=np.float64)
        half = out.shape[1] // 2
        if half:
            out[:, half:] = out[:, :half].mean(axis=1, keepdims=True)
        return out
    return wrapped


def test_whatif_sound_run_is_correct():
    assert small_run("ycsb_c.whatif")["correct"] is True


def test_whatif_hardware_swap_left_unchanged_is_not_correct(monkeypatch):
    from repro.serving import DesignCalculatorService
    orig = DesignCalculatorService.submit_hardware

    def unchanged(self, spec, workload, hw, new_hw, *args, **kwargs):
        return orig(self, spec, workload, hw, hw, *args, **kwargs)
    monkeypatch.setattr(DesignCalculatorService, "submit_hardware",
                        unchanged)
    assert small_run("ycsb_c.whatif")["correct"] is False


def test_whatif_altered_answer_is_not_correct(monkeypatch):
    from repro.core import devicecost
    monkeypatch.setattr(devicecost, "score_frontier",
                        _altered(devicecost.score_frontier))
    assert small_run("ycsb_c.whatif")["correct"] is False


def test_sweep_sound_run_is_correct():
    assert small_run("ycsb_a.sweep")["correct"] is True


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_sweep_fault_is_not_correct(monkeypatch, fault):
    from repro.core import devicecost
    monkeypatch.setattr(devicecost, "score_sweep",
                        fault(devicecost.score_sweep))
    assert small_run("ycsb_a.sweep")["correct"] is False


def test_search_sound_and_altered(monkeypatch):
    assert small_run("ycsb_a.search", seconds=30.0)["correct"] is True
    from repro.core import devicecost
    monkeypatch.setattr(devicecost, "score_sweep",
                        _altered(devicecost.score_sweep))
    assert small_run("ycsb_a.search", seconds=30.0)["correct"] is False


FOUR = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import test_faults as t
from repro.serving.shards import ScoringShardPool
sound = t.small_run("ycsb_a.sweep_4chip")["correct"]
orig = ScoringShardPool._score_parts
def no_exchange(self, *args, **kwargs):
    parts = orig(self, *args, **kwargs)
    return [parts[0]] * len(parts) if parts and len(parts) > 1 else parts
ScoringShardPool._score_parts = no_exchange
broken = t.small_run("ycsb_a.sweep_4chip")["correct"]
print(json.dumps({{"sound": sound, "broken": broken}}))
"""


def test_four_chip_sweep_without_the_exchange_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(root=ROOT, src=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == \
        {"sound": True, "broken": False}
