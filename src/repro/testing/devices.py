"""Forced host-device-count plumbing shared by benchmarks and tests.

JAX fixes its device list when the backend initializes, so a running
process cannot change its device count — multi-device behavior on CPU CI
is exercised by *launching a process* with
``--xla_force_host_platform_device_count=N`` in ``XLA_FLAGS`` (the
HomebrewNLP-Jax ``run.sh`` trick, see SNIPPETS.md).  Three consumers
build on the primitives here:

* ``benchmarks.common.apply_process_tuning`` re-execs the running
  benchmark with the flag appended (one simulated device per core);
* the ``devices(n)`` pytest marker (``tests/conftest.py``) re-invokes a
  test in a subprocess under exactly ``n`` forced devices, so one CI
  invocation covers 2/8/48-way sharding;
* ``benchmarks/device_scaling.py`` runs measurement children at 1 and 4
  devices and compares cells/sec (on the CPU only: on an accelerator the
  parent holds the chips, so it measures in-process).
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from typing import Dict, Optional, Sequence

DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
SRC_ROOT = os.path.join(REPO_ROOT, "src")


def forced_device_count(env: Optional[Dict[str, str]] = None
                        ) -> Optional[int]:
    """The forced host device count in ``env`` (default: this process's
    environment), or ``None`` when the flag is absent."""
    flags = (os.environ if env is None else env).get("XLA_FLAGS", "")
    match = re.search(re.escape(DEVICE_COUNT_FLAG) + r"=(\d+)", flags)
    return int(match.group(1)) if match else None


def forced_device_env(n: int, base: Optional[Dict[str, str]] = None
                      ) -> Dict[str, str]:
    """A copy of ``base`` (default: ``os.environ``) whose ``XLA_FLAGS``
    force exactly ``n`` host devices, replacing any existing count."""
    env = dict(os.environ if base is None else base)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(DEVICE_COUNT_FLAG)]
    flags.append(f"{DEVICE_COUNT_FLAG}={int(n)}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def run_under_devices(n: int, argv: Sequence[str], *,
                      timeout: float = 600.0,
                      env: Optional[Dict[str, str]] = None
                      ) -> subprocess.CompletedProcess:
    """Run ``python <argv...>`` from the repo root under ``n`` forced
    host devices, with ``src`` on ``PYTHONPATH`` and output captured.
    Returns the ``CompletedProcess`` unchecked — callers decide whether
    a nonzero exit is a failure or a measurement."""
    child_env = forced_device_env(n, env)
    extra = child_env.get("PYTHONPATH", "")
    child_env["PYTHONPATH"] = \
        SRC_ROOT + (os.pathsep + extra if extra else "")
    return subprocess.run([sys.executable] + list(argv), cwd=REPO_ROOT,
                          env=child_env, capture_output=True, text=True,
                          timeout=timeout)


def run_pytest_under_devices(n: int, nodeid: str, *,
                             timeout: float = 900.0
                             ) -> subprocess.CompletedProcess:
    """Re-invoke one pytest node under ``n`` forced host devices (the
    ``devices(n)`` marker's subprocess hop)."""
    return run_under_devices(
        n, ["-m", "pytest", "-x", "-q", "-p", "no:cacheprovider", nodeid],
        timeout=timeout)
