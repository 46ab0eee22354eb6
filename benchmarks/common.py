"""Shared benchmark plumbing: profile cache, tables, JSON artifacts."""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_DIR = os.path.join(ROOT, "experiments", "bench")
PROFILE_PATH = os.path.join(ROOT, "experiments", "profiles",
                            "container.json")
#: JAX's persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed path, since the path is part of every entry's key
COMPILE_CACHE_DIR = os.path.join(ROOT, ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is
    (JAX reads it itself); otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`.  Returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def container_profile(refresh: bool = False):
    """Train (or load the cached) Level-2 model profile for this machine."""
    from repro.core.hardware import HardwareProfile
    from repro.core.training import train_profile
    if os.path.exists(PROFILE_PATH) and not refresh:
        return HardwareProfile.load(PROFILE_PATH)
    profile = train_profile("HW-container", reps=48, max_size=1 << 20)
    profile.save(PROFILE_PATH)
    return profile


def _atomic_dump(obj, path: str) -> None:
    """Serialize to a sibling temp file, then ``os.replace`` over ``path``.

    A crash mid-``json.dump`` must never truncate an existing artifact —
    the trajectory files accumulate cross-PR history that a plain
    ``open(path, "w")`` would destroy on the next interrupted run."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as fh:
            json.dump(obj, fh, indent=1, default=str)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def emit(name: str, rows: Sequence[Dict], keys: Optional[List[str]] = None
         ) -> None:
    """Print an aligned table and persist rows under experiments/bench/."""
    os.makedirs(BENCH_DIR, exist_ok=True)
    _atomic_dump(list(rows), os.path.join(BENCH_DIR, f"{name}.json"))
    _print_table(name, rows, keys)


def emit_trajectory(name: str, label: str, rows: Sequence[Dict],
                    keys: Optional[List[str]] = None) -> None:
    """*Append* one labelled entry to experiments/bench/<name>.json.

    Unlike :func:`emit` (which overwrites), the trajectory file is a list
    of ``{"entry", "label", "date", "rows"}`` records that accumulates
    across PRs, so perf history survives re-runs.  A legacy bare-rows file
    (the pre-trajectory format) is migrated into entry 0.

    The rewrite is atomic (temp file + ``os.replace``); a corrupted
    history file — e.g. truncated by a crash on a pre-atomic version — is
    backed up beside itself and a fresh history is started instead of
    raising on every future append.
    """
    os.makedirs(BENCH_DIR, exist_ok=True)
    path = os.path.join(BENCH_DIR, f"{name}.json")
    history: List[Dict] = []
    if os.path.exists(path):
        try:
            with open(path) as fh:
                existing = json.load(fh)
            if not isinstance(existing, list):
                raise ValueError(f"expected a list, found "
                                 f"{type(existing).__name__}")
        except ValueError:          # json.JSONDecodeError subclasses this
            backup = f"{path}.corrupt-{time.strftime('%Y%m%d-%H%M%S')}"
            os.replace(path, backup)
            print(f"warning: {path} was corrupted; backed it up to "
                  f"{backup} and starting a fresh history")
            existing = []
        if existing and isinstance(existing[0], dict) and \
                "rows" not in existing[0]:
            history = [{"entry": 0, "label": "pre-trajectory",
                        "rows": existing}]
        else:
            history = existing
    history.append({"entry": len(history), "label": label,
                    "date": time.strftime("%Y-%m-%d %H:%M:%S"),
                    "rows": list(rows)})
    _atomic_dump(history, path)
    _print_table(f"{name} [entry {len(history) - 1}: {label}]", rows, keys)


def _print_table(name: str, rows: Sequence[Dict],
                 keys: Optional[List[str]] = None) -> None:
    if not rows:
        print(f"[{name}] (no rows)")
        return
    keys = keys or list(rows[0].keys())
    widths = {k: max(len(k), *(len(_fmt(r.get(k))) for r in rows))
              for k in keys}
    print(f"== {name} ==")
    print("  ".join(k.ljust(widths[k]) for k in keys))
    for row in rows:
        print("  ".join(_fmt(row.get(k)).ljust(widths[k]) for k in keys))
    print()


def _fmt(value) -> str:
    if isinstance(value, float):
        if value == 0:
            return "0"
        if abs(value) < 1e-3 or abs(value) >= 1e5:
            return f"{value:.3e}"
        return f"{value:.4g}"
    return str(value)


def timer():
    t0 = time.perf_counter()
    return lambda: time.perf_counter() - t0


#: guard so the tuning re-exec happens exactly once
_TUNED_ENV = "_REPRO_BENCH_TUNED"
_TCMALLOC = "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4"


def apply_process_tuning(n_devices: int = None) -> None:
    """Re-exec the current command under the standard serving-process
    tuning: tcmalloc preloaded (thread-friendly allocator for the
    multi-client load benchmarks) and ``XLA_FLAGS`` forcing one host
    device per core (``n_devices`` overrides; an explicit flag already
    in the environment always wins).  Both only take effect at process
    start — tcmalloc must be preloaded and XLA reads its flags when the
    backend initializes — hence the exec.  The device-count plumbing is
    shared with the pytest ``devices(n)`` marker via
    :mod:`repro.testing.devices`.  No-ops inside the tuned child, when
    already configured, or on platforms without tcmalloc."""
    from repro.testing.devices import forced_device_count, forced_device_env
    if os.environ.get(_TUNED_ENV) == "1":
        return
    env = dict(os.environ)
    env[_TUNED_ENV] = "1"
    changed = False
    if os.path.exists(_TCMALLOC) and "tcmalloc" not in env.get(
            "LD_PRELOAD", ""):
        env["LD_PRELOAD"] = (env.get("LD_PRELOAD", "") + " " +
                             _TCMALLOC).strip()
        changed = True
    if forced_device_count(env) is None:
        n = n_devices if n_devices is not None \
            else min(os.cpu_count() or 1, 48)
        env = forced_device_env(n, env)
        changed = True
    if not changed:
        return
    os.execve(sys.executable, [sys.executable, "-m",
                               main_module_name()] + sys.argv[1:], env)


def main_module_name() -> str:
    """The ``-m``-style name of the currently running benchmark module."""
    main = sys.modules.get("__main__")
    spec = getattr(main, "__spec__", None)
    if spec is not None and spec.name:
        return spec.name
    return "benchmarks.run"
