"""Crash-safety of the cross-PR benchmark trajectory files."""
import json
import os

import pytest

pytest.importorskip("benchmarks.common",
                    reason="benchmarks package needs repo root on sys.path")

from benchmarks import common


@pytest.fixture()
def bench_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "BENCH_DIR", str(tmp_path))
    return tmp_path


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def test_emit_trajectory_appends_and_migrates(bench_dir, capsys):
    path = bench_dir / "BENCH_x.json"
    common.emit_trajectory("BENCH_x", "first", [{"a": 1}])
    common.emit_trajectory("BENCH_x", "second", [{"a": 2}])
    history = _read(path)
    assert [e["entry"] for e in history] == [0, 1]
    assert history[1]["label"] == "second"
    # legacy bare-rows files migrate into entry 0
    legacy = bench_dir / "BENCH_y.json"
    legacy.write_text(json.dumps([{"old": True}]))
    common.emit_trajectory("BENCH_y", "new", [{"a": 3}])
    history = _read(legacy)
    assert history[0]["label"] == "pre-trajectory"
    assert history[1]["label"] == "new"


def test_emit_trajectory_survives_corrupted_history(bench_dir, capsys):
    """A file truncated by a crash mid-dump must not poison every future
    append: the bad file is backed up and a fresh history starts."""
    path = bench_dir / "BENCH_x.json"
    path.write_text('[{"entry": 0, "label": "tru')     # torn json.dump
    common.emit_trajectory("BENCH_x", "after-crash", [{"a": 1}])
    history = _read(path)
    assert len(history) == 1 and history[0]["entry"] == 0
    assert history[0]["label"] == "after-crash"
    backups = [f for f in os.listdir(bench_dir) if ".corrupt-" in f]
    assert len(backups) == 1
    assert "tru" in (bench_dir / backups[0]).read_text()
    assert "corrupted" in capsys.readouterr().out
    # valid JSON of the wrong shape is quarantined the same way
    wrong = bench_dir / "BENCH_z.json"
    for payload in ("null", '{"rows": []}'):
        wrong.write_text(payload)
        common.emit_trajectory("BENCH_z", "recovered", [{"a": 1}])
        assert _read(wrong)[-1]["label"] == "recovered"


def test_emit_trajectory_write_is_atomic(bench_dir, monkeypatch):
    """The rewrite goes through a temp file + os.replace — a crash inside
    json.dump leaves the previous history intact (and no temp litter)."""
    path = bench_dir / "BENCH_x.json"
    common.emit_trajectory("BENCH_x", "first", [{"a": 1}])
    before = path.read_text()

    def boom(*args, **kwargs):
        raise KeyboardInterrupt("crash mid-dump")
    monkeypatch.setattr(common.json, "dump", boom)
    with pytest.raises(KeyboardInterrupt):
        common.emit_trajectory("BENCH_x", "doomed", [{"a": 2}])
    assert path.read_text() == before
    assert [f for f in os.listdir(bench_dir) if f != "BENCH_x.json"] == []


@pytest.mark.parametrize("from_env", [True, False])
def test_enable_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache
    lives at one fixed, gitignored directory of the checkout."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = common.enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert path == common.COMPILE_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == path
            assert os.path.dirname(path) == common.ROOT
            with open(os.path.join(common.ROOT, ".gitignore")) as fh:
                assert os.path.basename(path) + "/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
