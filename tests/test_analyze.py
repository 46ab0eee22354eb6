"""repro-lint framework tests: every checker must demonstrably fire on
its seeded fixture, stay silent on the clean twin, honor documented
suppressions, and report stably over the CLI."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.lint

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "lint")


def _run(checker_name, *relpaths):
    from tools.analyze import run_paths
    from tools.analyze.checkers import BY_NAME
    paths = [os.path.join(FIXTURES, *rp.split("/")) for rp in relpaths]
    return run_paths(paths, checkers=[BY_NAME[checker_name]],
                     baseline=None)


def _rules(findings):
    return {f.rule for f in findings}


# -- cache-keys -------------------------------------------------------------

def test_cache_keys_fires_on_seeded_fixture():
    findings = _run("cache-keys", "cache_keys/bad.py")
    assert _rules(findings) == {"hardware-in-key", "workload-in-key"}
    hw = [f for f in findings if f.rule == "hardware-in-key"]
    assert len(hw) == 2, "both the .get and the .put key must be flagged"
    assert all(f.path.endswith("cache_keys/bad.py") and f.line > 0
               for f in findings)


def test_cache_keys_silent_on_clean_twin():
    assert _run("cache-keys", "cache_keys/clean.py") == []


# -- locks ------------------------------------------------------------------

def test_locks_fires_on_seeded_fixture():
    findings = _run("locks", "locks/bad.py")
    assert _rules(findings) == {"unlocked"}
    msgs = [f.message for f in findings]
    assert any("_data" in m for m in msgs), "unlocked field read"
    assert any("_hits" in m for m in msgs), "unlocked field write"
    assert any("REGISTRY" in m for m in msgs), "unlocked guarded global"


def test_locks_silent_on_clean_twin_and_honors_suppression():
    # clean.py contains an unlocked read carrying a documented
    # '# lint: unlocked(...)' — the run must come back empty anyway
    assert _run("locks", "locks/clean.py") == []


@pytest.mark.parametrize("fixture,flagged", [("spans_bad.py", 2),
                                              ("spans_clean.py", 0)])
def test_locks_guards_the_span_table(fixture, flagged):
    findings = _run("locks", f"locks/{fixture}")
    assert len(findings) == flagged
    assert all("_TOTALS" in f.message and "_LOCK" in f.message
               for f in findings)


# -- futures ----------------------------------------------------------------

def test_futures_fires_on_seeded_fixture():
    findings = _run("futures", "futures/bad.py")
    assert _rules(findings) == {"dropped-future", "unawaited-future",
                                "untimed-wait"}
    untimed = [f for f in findings if f.rule == "untimed-wait"]
    assert len(untimed) == 2, "helper-returned and chained waits"


def test_futures_silent_on_clean_twin_and_honors_suppression():
    assert _run("futures", "futures/clean.py") == []


# -- jit-safety -------------------------------------------------------------

def test_jit_safety_fires_on_seeded_fixture():
    findings = _run("jit-safety", "jit_safety/bad.py")
    assert _rules(findings) == {"traced-branch", "traced-concretize",
                                "array-closure", "unhashable-static"}
    concretize = [f for f in findings if f.rule == "traced-concretize"]
    assert any("_pad" in f.message for f in concretize), \
        "the helper reached through its call site must be flagged"


def test_jit_safety_silent_on_clean_twin():
    assert _run("jit-safety", "jit_safety/clean.py") == []


# -- docs-refs --------------------------------------------------------------

def test_docs_refs_fires_and_stays_silent():
    from tools.analyze.checkers import docs_refs
    bad = os.path.join(FIXTURES, "docs_refs", "bad.md")
    clean = os.path.join(FIXTURES, "docs_refs", "clean.md")
    errors = docs_refs.check_doc_texts([bad])
    assert len(errors) == 2
    assert any("not_a_real_function" in e for e in errors)
    assert any("nonexistent.py" in e for e in errors)
    assert docs_refs.check_doc_texts([clean]) == []


# -- framework --------------------------------------------------------------

def test_bare_suppression_is_itself_reported():
    findings = _run("locks", "framework/bare.py")
    assert [f.rule for f in findings] == ["bare-suppression"]
    assert findings[0].checker == "framework"


def test_json_report_is_stable():
    from tools.analyze import render_json
    findings = _run("futures", "futures/bad.py")
    report = json.loads(render_json(findings))
    assert report["version"] == 1
    assert report["count"] == len(findings) > 0
    for entry in report["findings"]:
        assert set(entry) == {"path", "line", "checker", "rule", "message"}


def test_baseline_subtracts_known_findings(tmp_path):
    from tools.analyze import run_paths
    from tools.analyze.checkers import BY_NAME
    bad = os.path.join(FIXTURES, "futures", "bad.py")
    findings = run_paths([bad], checkers=[BY_NAME["futures"]],
                         baseline=None)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps([f.to_dict() for f in findings]))
    assert run_paths([bad], checkers=[BY_NAME["futures"]],
                     baseline=str(baseline)) == []


# -- CLI --------------------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.analyze", *args],
        cwd=ROOT, capture_output=True, text=True, timeout=120)


def test_cli_exits_nonzero_with_json_on_findings():
    proc = _cli("tests/fixtures/lint/futures/bad.py",
                "--checker", "futures", "--baseline", "none",
                "--format", "json")
    assert proc.returncode == 1, proc.stderr
    report = json.loads(proc.stdout)
    assert report["count"] > 0


def test_cli_exits_zero_on_clean_input():
    proc = _cli("tests/fixtures/lint/futures/clean.py",
                "--checker", "futures", "--baseline", "none")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 findings" in proc.stdout


def test_cli_rejects_unknown_checker():
    proc = _cli("--checker", "no-such-checker")
    assert proc.returncode == 2
