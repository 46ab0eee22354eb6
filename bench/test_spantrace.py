"""The program's spans in a profiler trace (bench/spantrace.py), and the
readers of the span and queue counters (CPU)."""
import json
import os

import pytest

from bench import spantrace, trace


def _events(*spans):
    return [[name, float(a), float(b - a)] for name, a, b in spans]


@pytest.fixture()
def spanned():
    """One device; the host packs, then dispatches and fetches, while a
    client builds its next sweep on another thread."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": _events(("fusion", 300, 400), ("fusion", 700, 750)),
            "modules": _events(("jit__sweep_kernel(3)", 300, 400)),
        }},
        "host": {
            "/host:CPU/0/python": _events(
                (trace.WINDOW_SPAN, 0, 1000), ("repro.pack", 20, 280),
                ("repro.fused.dispatch", 280, 310),
                ("PjitFunction(_sweep_kernel)", 285, 305),
                ("repro.fused.fetch", 310, 420),
                ("np.asarray(jax.Array)", 312, 420),
                ("repro.pack", 450, 690), ("repro.pack", 1100, 1200)),
            "/host:CPU/1/python": _events(
                ("repro.submit", 0, 10),
                (spantrace.CLIENT_BUILD_SPAN, 600, 900),
                ("pack_sweep", 460, 680)),
        },
    }


def test_span_seconds_sum_program_spans_that_start_in_the_window(spanned):
    got = spantrace.span_seconds(spanned, (0.0, 1000.0))
    assert set(got) == {"repro.pack", "repro.fused.dispatch",
                        "repro.fused.fetch", "repro.submit"}
    # the pack at 1100 starts after the window closes
    assert got["repro.pack"] == pytest.approx((260 + 240) * 1e-9)
    assert got["repro.fused.fetch"] == pytest.approx(110e-9)


def test_idle_by_span_and_the_share_program_spans_cover(spanned):
    got = spantrace.idle_by_span(spanned, (0.0, 1000.0), chips=1)
    # idle: [0, 300], [400, 700], [750, 1000] = 850 ns
    assert got["idle_s"] == pytest.approx(850e-9)
    s = got["seconds"]
    assert s["repro.pack"] == pytest.approx((260 + 240) * 1e-9)
    assert s["repro.fused.dispatch"] == pytest.approx(20e-9)
    assert s["repro.fused.fetch"] == pytest.approx(20e-9)
    assert s["repro.submit"] == pytest.approx(10e-9)
    assert s[spantrace.CLIENT_BUILD_SPAN] == pytest.approx(
        (100 + 150) * 1e-9)
    # program spans: [0, 10], [20, 300] idle part, [400, 420],
    # [450, 690]; the client's build is the benchmark's, not the program's
    covered = 10 + 280 + 20 + 240
    assert got["covered_pct"] == pytest.approx(100 * covered / 850)


def test_idle_by_span_counts_overlapping_threads_once(spanned):
    spanned["host"]["/host:CPU/2/python"] = _events(("repro.pack", 0, 300))
    got = spantrace.idle_by_span(spanned, (0.0, 1000.0), chips=1)
    assert got["seconds"]["repro.pack"] == pytest.approx(
        (300 + 240) * 1e-9)


def test_an_idle_chip_with_no_spans_covers_nothing():
    bare = {"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
            "host": {"/host:CPU/0/python": _events(
                (trace.WINDOW_SPAN, 0, 100), ("np.asarray", 10, 90))}}
    got = spantrace.idle_by_span(bare, (0.0, 100.0), chips=1)
    assert got == {"seconds": {}, "idle_s": pytest.approx(100e-9),
                   "covered_pct": 0.0}
    assert spantrace.span_seconds(bare, (0.0, 100.0)) == {}


def test_a_gap_under_a_pack_span_is_labelled_by_it(spanned):
    gaps = trace.idle_gaps(spanned, (0.0, 1000.0))
    # [0, 300]: the pack covers 260 of it, the jit dispatch 15;
    # [400, 700]: the pack 240, the library's pack_sweep inside it 220,
    # the client's build 100; [750, 1000]: only the client's build
    assert [g[0] for g in gaps] == ["repro.pack", "repro.pack",
                                    spantrace.CLIENT_BUILD_SPAN]


def test_the_spans_line_sets_counters_beside_the_trace(spanned):
    before = {"span_s.repro.pack": 1.0, "queue_wait_s": 3.0}
    after = {"span_s.repro.pack": 1.0 + 500e-9, "queue_wait_s": 4.0}
    line = spantrace.span_line(spanned, 1, before, after)
    assert line.startswith("spans: covered_pct=")
    counted = json.loads(line.split("span_counter_s=", 1)[1])
    assert counted == {"repro.pack": pytest.approx(500e-9)}
    # a program without spans: nothing counted, nothing raised
    assert line.split("span_counter_s=")[0] in spantrace.span_line(
        spanned, 1, {}, {})


NEW_READERS = ["queue_wait_ms.sweep", "pack_ms_per_sweep.sweep",
               "dispatch_ms_per_sweep.sweep", "fetch_wait_ms_per_sweep.sweep",
               "score_wait_s_per_search.search", "refine_s_per_search.search",
               "verify_s_per_search.search"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_span_readers_are_silent_without_their_counters(name):
    from bench import run
    parent = {"packed_spec_hits": 3, "packed_spec_misses": 5,
              "score_calls": 7}
    assert run.read_metric(name, {"counters": parent, "trace": None,
                                  "answered": 4}) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_span_readers_divide_by_the_work_answered(name):
    from bench import run
    counters = {"queue_wait_s": 2.0, "dequeued": 4}
    for span in ("repro.pack", "repro.fused.dispatch", "repro.fused.fetch",
                 "repro.search.score", "repro.search.refine",
                 "repro.search.verify"):
        counters[f"span_s.{span}"] = 2.0
    got = run.read_metric(name, {"counters": counters, "trace": None,
                                 "answered": 4})
    assert got == pytest.approx(0.5 if name.endswith(".search") else 500.0)
    if not name.startswith("queue_wait"):
        assert run.read_metric(name, {"counters": counters, "trace": None,
                                      "answered": 0}) is None


def test_a_recorded_v5e_sweep_window_reduces_by_span():
    """Three seconds of a traced ``ycsb_a.sweep`` window on one v5e chip
    (the window span, the program's and the client's spans, the chip's
    operations and modules), as ``trace.load`` returns them."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "sweep_v5e.json")
    assert os.path.getsize(path) < 200_000
    with open(path) as fh:
        recorded = json.load(fh)
    window = trace.window_of(recorded)
    summary = trace.require(trace.summarize(recorded, chips=1), chips=1)
    got = spantrace.idle_by_span(recorded, window, chips=1)
    assert got["seconds"] and got["idle_s"] > 0
    assert got["idle_s"] == pytest.approx(
        summary["window_s"] - summary["busy_s"])
    assert 0.0 < got["covered_pct"] <= 100.0
    assert max(got["seconds"], key=got["seconds"].get).startswith("repro.")
    assert spantrace.span_seconds(recorded, window)
