"""Host spans and timing counters (repro.core.spans) on the served path:
the process-wide table, the profiler events with their request ids, the
search's leaf spans, and the service's queue-wait counters."""
import glob
import os
import sys
import threading
import time

import jax

from repro.core import elements as el, spans
from repro.core.synthesis import Workload
from repro.serving import DesignCalculatorService

W = Workload(n_entries=200_000, n_queries=100)
MIX = {"get": 80.0, "update": 20.0}
SPECS = [el.spec_btree(), el.spec_hash_table(), el.spec_skip_list(),
         el.spec_trie()]
POINTS = [Workload(n_entries=n, n_queries=100) for n in (1 << 14, 1 << 18)]


def _delta(before, name):
    after = spans.totals()[name]
    return after[0] - before[name][0], after[1] - before[name][1]


def test_totals_list_every_name_before_first_use():
    assert set(spans.NAMES) <= set(spans.totals())
    assert all(name.startswith("repro.") for name in spans.NAMES)


def test_totals_add_up_across_threads():
    """More threads than cores, switching every microsecond: a lost
    read-modify-write of the table would lose counts."""
    name, per_thread, nap = "repro.test.threads", 200, 1e-5
    threads = (os.cpu_count() or 1) + 2
    before = spans.totals()
    before.setdefault(name, (0.0, 0))
    slept = [0.0] * threads

    def work(t):
        for _ in range(per_thread):
            t0 = time.perf_counter()
            with spans.span(name, rid=t):
                time.sleep(nap)
            slept[t] += time.perf_counter() - t0

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=work, args=(t,))
                   for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    seconds, count = _delta(before, name)
    assert count == threads * per_thread
    # every span's own time is inside its caller's clock reads
    assert threads * per_thread * nap <= seconds <= sum(slept)


def _events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    data = ProfileData.from_file(path[-1])
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_traced_sweeps_carry_their_request_ids_from_submit_to_finalize(
        hw_analytical, tmp_path):
    # a window long enough that both sweeps coalesce and are spliced
    with DesignCalculatorService([hw_analytical], window_s=0.2) as svc:
        svc.workload_sweep(SPECS, POINTS, hw_analytical)   # compile
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            futures = [svc.submit_sweep(SPECS[:3], POINTS, hw_analytical),
                       svc.submit_sweep(SPECS[1:], POINTS, hw_analytical)]
            answers = [f.result(timeout=120) for f in futures]
        finally:
            jax.profiler.stop_trace()
    assert [a.totals.shape for a in answers] == [(len(POINTS), 3)] * 2
    events = _events(str(tmp_path))
    for name in (spans.SUBMIT, spans.WINDOW, spans.PACK, spans.SPLICE,
                 spans.DISPATCH, spans.FETCH, spans.FINALIZE):
        assert name in events, (name, sorted(events))
    submits = events[spans.SUBMIT]
    rids = [e["rid"] for e in submits]
    assert len(set(rids)) == 2
    assert all(e["lane"] == "bulk" and "parent" not in e for e in submits)
    assert [e["rid"] for e in events[spans.WINDOW]] == rids[:1]
    assert sorted(e["rid"] for e in events[spans.PACK]) == sorted(rids)
    assert sorted(e["rid"] for e in events[spans.FINALIZE]) == sorted(rids)
    (splice,) = events[spans.SPLICE]
    assert splice["rid"] == rids[0] and splice["n"] == 2
    assert {(e["designs"], e["pinned"]) for e in events[spans.PACK]} == {
        (3, 0)}
    assert all(e["records"] > 0 for e in events[spans.DISPATCH])
    assert len(events[spans.FETCH]) == len(events[spans.DISPATCH])


def test_a_search_is_covered_by_its_leaf_spans(hw_analytical):
    names = (spans.SEARCH_DECODE, spans.SEARCH_SCORE, spans.SEARCH_VERIFY,
             spans.SEARCH_REFINE, spans.SEARCH_BREED)
    with DesignCalculatorService([hw_analytical]) as svc:
        kwargs = dict(budget_designs=48, population=8, generations=20,
                      seed=4, refine_top=2, refine_steps=2)
        svc.submit_search(W, hw_analytical, MIX,
                          **kwargs).result(timeout=120)   # compile
        before = spans.totals()
        answer = svc.submit_search(W, hw_analytical, MIX,
                                   **kwargs).result(timeout=120)
    covered = {name: _delta(before, name) for name in names}
    assert covered[spans.SEARCH_SCORE][1] >= 1
    assert covered[spans.SEARCH_VERIFY][1] >= 1
    assert covered[spans.SEARCH_REFINE][1] >= 1
    share = sum(s for s, _ in covered.values()) / answer["elapsed_s"]
    assert 0.95 <= share <= 1.0, covered


def test_inner_sweeps_name_their_search_as_parent(hw_analytical, tmp_path):
    with DesignCalculatorService([hw_analytical]) as svc:
        kwargs = dict(budget_designs=24, population=8, generations=3,
                      seed=1, refine_top=1, refine_steps=1)
        svc.submit_search(W, hw_analytical, MIX,
                          **kwargs).result(timeout=120)   # compile
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            svc.submit_search(W, hw_analytical, MIX,
                              **kwargs).result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    events = _events(str(tmp_path))
    (search_rid,) = {e["rid"] for e in events[spans.SEARCH_SCORE]}
    submits = events[spans.SUBMIT]
    assert submits and all(e["parent"] == search_rid for e in submits)
    assert len(submits) == len(events[spans.SEARCH_SCORE])


def test_queue_wait_grows_with_each_dequeued_request(hw_analytical):
    svc = DesignCalculatorService([hw_analytical], start=False)
    try:
        stats = svc.stats()
        assert stats["queue_wait_s"] == 0.0 and stats["dequeued"] == 0
        svc.start()
        last = svc.stats()
        for n in range(1, 4):
            svc.what_if_design(SPECS[0], SPECS[n], W, hw_analytical, MIX)
            now = svc.stats()
            assert now["dequeued"] == last["dequeued"] + 1
            assert now["queue_wait_s"] > last["queue_wait_s"]
            last = now
    finally:
        svc.stop()


def test_gc_span_counts_collections_while_a_service_runs(hw_analytical):
    import gc
    users = spans._GC_STATE["users"]
    with DesignCalculatorService([hw_analytical]):
        assert spans._on_gc in gc.callbacks
        before = spans.totals()
        gc.collect()
        seconds, count = _delta(before, spans.GC)
        assert count >= 1 and seconds > 0.0
    assert spans._GC_STATE["users"] == users
    assert (spans._on_gc in gc.callbacks) == (users > 0)


def test_no_profiler_means_a_cheap_span():
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with spans.span("repro.test.cost", rid=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 50e-6


def test_stats_carry_the_span_table(hw_analytical):
    """``stats()`` holds ``span_s.<name>`` / ``span_n.<name>`` for every
    span, so a caller's window of stats deltas covers the spans too."""
    with DesignCalculatorService([hw_analytical]) as svc:
        before = svc.stats()
        svc.what_if_design(SPECS[0], SPECS[1], W, hw_analytical, MIX)
        after = svc.stats()
    for name in spans.NAMES:
        assert f"span_s.{name}" in after and f"span_n.{name}" in after
    for name in (spans.SUBMIT, spans.PACK, spans.FINALIZE):
        assert after[f"span_n.{name}"] > before[f"span_n.{name}"]
        assert after[f"span_s.{name}"] > before[f"span_s.{name}"]
    assert spans.counters()[f"span_n.{spans.SUBMIT}"] == \
        after[f"span_n.{spans.SUBMIT}"]
