"""The traffic generator and the load loops (CPU, no program)."""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import drive
from bench import traffic as tr

SEED = 2**31 + 11      # seeds run past 32 signed bits


@pytest.fixture(scope="module")
def ycsb_c():
    return tr.load_config("ycsb_c")


@pytest.fixture(scope="module")
def ycsb_a():
    return tr.load_config("ycsb_a")


def test_same_seed_same_questions_sweeps_and_searches(ycsb_c, ycsb_a):
    whatif = dict(tr.load_traffic("whatif"), rate_per_s=200.0)
    assert tr.whatif_questions(ycsb_c, whatif, SEED, 5.0) == \
        tr.whatif_questions(ycsb_c, whatif, SEED, 5.0)
    assert tr.whatif_questions(ycsb_c, whatif, SEED, 5.0) != \
        tr.whatif_questions(ycsb_c, whatif, SEED + 1, 5.0)
    sweep = dict(tr.load_traffic("sweep"), designs=64)
    assert tr.sweep_designs(ycsb_a, sweep, SEED, 1, 3) == \
        tr.sweep_designs(ycsb_a, sweep, SEED, 1, 3)
    search = tr.load_traffic("search")
    assert tr.search_seeds(search, SEED) == tr.search_seeds(search, SEED)
    # another seed searches the same list in another order
    assert sorted(tr.search_seeds(search, SEED)) == \
        sorted(tr.search_seeds(search, SEED + 1))


def test_whatif_mix_rate_and_run_length_hold_over_a_long_draw(ycsb_c):
    params = dict(tr.load_traffic("whatif"), rate_per_s=200.0)
    seconds = 200.0
    qs = tr.whatif_questions(ycsb_c, params, SEED, seconds)
    assert len(qs) == pytest.approx(200.0 * seconds, rel=0.03)
    assert all(a.due_s <= b.due_s for a, b in zip(qs, qs[1:]))
    share = {k: sum(q.kind == k for q in qs) / len(qs)
             for k in params["question_mix"]}
    for k, v in share.items():
        assert v == pytest.approx(1 / 3, abs=0.02), (k, v)
    # run lengths: consecutive questions of one session on one baseline
    runs, last = [], {}
    for q in qs:
        key = (q.design, q.hw)
        if last.get(q.session, (None,))[0] == key:
            last[q.session] = (key, last[q.session][1] + 1)
        else:
            if q.session in last:
                runs.append(last[q.session][1])
            last[q.session] = (key, 1)
    assert np.mean(runs) == pytest.approx(params["run_mean"], rel=0.1)
    kinds = {q.kind: q for q in qs}
    assert kinds["hardware"].new_hw != kinds["hardware"].hw
    assert kinds["design"].variant != kinds["design"].design


def test_no_sweep_of_a_run_repeats_another(ycsb_a):
    params = dict(tr.load_traffic("sweep"), designs=256)
    sweeps = [tuple(tr.sweep_designs(ycsb_a, params, SEED, c, k))
              for c in range(2) for k in range(8)]
    assert len(set(sweeps)) == len(sweeps)
    points = tr.sweep_points(ycsb_a, params)
    assert len(points) == 16
    shares = [p[1]["update"] / 100.0 for p in points]
    assert shares[0] == pytest.approx(0.05) and shares[-1] == \
        pytest.approx(0.5)
    four = tr.sweep_points(ycsb_a, dict(params, zipf_alphas=[0.5, 0.99]))
    assert len(four) == 32
    assert {p[0].zipf_alpha for p in four} == {0.5, 0.99}


class _StallingServer:
    """Answers each request 1 ms after it arrives, except that it stalls
    for ``stall_s`` once, from the ``stall_at``-th request on: requests
    queue behind the stall like behind a busy worker."""

    def __init__(self, stall_at: int, stall_s: float) -> None:
        self.stall_at, self.stall_s = stall_at, stall_s
        self.n = 0
        self.free_at = 0.0
        self.lock = threading.Lock()

    def submit(self, _item) -> Future:
        fut: Future = Future()
        with self.lock:
            now = time.perf_counter()
            start = max(now, self.free_at)
            if self.n == self.stall_at:
                start += self.stall_s
            self.free_at = start + 0.001
            self.n += 1
            delay = self.free_at - now
        threading.Timer(delay, fut.set_result, args=(True,)).start()
        return fut


def test_open_loop_times_from_the_due_time_so_a_stall_shows_in_p95():
    items = [i * 0.01 for i in range(100)]          # 100/s for one second
    server = _StallingServer(stall_at=50, stall_s=1.0)
    slots, t0 = drive.open_loop(items, lambda due: due, server.submit)
    end = drive.wait_open(slots, t0 + 10.0)
    lat = drive.latencies_ms(slots, end)
    # every request due during the stall waited for it
    assert drive.percentile(lat, 95) > 500.0
    assert drive.percentile(lat, 25) < 100.0
    assert all(s.ok for s in slots)
    # the generator kept its schedule: the stall is the server's
    assert drive.percentile(drive.lateness_ms(slots), 50) < 50.0


def test_failed_and_unanswered_requests_count_at_the_end_of_the_wait():
    def submit(i):
        fut: Future = Future()
        if i == 1:
            fut.set_exception(RuntimeError("refused"))
        elif i == 2:
            pass                                    # never resolves
        else:
            fut.set_result(True)
        return fut
    slots, t0 = drive.open_loop([0, 1, 2], lambda i: 0.0, submit)
    end = drive.wait_open(slots, t0 + 0.2)
    lat = drive.latencies_ms(slots, end)
    assert lat[0] < lat[1] and lat[2] >= 200.0 - 1e-6
    assert [s.ok for s in slots] == [True, False, False]
    assert slots[2].done is None


def test_closed_loop_stops_submitting_after_the_window_and_finishes():
    calls = []

    def make(client, k):
        def submit():
            fut: Future = Future()
            threading.Timer(0.05, fut.set_result, args=(k,)).start()
            calls.append((client, k))
            return fut
        return (client, k), submit

    slots, t0, end = drive.closed_loop(2, make, 0.3, timeout_s=5.0)
    assert all(s.ok for s in slots)
    assert 8 <= len(slots) <= 16
    assert end >= t0 + 0.3 and end - t0 < 0.3 + 0.2
