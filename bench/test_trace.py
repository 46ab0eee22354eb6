"""The reduction from a profiler trace to device metrics (CPU)."""
import pytest

from bench import trace


def _events(*spans):
    return [[name, float(a), float(b - a)] for name, a, b in spans]


@pytest.fixture()
def synthetic():
    """Two devices; device 0's operations overlap, device 1 runs one."""
    return {
        "devices": {
            "/device:TPU:0": {
                "ops": _events(("fusion", 100, 300), ("copy", 200, 400),
                               ("fusion", 600, 700), ("late", 950, 1200)),
                "modules": _events(("jit__sweep_kernel(3)", 100, 400),
                                   ("jit_other", 600, 700),
                                   ("pmap__score_kernel", 950, 1200)),
            },
            "/device:TPU:1": {
                "ops": _events(("fusion", 500, 600)),
                "modules": _events(("jit__sweep_kernel(3)", 500, 600)),
            },
        },
        "host": {"/host:CPU/python": _events(
            (trace.WINDOW_SPAN, 0, 1000), ("pack_sweep", 400, 500),
            ("submit", 700, 950))},
    }


def test_busy_is_the_union_of_operations_not_their_sum(synthetic):
    busy = trace.device_busy(synthetic, (0.0, 1000.0))
    # [100, 400] once although two operations cover [200, 300];
    # [600, 700]; and [950, 1000] of the operation the window cuts
    assert busy["/device:TPU:0"] == pytest.approx((300 + 100 + 50) * 1e-9)
    assert busy["/device:TPU:1"] == pytest.approx(100 * 1e-9)


def test_idle_share_is_over_the_window_and_averaged_over_chips(synthetic):
    one = trace.summarize(synthetic, chips=1)
    assert one["window_s"] == pytest.approx(1000e-9)
    assert one["idle_pct"] == pytest.approx(100 * (1 - 450 / 1000))
    two = trace.summarize(synthetic, chips=2)
    assert two["busy_s"] == pytest.approx((450 + 100) / 2 * 1e-9)
    assert two["idle_pct"] == pytest.approx(100 * (1 - 275 / 1000))
    assert two["devices_with_ops"] == 2


def test_fused_time_is_found_by_module_name(synthetic):
    fused, calls = trace.fused_seconds(synthetic, (0.0, 1000.0))
    # both sweep modules and the pmap score module; not jit_other
    assert calls == 3
    assert fused == pytest.approx((300 + 100 + 250) * 1e-9)


def test_idle_gaps_are_labelled_by_the_host(synthetic):
    gaps = trace.idle_gaps(synthetic, (0.0, 1000.0))
    lengths = [g[1] for g in gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert sum(lengths) == pytest.approx((1000 - 550) * 1e-9)
    # [700, 950] while the host submitted, [400, 500] while it packed,
    # [0, 100] with no host event recorded
    assert gaps[0] == ["submit", pytest.approx(250e-9)]
    assert {g[0] for g in gaps} == {"submit", "pack_sweep",
                                    "untraced host work"}


def test_load_reads_a_profiler_trace_and_its_window(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones(1024)
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    loaded = trace.load(trace.find_xplane(str(tmp_path)))
    lo, hi = trace.window_of(loaded)
    assert hi > lo
    # the CPU backend records no TPU planes: nothing to call a device
    assert loaded["devices"] == {}


def test_require_passes_a_trace_that_shows_every_chip(synthetic):
    summary = trace.summarize(synthetic, chips=2)
    assert trace.require(summary, chips=2) is summary


@pytest.mark.parametrize("case", ["no_window", "chip_without_ops",
                                  "no_fused_module"])
def test_require_refuses_a_trace_that_reads_as_idle(synthetic, case):
    chips = 2
    if case == "no_window":
        synthetic["host"] = {}
    elif case == "chip_without_ops":
        chips = 3             # a third chip has no plane in the trace
    else:
        for lines in synthetic["devices"].values():
            lines["modules"] = [["jit_other", 600.0, 100.0]]
    with pytest.raises(trace.TraceError):
        trace.require(trace.summarize(synthetic, chips), chips)


def test_load_keeps_host_lines_that_share_a_name(monkeypatch, tmp_path):
    """Threads' lines may carry one name; the window span on the first
    must survive the second."""
    from types import SimpleNamespace as NS
    import jax

    def line(name, *events):
        return NS(name=name, events=[NS(name=n, start_ns=a, duration_ns=d)
                                     for n, a, d in events])
    planes = [NS(name="/host:CPU", lines=[
        line("python3", (trace.WINDOW_SPAN, 10, 90)),
        line("python3", ("submit", 20, 5))])]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: NS(planes=planes)))
    loaded = trace.load(str(tmp_path / "t.xplane.pb"))
    assert trace.window_of(loaded) == (10.0, 100.0)
    assert sum(len(v) for v in loaded["host"].values()) == 2


def test_top_ops_name_an_operation_by_its_instruction(synthetic):
    ops = synthetic["devices"]["/device:TPU:0"]["ops"]
    ops.append(["%fusion.3 = f32[16,8192]{0,1:T(8,128)} fusion(s32[2048] "
                "%p0), kind=kLoop", 300.0, 200.0])
    top = dict(trace.top_ops(synthetic, (0.0, 1000.0)))
    assert top["fusion.3"] == pytest.approx(200e-9)
    # "fusion" ran 300 ns on device 0 and 100 ns on device 1
    assert top["fusion"] == pytest.approx(400e-9)
