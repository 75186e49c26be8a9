"""CPU tests of the trace reduction, on a small trace written out as an
XSpace text proto (two TPU planes, nested op events, harness spans) and
on a trace recorded here on the CPU."""
from __future__ import annotations

import jax
import pytest

import trace_reduce


def _event(meta: int, start_ns: int, end_ns: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {(end_ns - start_ns) * 1000} }}")


def _plane(pid: int, name: str, lines, names) -> str:
    body = [f"id: {pid}", f'name: "{name}"']
    for lid, (lname, events) in enumerate(lines, start=1):
        evs = " ".join(_event(*e) for e in events)
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    f"{evs} }}")
    for key, n in names.items():
        body.append(f'event_metadata {{ key: {key} value {{ id: {key} '
                    f'name: "{n}" }} }}')
    return "planes { " + " ".join(body) + " }"


@pytest.fixture(scope="module")
def profile():
    ops = {1: "jit_a(1)", 2: "jit_b(2)", 3: "%while.1 = (f32[8]) while()",
           4: "%fusion.2 = f32[8] fusion()", 5: "%copy.1 = f32[8] copy()"}
    dev0 = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(1, 1000, 3000), (2, 7500, 8500),
                         (2, 9500, 10000)]),
        ("XLA Ops", [(3, 1000, 3000), (4, 1200, 1800), (4, 2000, 2600),
                     (5, 7500, 8500), (5, 9500, 10000)])], ops)
    dev1 = _plane(2, "/device:TPU:1", [
        ("XLA Modules", [(1, 1000, 2000)]),
        ("XLA Ops", [(4, 1000, 2000)])], ops)
    host = _plane(3, "/host:CPU", [
        ("python3", [(1, 100, 10100), (2, 200, 5200), (3, 6000, 7000),
                     (4, 8000, 9900)]),
        ("other-thread", [(5, 0, 20000)])],
        {1: "bench.query", 2: "bench.evaluate", 3: "DevicePut",
         4: "bench.readback", 5: "ThreadWait"})
    return jax.profiler.ProfileData.from_text_proto(
        "\n".join([dev0, dev1, host]))


def test_busy_union_and_window(profile):
    red = trace_reduce.reduce_profile(profile)
    # the window runs from the first to the last device event
    assert red["window_s"] == pytest.approx(9000e-9)
    # nested op events count once: 3500 ns on TPU:0, 1000 ns on TPU:1
    assert red["busy_s_by_device"] == {"0": pytest.approx(3500e-9),
                                       "1": pytest.approx(1000e-9)}
    assert red["busy_s"] == pytest.approx(2250e-9)
    assert red["devices"] == 2
    assert red["module_s"] == {"jit_a(1)": pytest.approx(3000e-9),
                               "jit_b(2)": pytest.approx(1500e-9)}
    assert red["module_runs"] == {
        "jit_a(1)": [pytest.approx(1000e-9), pytest.approx(2000e-9)],
        "jit_b(2)": [pytest.approx(1000e-9), pytest.approx(500e-9)]}
    # the one query span: 10000 ns long, busy 3500 and 1000 ns on the chips
    assert red["queries"] == [[pytest.approx(10000e-9),
                               pytest.approx(2250e-9)]]


def test_device_filter(profile):
    red = trace_reduce.reduce_profile(profile, device_ids=[1])
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(1000e-9)


def test_top_ops_by_module(profile):
    red = trace_reduce.reduce_profile(profile)
    assert [name for name, _ in red["top_ops"]] == [
        "jit_a(1)/fusion.2", "jit_a(1)/while.1", "jit_b(2)/copy.1"]
    assert red["top_ops"][2][1] == pytest.approx(1500e-9)
    assert red["top_ops"][0][1] == pytest.approx(2200e-9)


def test_idle_gaps_labelled_by_host(profile):
    red = trace_reduce.reduce_profile(profile)
    # a gap is labelled with the innermost event of the harness's thread
    # around its middle; events of other threads do not count
    assert red["idle_gaps"] == [
        ["bench.query", pytest.approx(4500e-9)],
        ["bench.readback", pytest.approx(1000e-9)]]


def test_readers(profile):
    red = trace_reduce.reduce_profile(profile)
    ctx = {"trace": red, "counters": [{"dispatches": 4},
                                      {"dispatches": 6}]}
    # the stretch: busy 2250 of 9000 ns
    assert trace_reduce.idle_share(ctx) == pytest.approx(75.0)
    # whole queries: busy 2250 ns of one 10000 ns query
    assert trace_reduce.query_idle_share(ctx) == pytest.approx(77.5)
    assert trace_reduce.device_ms_per_query(ctx) == pytest.approx(2250e-6)
    # jit_b runs 1000 and 500 ns, five dispatches a query
    assert trace_reduce.program_ms_per_query(ctx, "jit_b") == \
        pytest.approx(3750e-6)
    assert trace_reduce.program_ms_per_query(ctx, "jit") is None
    assert trace_reduce.program_ms_per_query(
        {"trace": red, "counters": []}, "jit_b") is None
    for read in (trace_reduce.idle_share, trace_reduce.query_idle_share,
                 trace_reduce.device_ms_per_query):
        assert read({"trace": None}) is None


def test_program_runs_cut_by_the_stretch_are_left_out():
    """The first and the last execution of a program may be cut by the
    traced stretch; with more than two, the mean leaves them out."""
    ctx = {"trace": {"module_runs": {
        "jit_chunk_fn(7)": [0.001, 0.004, 0.004, 0.002],
        "jit_other(8)": [1.0, 1.0, 1.0]}},
        "counters": [{"dispatches": 626}]}
    assert trace_reduce.program_ms_per_query(ctx, "jit_chunk_fn") == \
        pytest.approx(4.0 * 626)


@pytest.mark.parametrize("intervals,merged", [
    ([(5, 6), (1, 3), (2, 4)], [(1, 4), (5, 6)]),
    ([(1, 1), (2, 3)], [(2, 3)]),
    ([(1, 10), (2, 3)], [(1, 10)]),
])
def test_union(intervals, merged):
    assert trace_reduce.union(intervals) == merged


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here reduces without error; a CPU trace has no
    TPU plane, so no device is read and the device readers find
    nothing."""
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jax.numpy.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.query"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = trace_reduce.reduce_dir(str(tmp_path))
    assert red["devices"] == 0 and red["busy_s"] == 0
    assert red["window_s"] == 0 and red["idle_gaps"] == []
    # the query span is whole in the trace; no device time inside it
    assert len(red["queries"]) == 1 and red["queries"][0][1] == 0
    for read in (trace_reduce.idle_share, trace_reduce.query_idle_share,
                 trace_reduce.device_ms_per_query):
        assert read({"trace": red}) is None


def test_harness_line_without_spans(tmp_path):
    """A stretch inside a long query holds none of the harness's spans:
    the gaps are then labelled from the main thread's own events."""
    f = jax.jit(lambda x: (x * 3.0).sum())
    x = jax.numpy.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.TraceAnnotation("bench.query"):
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        for _ in range(3):
            f(x).block_until_ready()
        jax.profiler.stop_trace()
    import glob
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    pd = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in pd.planes if p.name == trace_reduce.HOST_PLANE)
    events = trace_reduce.harness_lines(list(host.lines))
    names = {name for _, _, name in events}
    assert trace_reduce.QUERY_SPAN not in names
    assert any(n.startswith("PjitFunction") for n in names), names
