"""CPU tests of the program's spans and program names, and of their
reduction: on a small trace written out as an XSpace text proto (two TPU
planes, nested ``repro.*`` spans with metadata, a second thread), on a
trace recorded here around tiny protocol-study queries, and on a tiny
stream over four virtual CPU devices."""
from __future__ import annotations

import json
import subprocess
import sys

import jax
import pytest

from conftest import HERE, subprocess_env, tiny_cell
from test_chip_trace import _plane

import span_reduce
import trace_reduce

SPACE = ("repro.space.evaluate", "repro.space.lower", "repro.space.assemble")
ENGINE = ("repro.engine.probe", "repro.engine.core", "repro.engine.escalate",
          "repro.engine.readback")
STREAM = ("repro.stream.marshal", "repro.stream.dispatch",
          "repro.stream.retire", "repro.stream.winners")
UNNAMED = ("jit(_unknown)", "jit(build)", "jit__unknown", "jit_build")


def _xspace(host_lines, host_names):
    """Two TPU planes (busy [1500, 2500] and [4000, 5000] ns on TPU:0,
    [2000, 2500] ns on TPU:1) and the given host lines."""
    ops = {1: "jit_a(1)", 2: "%fusion.1 = f32[8] fusion()"}
    dev0 = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(1, 1500, 2500), (1, 4000, 5000)]),
        ("XLA Ops", [(2, 1600, 2400), (2, 4000, 4500)])], ops)
    dev1 = _plane(2, "/device:TPU:1", [
        ("XLA Modules", [(1, 2000, 2500)]),
        ("XLA Ops", [(2, 2000, 2500)])], ops)
    host = _plane(3, "/host:CPU", host_lines, host_names)
    return jax.profiler.ProfileData.from_text_proto(
        "\n".join([dev0, dev1, host]))


@pytest.fixture(scope="module")
def grid_profile():
    names = {1: "bench.query", 2: "repro.space.evaluate",
             3: "repro.space.lower",
             4: "repro.engine.probe#family=flitsim.symmetric#",
             5: "repro.engine.readback#family=flitsim.symmetric#",
             6: "repro.space.assemble",
             7: "repro.compile#family=flitsim.symmetric,key=(1, 2)#"}
    return _xspace([
        ("python3", [(1, 0, 10000), (2, 100, 9000), (3, 200, 1000),
                     (4, 1000, 6000), (5, 2000, 3000), (6, 6000, 8000),
                     (3, 11000, 12000)]),
        ("other-thread", [(7, 1200, 1800)])], names)


def test_span_totals_inside_queries(grid_profile):
    red = span_reduce.reduce_spans(grid_profile)
    spans = red["spans"]
    # the metadata suffix is stripped; the lowering after the query is
    # left out (the trace holds a query, so only spans inside one count)
    assert set(spans) == {"repro.space.evaluate", "repro.space.lower",
                          "repro.engine.probe", "repro.engine.readback",
                          "repro.space.assemble", "repro.compile"}
    assert spans["repro.space.lower"]["n"] == 1
    ev = spans["repro.space.evaluate"]
    assert ev["total_s"] == pytest.approx(8900e-9)
    # less its children on the same thread (800 + 5000 + 2000 ns); the
    # compile on another thread is not its child
    assert ev["self_s"] == pytest.approx(1100e-9)
    assert spans["repro.engine.probe"]["self_s"] == pytest.approx(4000e-9)
    assert spans["repro.compile"]["self_s"] == pytest.approx(600e-9)
    # idle on two chips, averaged: TPU:0 busy 2000 ns and TPU:1 busy
    # 500 ns of the evaluation's 8900 ns
    assert ev["idle_s"] == pytest.approx(7650e-9)
    assert ev["idle_self_s"] == pytest.approx(1100e-9)
    probe = spans["repro.engine.probe"]
    assert probe["idle_s"] == pytest.approx(3750e-9)
    assert probe["idle_self_s"] == pytest.approx(3250e-9)
    rb = spans["repro.engine.readback"]
    assert rb["idle_s"] == rb["idle_self_s"] == pytest.approx(500e-9)
    # the compile: TPU:0 busy 300 of its 600 ns, TPU:1 idle throughout
    assert spans["repro.compile"]["idle_s"] == pytest.approx(450e-9)
    # the query: 10000 ns, idle 8000 and 9500 ns; the spans cover all of
    # it but the idle before 100 ns and after 9000 ns
    assert red["regions"] == 1
    assert red["region_s"] == pytest.approx(10000e-9)
    assert red["region_idle_s"] == pytest.approx(8750e-9)
    assert red["uncovered_idle_s"] == pytest.approx(1100e-9)


def test_stream_stretch_leaves_out_cut_spans():
    """Without a query span, only the spans the stretch (first to last
    device event, 1500-5000 ns) holds whole count."""
    names = {1: "repro.stream.dispatch#index=3#",
             2: "repro.stream.dispatch#index=4#",
             3: "repro.stream.retire", 4: "repro.stream.marshal"}
    pd = _xspace([("python3", [(1, 1400, 1600), (2, 2000, 2200),
                               (3, 2200, 4000), (4, 4900, 5100)])], names)
    red = span_reduce.reduce_spans(pd)
    assert set(red["spans"]) == {"repro.stream.dispatch",
                                 "repro.stream.retire"}
    dispatch = red["spans"]["repro.stream.dispatch"]
    assert dispatch["n"] == 1
    # TPU:0 busy over all of it, TPU:1 busy 200 of its 200 ns
    assert dispatch["idle_s"] == pytest.approx(0.0)
    retire = red["spans"]["repro.stream.retire"]
    # [2200, 4000]: TPU:0 busy 300 ns, TPU:1 busy 300 ns
    assert retire["idle_s"] == pytest.approx(1500e-9)
    assert red["region_s"] == pytest.approx(3500e-9)
    # idle in the stretch: 1500 ns on TPU:0, 3000 ns on TPU:1; the cut
    # spans cover their parts inside it, [1500, 1600] and [4900, 5000]
    # (idle on TPU:1 only), so [1600, 2000] and [4000, 4900] are left
    assert red["region_idle_s"] == pytest.approx(2250e-9)
    assert red["uncovered_idle_s"] == pytest.approx(650e-9)


def test_device_filter_and_no_device(grid_profile):
    one = span_reduce.reduce_spans(grid_profile, device_ids=[1])
    # TPU:1 alone: busy 500 ns inside the evaluation
    assert one["spans"]["repro.space.evaluate"]["idle_s"] == \
        pytest.approx(8400e-9)
    none = span_reduce.reduce_spans(grid_profile, device_ids=[7])
    assert none["spans"]["repro.space.evaluate"]["idle_s"] is None
    assert none["region_idle_s"] is None


@pytest.mark.parametrize("busy,a,b,want", [
    ([(10, 20), (30, 40)], 0, 50, 20),
    ([(10, 20), (30, 40)], 15, 35, 10),
    ([(10, 20), (30, 40)], 21, 29, 0),
    ([], 0, 50, 0),
])
def test_busy_within(busy, a, b, want):
    assert span_reduce.Busy(busy).within(a, b) == pytest.approx(want)


def test_harness_reduction_unchanged(grid_profile):
    """The span reduction reads the trace beside the harness's own, which
    still reads the same numbers from it."""
    red = trace_reduce.reduce_profile(grid_profile)
    span_reduce.reduce_spans(grid_profile)
    assert red == trace_reduce.reduce_profile(grid_profile)
    assert red["busy_s"] == pytest.approx((2000e-9 + 500e-9) / 2)


# -- the program's spans and names, recorded on the CPU ---------------------


def _host_names(pd):
    return {span_reduce.span_name(e.name) for plane in pd.planes
            for line in plane.lines for e in line.events}


def _module_names(families):
    from repro.core import space
    return {exe.as_text().split(",", 1)[0].split()[-1]
            for exe in space.programs(families).values()}


def test_grid_queries_record_every_span(tmp_path, fresh_programs):
    """Tiny protocol-study queries of both cells, and an asymmetric grid
    whose probe misses a few cells (they escalate), under a trace
    recorded here: every space and engine span is there, inside the
    query spans, and every program carries its family and path."""
    import harness
    from repro.core import ADAPTIVE_SIM, DesignSpace, axis
    from repro.core.space import FLITSIM_FAMILIES
    runners = []
    for workload in ("protocol_study.periodic", "protocol_study.aperiodic"):
        cell = tiny_cell(workload)
        mod = harness.load_module("runners", cell.config["runner"])
        runners.append(mod.Runner(cell.config, cell.traffic, 2 ** 31 + 9,
                                  1))
    mixes = [(i, 40 - i) for i in range(0, 36, 4)] + [(97, 31), (89, 53)]
    partly_periodic = DesignSpace(
        [axis("protocol", ("lpddr6_asym", "hbm_asym")), axis("mix", mixes)],
        sim=ADAPTIVE_SIM)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for runner in runners:
        with jax.profiler.TraceAnnotation("bench.query"):
            runner.query(0)
    with jax.profiler.TraceAnnotation("bench.query"):
        partly_periodic.evaluate(metrics=("sim_efficiency",))
    jax.profiler.stop_trace()
    from repro.core import flitsim
    assert flitsim.last_run_info()["flitsim.asymmetric"]["stragglers"] > 0
    pd = span_reduce.load_profile(str(tmp_path))
    spans = span_reduce.reduce_spans(pd)["spans"]
    for name in SPACE + ENGINE + ("repro.compile",):
        assert spans.get(name, {}).get("n", 0) >= 1, (name, sorted(spans))
        assert spans[name]["self_s"] <= spans[name]["total_s"]
    names = _host_names(pd)
    programs = {n for n in names if n.startswith("PjitFunction(jit(")}
    assert "PjitFunction(jit(flitsim_symmetric_probe))" in programs
    assert "PjitFunction(jit(flitsim_asymmetric_core))" in programs
    assert "PjitFunction(jit(flitsim_asymmetric_cells))" in programs
    assert not any(u in p for p in programs for u in UNNAMED), programs
    modules = _module_names(FLITSIM_FAMILIES)
    assert modules and all(m.startswith("jit_flitsim_") for m in modules), \
        modules


def test_traced_run_reads_spans_beside_the_harness(fresh_programs):
    """The traced run of the script: the harness's result line as it is,
    and the spans of the same trace inside its whole queries."""
    import time
    cell = tiny_cell("protocol_study.periodic")
    line = span_reduce.traced_run(cell, 2 ** 31 + 11, 0.5,
                                  t_process=time.perf_counter(),
                                  require_tpu=False, compile_cache=False,
                                  log=lambda s: None)
    assert line["correct"] is True, line["checks"]
    found = line["span_reduction"]
    assert found["regions"] == len(found["query_s"]) >= 1
    assert found["names"]["bench.query"] >= found["regions"]
    for name in ("repro.space.evaluate", "repro.engine.probe",
                 "repro.engine.readback"):
        assert found["spans"][name]["n"] >= found["regions"], name
    cost = span_reduce.span_cost_us(1000)
    assert set(cost) == {"plain", "one_keyword"}


STREAM_SCRIPT = """
import json, sys, tempfile
sys.path.insert(0, {here!r})
import jax
import harness, span_reduce
from conftest import tiny_cell
from repro.core import space
cell = tiny_cell("joint_space.1e7", chips=4)
runner = harness.load_module("runners", "joint_space").Runner(
    cell.config, cell.traffic, 2 ** 31 + 13, 4)
d = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(d, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench.query"):
    res = runner.query(0)
jax.profiler.stop_trace()
pd = span_reduce.load_profile(d)
names = sorted({{span_reduce.span_name(e.name) for p in pd.planes
                for l in p.lines for e in l.events}})
modules = sorted(exe.as_text().split(",", 1)[0].split()[-1] for exe in
                 space.programs(space.STREAM_FAMILIES).values())
print(json.dumps({{"devices": res.devices, "names": names,
                  "modules": modules}}))
"""


def test_stream_records_every_span_on_four_devices():
    env = subprocess_env(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", STREAM_SCRIPT.format(here=HERE)], env=env,
        capture_output=True, text=True, timeout=600, cwd=HERE)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    names = set(out["names"])
    for name in STREAM + ("repro.space.evaluate", "repro.compile"):
        assert name in names, (name, sorted(names))
    # the stream's chunk program keeps its name
    assert "PjitFunction(jit(chunk_fn))" in names
    assert out["modules"] == ["jit_chunk_fn"]
    assert not any(u in n for n in names for u in UNNAMED)
    assert not any(n.startswith("repro.engine.") for n in names)
