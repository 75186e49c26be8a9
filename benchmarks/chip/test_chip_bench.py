"""CPU tests of the chip benchmark's harness: discovery by name, the
contract's shape of ``BENCHMARK.json``, each query runner at a tiny size,
seeds that change values and not paths, and the run command's refusal to
run off a TPU."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from conftest import HERE, run_tiny, subprocess_env, tiny_cell

import harness

SPEC = harness.load_spec()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(WORKLOADS) // 2)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_discovery_by_name(workload):
    """Every name in BENCHMARK.json resolves to its own file: the
    configuration, the traffic mix, the runner the configuration names,
    and a reader for each per-layer metric of the cell."""
    cell = harness.find_cell(SPEC, workload)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == workload)
    runner = harness.load_module("runners", cell.config["runner"]).Runner
    reported = {runner.RATE_METRIC, runner.P95_METRIC, "setup_s"}
    assert {m["name"] for m in cell.end_to_end} <= reported
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_every_metric_reader_and_file_is_named_in_the_spec():
    readers = {f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
               if f.endswith(".py")}
    assert readers == {m["name"] for m in SPEC["per_layer"]}
    traffic = {f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))}
    assert traffic == {w["traffic"] for w in SPEC["workloads"]}


@pytest.mark.parametrize("workload", ONE_CHIP)
@pytest.mark.parametrize("trace", [False, True])
def test_runner_tiny(workload, trace):
    """A whole run of each cell at a tiny size on the CPU: correct, whole
    queries, and the metrics the spec names for the kind of run."""
    cell = tiny_cell(workload)
    line = run_tiny(cell, trace=trace)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    want = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    if trace:
        # a CPU trace has no TPU plane: the device readers find nothing
        want = {n for n in want
                if not n.startswith(("kernel.", "device."))}
    assert want <= set(line["metrics"]), (want, line["metrics"])
    assert all(v["value"] > 0 for k, v in line["metrics"].items()
               if k != "engine.probe_certified_share")
    json.dumps(line)


def _certified_share(workload: str, seed: int):
    cell = harness.find_cell(SPEC, workload)
    cell.traffic = dict(cell.traffic, pool=1)
    mod = harness.load_module("runners", cell.config["runner"])
    runner = mod.Runner(cell.config, cell.traffic, seed, 1)
    runner.query(0)
    c = runner.counters()
    return 100.0 * c["certified_cells"] / c["cells"], runner.pool[0]


def test_seeds_change_values_not_paths():
    """At the cells' own sizes: a dozen seeds draw different mixes and
    perturbations, and every seed sends the same share of cells through
    the periodic probes: all of them in ``.periodic``, none in
    ``.aperiodic``."""
    shares = {}
    for workload in ("protocol_study.periodic", "protocol_study.aperiodic"):
        seen, values = set(), []
        for seed in range(2 ** 31 + 100, 2 ** 31 + 112):
            share, inp = _certified_share(workload, seed)
            seen.add(share)
            values.append(json.dumps(inp, sort_keys=True))
        assert len(seen) == 1, (workload, seen)
        assert len(set(values)) == 12
        shares[workload] = seen.pop()
    assert shares["protocol_study.periodic"] == 100.0
    assert shares["protocol_study.aperiodic"] == 0.0


def test_joint_seeds_order_the_same_scales():
    """Every seed asks the same perturbation scales, in its own order."""
    cell = harness.find_cell(SPEC, "joint_space.1e7")
    mod = harness.load_module("runners", "joint_space")
    a = mod.Runner(cell.config, cell.traffic, 2 ** 31 + 3, 1).scales
    b = mod.Runner(cell.config, cell.traffic, 2 ** 31 + 4, 1).scales
    lo, hi = cell.config["perturbation"]["range"]
    assert a.shape == b.shape == (cell.traffic["perturbations"],)
    assert not (a == b).all()
    assert (np.sort(a) == np.sort(b)).all()
    assert a.min() == lo and a.max() == hi


def test_run_exits_nonzero_off_tpu():
    env = subprocess_env(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "protocol_study.periodic", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_no_backend_at_import():
    """Importing the harness, its readers and runners, and these tests
    starts no JAX backend (so no worker loads libtpu while collecting)."""
    code = (
        "import sys, os; sys.path.insert(0, %r)\n"
        "import harness, reference, trace_reduce, control, conftest\n"
        "import test_chip_bench, test_chip_trace, test_chip_correct\n"
        "for kind in ('runners', 'metrics'):\n"
        "    for f in sorted(os.listdir(os.path.join(%r, kind))):\n"
        "        if not f.endswith('.py'): continue\n"
        "        harness.load_module(kind, f[:-3])\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "assert not any('libtpu' in m for m in sys.modules)\n"
        "print('ok')\n") % (HERE, HERE)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, cwd=HERE,
                          env=subprocess_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("ok")
