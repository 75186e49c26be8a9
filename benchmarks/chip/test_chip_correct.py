"""CPU tests of the correctness check at a tiny size: the bfloat16 control
comes out not correct, and so does a whole run with a fault planted in
the timed path underneath the harness (a step that returns its state
unchanged, half of the batch left out, an answer altered where it is
produced, and, with the stream sharded over four devices, the exchange
between chips left out)."""
from __future__ import annotations

import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE, run_tiny, subprocess_env, tiny_cell

import control
import harness

ONE_CHIP = [w["name"] for w in harness.load_spec()["workloads"]
            if w["chips"] == 1]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_not_correct(workload):
    cell = tiny_cell(workload)
    mod = harness.load_module("runners", cell.config["runner"])
    for seed in (2 ** 31 + 11, 2 ** 31 + 12, 2 ** 31 + 13):
        runner = mod.Runner(cell.config, cell.traffic, seed, 1)
        checks = runner.check(control.control_results(runner))
        assert not all(c["ok"] for c in checks.values()), checks


def _unchanged_state(monkeypatch):
    from repro.core import flitsim
    from repro.kernels.flit_sim import ref

    def sym(p, x, y, backlog):
        return lambda core: (core, jnp.zeros_like(core[0]))

    def asym(p, x, y):
        return lambda core: core

    for mod in (flitsim, ref):
        monkeypatch.setattr(mod, "_symmetric_stepfn", sym)
        monkeypatch.setattr(mod, "_asymmetric_stepfn", asym)


def _half_batch(monkeypatch):
    """Grids: the second half of the mixes is not simulated and reads the
    mean of the first half.  Streams: the second half of every dispatch
    is left out of the reductions."""
    from repro.core import flitsim, streaming
    grid, chunk_ids = flitsim.simulate_grid, streaming._chunk_ids

    def simulate_grid(*a, **kw):
        out = np.array(grid(*a, **kw))
        half = max(out.shape[-1] // 2, 1)
        out[..., half:] = out[..., :half].mean(axis=-1, keepdims=True)
        return jnp.asarray(out)

    def half_ids(lo, step, n_cells):
        ids, valid, live = chunk_ids(lo, step, n_cells)
        valid[step // 2:] = 0
        return ids, valid, live

    monkeypatch.setattr(flitsim, "simulate_grid", simulate_grid)
    monkeypatch.setattr(streaming, "_chunk_ids", half_ids)


def _altered_answer(monkeypatch):
    """The first row of answers is altered where the engine produces it:
    a grid's efficiency scaled by 0.9, a stream's winners moved to the
    next label."""
    from repro.core import flitsim, streaming
    grid, winners = flitsim.simulate_grid, streaming._winner_array

    def simulate_grid(*a, **kw):
        out = np.asarray(grid(*a, **kw)).copy()
        out[0] *= 0.9
        return jnp.asarray(out)

    def winner_array(codes, *a, **kw):
        labels = a[-1]
        codes = codes.copy()
        codes[:max(len(codes) // 4, 1)] = (
            codes[:max(len(codes) // 4, 1)] + 1) % len(labels)
        return winners(codes, *a, **kw)

    monkeypatch.setattr(flitsim, "simulate_grid", simulate_grid)
    monkeypatch.setattr(streaming, "_winner_array", winner_array)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("workload", ONE_CHIP)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(workload, fault, monkeypatch, fresh_programs):
    cell = tiny_cell(workload)
    assert run_tiny(cell)["correct"] is True
    from repro.core import clear_cache
    clear_cache()
    FAULTS[fault](monkeypatch)
    line = run_tiny(cell)
    assert line["correct"] is False, (fault, line["checks"])


EXCHANGE_SCRIPT = """
import sys
sys.path.insert(0, {here!r})
import jax
from conftest import run_tiny, tiny_cell
cell = tiny_cell("joint_space.1e7", chips=4)
assert run_tiny(cell)["correct"] is True
from repro.core import clear_cache
clear_cache()
# the exchange between chips left out: each device keeps its own partial
jax.lax.psum = lambda x, axis_name, **kw: x
jax.lax.pmax = lambda x, axis_name, **kw: x
print("correct", run_tiny(cell)["correct"])
"""


def test_exchange_left_out_is_not_correct():
    env = subprocess_env(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", EXCHANGE_SCRIPT.format(here=HERE)], env=env,
        capture_output=True, text=True, timeout=600, cwd=HERE)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "correct False"
