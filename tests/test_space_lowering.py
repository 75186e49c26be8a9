"""The materialized grids' host path: lowering and assembly in numpy.

Contracts:

  * every answer is bit-identical to the device-side lowering it
    replaced: a sha256 digest of ``sim_efficiency`` / ``trace_efficiency``
    is pinned for grids that take each engine path (the periodic probes,
    the chunked cores with straggler escalation, the fixed scans, the
    asymmetric broadcast over backlogs, the trace scans);
  * in a warm ``evaluate`` no host-to-device put and no eager
    ``jax.numpy`` op runs outside the compiled engine calls, but for the
    straggler escalation's per-cell gathers (counted here);
  * ``last_run_info()["space"]`` reads one readback per family;
  * ``DesignSpace`` still accepts a device array from
    ``flitsim.simulate_grid`` (the seam the chip benchmark's fault
    injection patches).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.core import flitsim
from repro.core.space import (
    ADAPTIVE_SIM, FIXED_SIM, DesignSpace, SimConfig, axis,
)
from repro.traces import TrafficTrace

SYM = ("cxl_unopt", "cxl_opt", "chi")
#: straggler denominators past PERIOD_MAX: the probes never certify them
APERIODIC_KS = [round(127 * k / 34) for k in range(1, 34)]


def _mixes(q, ks):
    return [(100.0 * k / q, 100.0 - 100.0 * k / q) for k in ks]


def _periodic():
    """Backlogs <= 2 and mixes a/32: every cell probe-certified."""
    return DesignSpace([axis("protocol", SYM + ("lpddr6_asym",)),
                        axis("backlog", [1.0, 1.5, 2.0]),
                        axis("mix", _mixes(32, (0, 3, 8, 13, 16, 21, 27,
                                                32)))])


def _aperiodic():
    """Backlogs 32-128 (symmetric probe skipped; 297 cells, chunked core
    and escalation) and denominator-127 mixes (asymmetric probe missed)."""
    return DesignSpace([axis("protocol", SYM + ("lpddr6_asym",)),
                        axis("backlog", [32.0, 64.0, 128.0]),
                        axis("mix", _mixes(127, APERIODIC_KS))])


def _lanes():
    """A total_lanes perturbation grid broadcast over two backlogs; one
    mix of denominator 127 escalates out of the asymmetric probe."""
    return DesignSpace([
        axis("protocol_param", [{}, {"total_lanes": 0.75},
                                {"total_lanes": 1.3}]),
        axis("protocol", ("lpddr6_asym", "hbm_asym")),
        axis("backlog", [8.0, 64.0]),
        axis("mix", _mixes(40, (1, 9, 20, 33)) + _mixes(127, (50,)))])


def _lanes_aperiodic():
    """264 asymmetric cells the probe misses: chunked core, escalation."""
    return DesignSpace([
        axis("protocol_param", [{}, {"total_lanes": 0.7},
                                {"total_lanes": 1.1}, {"total_lanes": 1.4}]),
        axis("protocol", ("lpddr6_asym", "hbm_asym")),
        axis("mix", _mixes(127, APERIODIC_KS))])


def _trace():
    return DesignSpace([
        axis("protocol_param", [{}, {"credit_lines": 0.5}]),
        axis("protocol", ("cxl_opt", "lpddr6_asym")),
        axis("trace", [TrafficTrace("burst", (1.0, 2.0), (0.1, 0.9),
                                    (2.0, 32.0)),
                       TrafficTrace.steady("steady", 0.7, 16.0)])],
        sim=SimConfig(trace_cycles=64))


#: case -> (space, sim, metric, families run, engine calls, host arrays
#: handed to them, escalated families)
CASES = {
    "periodic": (_periodic, ADAPTIVE_SIM, "sim_efficiency", 2, 2, 22, ()),
    "aperiodic": (_aperiodic, ADAPTIVE_SIM, "sim_efficiency", 2, 4, 30,
                  ("symmetric",)),
    "lanes_fixed": (_lanes, FIXED_SIM, "sim_efficiency", 1, 1, 8, ()),
    "lanes_adaptive": (_lanes, ADAPTIVE_SIM, "sim_efficiency", 1, 2, 8,
                       ("asymmetric",)),
    "lanes_aperiodic": (_lanes_aperiodic, ADAPTIVE_SIM, "sim_efficiency",
                        1, 3, 16, ("asymmetric",)),
    "trace": (_trace, None, "trace_efficiency", 2, 2, 22, ()),
}

#: sha256 of each case's answer, computed with the device-side lowering
#: and assembly this host path replaced
DIGESTS = {
    "periodic":
        "a3fec0480a0e31a017b01a53c833fbdaedd0e6e0b08ba5605c018c080dc5bb66",
    "aperiodic":
        "01088268bdaa41e93cda88cf2ed79d18dcdc5c322e660898da689074f4e815cf",
    "lanes_fixed":
        "7e8136c9a4473935512e077d687b04f063c9727fd8e381c29304aa50c57cee43",
    "lanes_adaptive":
        "7e8136c9a4473935512e077d687b04f063c9727fd8e381c29304aa50c57cee43",
    "lanes_aperiodic":
        "451234a7d5adf7792ae3d4209b839245f419f59b77de516ce23b4da84bb6c9e2",
    "trace":
        "30f4c9edfac378d11b80786959a193ea9a99c4b2edcb8d7ef60995d50722828f",
}

#: puts an escalation makes: one per parameter field (``_gather_cells``)
#: and one per gathered grid axis (x, y and, symmetric, the backlogs)
ESCALATION_PUTS = {"symmetric": 11 + 3, "asymmetric": 6 + 2}

#: the eager methods of a device array the old assembly called
ARRAY_METHODS = ("__getitem__", "reshape", "astype", "ravel", "transpose",
                 "squeeze", "__mul__", "__add__", "__sub__",
                 "__truediv__", "__gt__", "__lt__")


def _evaluate(case):
    build, sim, metric = CASES[case][:3]
    kw = {} if sim is None else {"sim": sim}
    return build().evaluate(metrics=(metric,), **kw)[metric]


def _digest(arr) -> str:
    v = np.ascontiguousarray(np.asarray(arr.values), np.float32)
    h = hashlib.sha256(repr((arr.dims, v.shape)).encode())
    h.update(v.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("case", sorted(CASES))
def test_answers_bit_identical(case):
    assert _digest(_evaluate(case)) == DIGESTS[case]


def _count_device_calls(monkeypatch):
    """Record every put and eager ``jax.numpy`` op as ``(name, inside an
    escalation)``, through ``unittest.mock`` wrappers of ``jax.device_put``,
    every public ``jax.numpy`` function and a device array's eager
    methods."""
    from unittest import mock
    calls, depth = [], [0]

    def recorder(name, fn):
        def record(*a, **kw):
            calls.append((name, depth[0] > 0))
            return fn(*a, **kw)
        return record

    for name in dir(jnp):
        fn = getattr(jnp, name)
        if name.startswith("_") or not callable(fn) or isinstance(fn, type):
            continue
        monkeypatch.setattr(jnp, name, mock.Mock(
            side_effect=recorder(f"jnp.{name}", fn)))
    monkeypatch.setattr(jax, "device_put", mock.Mock(
        side_effect=recorder("jax.device_put", jax.device_put)))
    for name in ARRAY_METHODS:
        monkeypatch.setattr(ArrayImpl, name, recorder(
            f"Array.{name}", getattr(ArrayImpl, name)))

    escalate = flitsim._escalate_stragglers

    def escalate_counted(*a, **kw):
        depth[0] += 1
        try:
            return escalate(*a, **kw)
        finally:
            depth[0] -= 1
    monkeypatch.setattr(flitsim, "_escalate_stragglers", escalate_counted)
    return calls


@pytest.mark.parametrize("case", sorted(CASES))
def test_warm_evaluate_sends_host_arrays(case, monkeypatch):
    families, engine_calls, host_args, escalated = CASES[case][3:]
    want = _evaluate(case)                      # compiles every program
    calls = _count_device_calls(monkeypatch)
    got = _evaluate(case)
    monkeypatch.undo()
    np.testing.assert_array_equal(got.values, want.values)
    assert [c for c, inside in calls if not inside] == []
    # what is left is the escalation's per-cell gathers, one put each
    inside = [c for c, inside in calls if inside]
    assert set(inside) <= {"jnp.asarray"}
    assert len(inside) == sum(ESCALATION_PUTS[f] for f in escalated)
    info = flitsim.last_run_info()
    for fam in escalated:
        assert info[f"flitsim.{fam}"]["stragglers"] > 0
    space = info["space"]
    assert space["readbacks"] == families
    assert space["engine_calls"] == engine_calls
    assert space["host_args"] == host_args
    assert space["host_arg_bytes"] >= 4 * host_args


def test_simulate_grid_returns_host_array():
    eff = flitsim.simulate_grid(("chi", "lpddr6_asym"), [50.0, 80.0],
                                [50.0, 20.0], [2.0, 8.0], n_flits=256,
                                n_accesses=256)
    assert isinstance(eff, np.ndarray) and eff.dtype == np.float32
    assert eff.shape == (1, 2, 2, 2)
    # the asymmetric row is broadcast over the backlogs
    np.testing.assert_array_equal(eff[0, 1, 0], eff[0, 1, 1])


def test_device_array_from_simulate_grid_still_evaluates(monkeypatch):
    """The chip benchmark's planted faults patch ``flitsim.simulate_grid``
    with functions returning device arrays; the design space reads them
    back itself."""
    grid = flitsim.simulate_grid
    monkeypatch.setattr(flitsim, "simulate_grid",
                        lambda *a, **kw: jnp.asarray(grid(*a, **kw)))
    assert _digest(_evaluate("periodic")) == DIGESTS["periodic"]
    # each family's answer, then the patched function's device array
    assert flitsim.last_run_info()["space"]["readbacks"] == 3
