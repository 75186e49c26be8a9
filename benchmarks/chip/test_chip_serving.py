"""CPU tests of the ``bridge.report`` cell against the plain serving
reference at a small size (two traces of 256 ticks in four phases, four
slots, short lengths, the model at its published widths): the byte
model, the session replay and its phases, the trace scans and winners,
the service rate written in the traffic file; a tiny whole run that is
correct, and planted faults and the bfloat16 control that are not."""
from __future__ import annotations

import copy

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_tiny

import control
import harness
import reference_serving as ref

WORKLOAD = "bridge.report"
SEED = 2 ** 31 + 21


def small_cell():
    """The cell at the small size: the serving traffic cut, the other
    report sections as committed (the golden summary gates them)."""
    cell = harness.find_cell(harness.load_spec(), WORKLOAD)
    cfg, traffic = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    cfg["deployment"].update(batch_slots=4, chunk_tokens=512)
    traffic.update(pool=2, n_ticks=256, n_phases=4,
                   arrivals=["poisson", "bursty"], qps_multiples=[1.0],
                   trace_seconds=0.2)
    traffic["sessions"].update(
        prompt={"median": 600.0, "sigma": 0.6, "lo": 300, "hi": 2400},
        answer={"median": 16.0, "sigma": 0.5, "lo": 4, "hi": 64},
        ask_gap_ticks=8.0)
    cell.config, cell.traffic = cfg, traffic
    return cell


def runner(cell=None, seed=SEED):
    cell = cell or small_cell()
    mod = harness.load_module("runners", cell.config["runner"])
    return mod.Runner(cell.config, cell.traffic, seed, 1)


def test_byte_model_matches_the_program():
    r = runner()
    spec = r.deployment.spec()
    model = ref.ByteModel(r.config)
    assert model.kv == spec.kv_write_bytes_per_token == 61 * 1152
    assert model.shuffle == spec.moe_shuffle_bytes_per_token
    assert model.weights == spec.weight_stream_bytes
    assert model.expert == spec.expert_bytes
    for tokens in (1, 3, 16, 600):
        assert model.tick_weights(tokens) == spec.tick_weight_bytes(tokens)
        assert model.union(tokens) == spec.expert_union(tokens)
    for ctx in (0, 17, 40000):
        assert model.decode(ctx) == spec.decode_bytes(ctx)
    assert model.prefill(512, 100) == spec.prefill_chunk_bytes(512, 100)


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 3])
@pytest.mark.parametrize("arrival", ["poisson", "bursty"])
def test_replay_and_phases_match_the_reference(arrival, seed):
    from repro.traces.synthetic import replay_sessions
    from repro.traces.trace import TrafficTrace
    r = runner()
    dep, tr = r.deployment, r.traffic
    mu = ref.service_rate(r.config["deployment"], tr["sessions"])
    assert mu == pytest.approx(dep.service_rate(), rel=1e-12)
    got = replay_sessions(dep.spec(), dep, qps=2 * mu, n_ticks=256,
                          arrival=arrival, seed=seed)
    want = ref.replay(ref.ByteModel(r.config), r.config["deployment"],
                      tr["sessions"], 2 * mu, arrival, 256, seed)
    np.testing.assert_allclose(got.read_bytes, want["read"], rtol=1e-12)
    np.testing.assert_allclose(got.write_bytes, want["write"], rtol=1e-12)
    assert got.backlog.tolist() == want["backlog"]
    c = got.counters()
    assert c["prefix_hits"] > 0
    assert c["prefill_chunks"] > c["asks_admitted"] - c["prefix_hits"]
    trace = TrafficTrace.from_ticks("t", got.read_bytes, got.write_bytes,
                                    got.backlog, n_phases=4)
    phases = ref.phases(want, 4)
    assert list(trace.durations) == phases["durations"]
    np.testing.assert_allclose(trace.read_fractions,
                               phases["read_fractions"], rtol=1e-12)
    np.testing.assert_allclose(trace.backlogs, phases["backlogs"],
                               rtol=1e-12)


def test_trace_scans_and_winners_match_the_reference():
    r = runner()
    res = r.query(0)
    d = r.digest(0, res)
    traces, eff, bw = r.reference(0, jnp.float32)
    assert d["eff"].shape == eff.shape == (5, 2, 4)
    np.testing.assert_allclose(d["eff"], eff, rtol=1e-6)
    labels = np.asarray(r.protocols, dtype=object)
    assert list(d["winners"]) == list(labels[np.argmax(bw, axis=0)])
    checks = r.check([(0, d)])
    assert all(c["ok"] for c in checks.values()), checks


def test_service_rate_written_in_the_traffic_file():
    cell = harness.find_cell(harness.load_spec(), WORKLOAD)
    r = runner(cell)
    mu = cell.traffic["service_rate"]
    assert r.deployment.service_rate() == pytest.approx(mu, rel=1e-12)
    assert ref.service_rate(cell.config["deployment"],
                            cell.traffic["sessions"]) == pytest.approx(
        mu, rel=1e-12)


def test_small_run_is_correct():
    line = run_tiny(small_cell())
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["phase_gap"]["value"] == 0.0


def _no_carry(monkeypatch, r):
    """Each phase of a trace starts from an empty queue and fresh
    credits."""
    from repro.core import flitsim
    sym, asym = flitsim._symmetric_trace_point, \
        flitsim._asymmetric_trace_point

    def sym_point(p, xs, ys, bls, *, n_phases, cycles):
        return jnp.concatenate([
            sym(p, xs[n:n + 1], ys[n:n + 1], bls[n:n + 1], n_phases=1,
                cycles=cycles) for n in range(n_phases)])

    def asym_point(p, xs, ys, *, n_phases, cycles):
        return jnp.concatenate([
            asym(p, xs[n:n + 1], ys[n:n + 1], n_phases=1, cycles=cycles)
            for n in range(n_phases)])

    monkeypatch.setattr(flitsim, "_symmetric_trace_point", sym_point)
    monkeypatch.setattr(flitsim, "_asymmetric_trace_point", asym_point)


def _hits_prefill(monkeypatch, r):
    """Repeat asks prefill their prompt again."""
    from repro.traces import synthetic
    monkeypatch.setattr(synthetic._Session, "resident",
                        property(lambda self: False))


def _mha_cache(monkeypatch, r):
    """The cache priced as a key and a value per head."""
    from repro.traces import model_traffic
    monkeypatch.setattr(model_traffic, "cache_values_per_token",
                        lambda cfg: 2 * cfg.num_kv_heads * cfg.head_dim)


FAULTS = {"no_carry": _no_carry, "hits_prefill": _hits_prefill,
          "mha_cache": _mha_cache}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch, fresh_programs):
    r = runner()
    FAULTS[fault](monkeypatch, r)
    res = r.query(0)
    checks = r.check([(0, r.digest(0, res))])
    assert not all(c["ok"] for c in checks.values()), (fault, checks)


def test_control_is_not_correct():
    r = runner()
    checks = r.check(control.control_results(r))
    assert not checks["eff_gap"]["ok"], checks
