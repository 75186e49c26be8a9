"""DeepSeek-V3 on the serving-trace path: the configuration's counts, the
latent-cache and expert-share pricing, the session replay (prefix hits,
chunked prefill, heavy-tailed lengths), the deployment's record in the
serving section, and the three older trace models priced bit for bit as
before.  The comparison with the plain reference is in
``benchmarks/chip/test_chip_serving.py``."""
import hashlib

import numpy as np
import pytest

from repro.configs import arch_ids, get
from repro.configs.registry import traffic_config
from repro.traces import ModelTrafficSpec, synthetic_serving_trace
from repro.traces.deployment import LengthDist, ServingDeployment
from repro.traces.synthetic import replay_sessions

DSV3 = "deepseek-v3"

#: the older models' byte model and a digest of 27 of their traces,
#: pinned from the code before the deployment pricing existed
PINNED = {
    "smollm-360m": ((40960.0, 0.0, 0.0, 723642240.0),
                    "394afe5576018d603e2532c3a520232563f787a70476c579ca74e98214005dea"),
    "olmoe-1b-7b": ((131072.0, 0.0, 1048576.0, 2563903488.0),
                    "39f9cd8eb8c316c36b348a8481dd4ff47917d5a02c1a58f167e61ed0050d164a"),
    "mamba2-2.7b": ((0.0, 167772160.0, 0.0, 5662231552.0),
                    "05b85f923827569cf9e1dc0c3924bbdda0d3a9cda4865c2821d96c60aecd90dc"),
}

#: a deployment small enough to replay in a test: published widths, four
#: slots, short lengths so that repeats and chunks happen in 256 ticks
SMALL = ServingDeployment(
    DSV3, batch_slots=4, chunk_tokens=512,
    prompt=LengthDist(600.0, 0.6, 300, 2400),
    answer=LengthDist(16.0, 0.5, 4, 64), asks_per_prompt=(3, 5),
    ask_gap_ticks=8.0)


@pytest.mark.parametrize("model", sorted(PINNED))
def test_older_models_price_bit_for_bit(model):
    spec = ModelTrafficSpec.from_name(model)
    fields, digest = PINNED[model]
    assert (spec.kv_write_bytes_per_token, spec.state_bytes_per_token,
            spec.moe_shuffle_bytes_per_token,
            spec.weight_stream_bytes) == fields
    assert spec.tick_weight_bytes(7) == spec.weight_stream_bytes
    h = hashlib.sha256()
    for arr in ("poisson", "diurnal", "bursty"):
        for q in (0.05, 1.0, 4.0):
            t = synthetic_serving_trace(spec, qps=q, arrival=arr, seed=7)
            h.update(repr((t.durations, t.read_fractions,
                           t.backlogs)).encode())
    assert h.hexdigest() == digest


def test_config_counts_and_registration():
    cfg = traffic_config(DSV3)
    assert abs(cfg.param_count() / 671e9 - 1) < 0.01
    assert abs(cfg.active_param_count() / 37e9 - 1) < 0.03
    assert cfg.layer_kinds() == ("attn",) * 3 + ("moe",) * 58
    # traffic pricing only: no model builds it
    assert DSV3 not in arch_ids()
    with pytest.raises(KeyError):
        get(DSV3)


def test_latent_cache_and_expert_share():
    spec = SMALL.spec()
    cfg = traffic_config(DSV3)
    assert spec.kv_write_bytes_per_token == 61 * 1152
    mha = 61 * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    assert 50 < mha / spec.kv_write_bytes_per_token < 80
    assert spec.held_experts == 8 and spec.expert_parallel == 32
    # 3 x 7168 x 2048 FP8 bytes per routed expert
    assert spec.expert_bytes == 3 * 7168 * 2048
    # tokens routed in: 32 chips x top-8 x 8 of 256 = 8 a local token
    assert spec.moe_shuffle_bytes_per_token == 2 * 58 * 7168 * 8 * 2
    # the union grows with the global batch and saturates at the held 8
    assert spec.expert_union(1) == pytest.approx(
        8 * (1 - (1 - 8 / 256) ** 32))
    assert spec.expert_union(1) < spec.expert_union(4) < 8.0
    assert spec.expert_union(10_000) == pytest.approx(8.0)
    full = spec.weight_stream_bytes + 58 * 8 * spec.expert_bytes
    assert spec.tick_weight_bytes(10_000) == pytest.approx(full)
    # held weights: ~37 GB at 1 byte a parameter
    assert 36e9 < full + 129280 * 7168 < 38.5e9


def test_prefill_chunk_reads_the_prefix():
    spec = SMALL.spec()
    r0, w0 = spec.prefill_chunk_bytes(0, 100)
    r1, w1 = spec.prefill_chunk_bytes(400, 100)
    assert w0 == w1
    assert r1 - r0 == pytest.approx(400 * spec.kv_write_bytes_per_token)


def test_replay_hits_skip_prefill_and_chunks_keep_the_budget(monkeypatch):
    from repro.traces import synthetic
    spec = SMALL.spec()
    rep = replay_sessions(spec, SMALL, qps=SMALL.service_rate(),
                          n_ticks=256, arrival="poisson", seed=11)
    c = rep.counters()
    assert c["prefix_hits"] > 0 and c["asks_admitted"] > c["prefix_hits"]
    # prompts of 300-2400 tokens in chunks of at most 512
    assert c["prefill_chunks"] > c["asks_admitted"] - c["prefix_hits"]
    # the same sessions with every repeat prefilled again write more
    monkeypatch.setattr(synthetic._Session, "resident",
                        property(lambda self: False))
    cold = replay_sessions(spec, SMALL, qps=SMALL.service_rate(),
                           n_ticks=256, arrival="poisson", seed=11)
    assert cold.counters()["prefix_hits"] == 0
    assert cold.write_bytes.sum() > rep.write_bytes.sum()
    # a tick's cache writes never exceed the chunk budget plus a token a
    # slot; the kv part of the writes bounds it
    kv = spec.kv_write_bytes_per_token + spec.moe_shuffle_bytes_per_token / 2
    assert rep.write_bytes.max() <= (512 + 4) * kv * (1 + 1e-12)


def test_lengths_are_heavy_tailed_and_truncated():
    rng = np.random.default_rng(3)
    dist = ServingDeployment(DSV3).prompt
    draws = np.asarray([dist.draw(rng) for _ in range(4000)])
    assert draws.min() >= 16384 and draws.max() <= 131072
    assert np.mean(draws >= 32768) == pytest.approx(dist.survival(32768),
                                                    abs=0.03)
    assert draws.mean() > np.median(draws)
    assert dist.mean() == pytest.approx(draws.mean(), rel=0.03)


def test_service_rate():
    dep = ServingDeployment(DSV3)
    lifetime = 16 / dep.service_rate()
    assert 280 < lifetime < 300
    assert dep.mean_asks == 4.0


def test_serving_section_records_its_deployment(monkeypatch):
    from repro.core import DesignSpace, axis, flitsim
    from repro.core.report import ReportSpec
    seen = []
    from_name = ModelTrafficSpec.from_name.__func__
    monkeypatch.setattr(ModelTrafficSpec, "from_name", classmethod(
        lambda cls, arch, **kw: seen.append(arch) or from_name(
            cls, arch, **kw)))
    spec = ReportSpec(sections=("serving",), options={"serving": {
        "deployment": SMALL, "arrivals": ["poisson", "bursty"],
        "qps_points": [1.0], "n_ticks": 256, "n_phases": 4, "seed": 5}})
    rep = DesignSpace([axis("read_fraction", [0.5])]).report(spec)
    sf = rep["serving"].payload
    assert seen and set(seen) == {DSV3}
    assert sf["deployment"]["batch_slots"] == 4
    assert sf["deployment"]["chunk_tokens"] == 512
    assert sf["deployment"]["held_experts"] == 8
    assert sf["deployment"]["service_rate"] == SMALL.service_rate()
    assert sf["qps_points"] == [SMALL.service_rate()]
    assert sf["trace_names"] == [f"{DSV3}@poissonx1", f"{DSV3}@burstyx1"]
    assert set(sf["winner_by_model_qps"][DSV3]) == {"poisson@1",
                                                    "bursty@1"}
    eff = np.asarray(sf["phase_efficiency"]["hbm_asym"])
    assert eff.shape == (2, 4)
    info = flitsim.last_run_info()
    assert info["traces.replay"]["traces"] == 2
    assert info["traces.replay"]["prefix_hit_share"] > 0
    assert 0 < info["traces.replay"]["expert_union_mean"] <= 8
    assert set(info["report"]["seconds"]) == {"serving"}


def test_report_counts_each_sections_engine_runs():
    """The ``report`` counters keep every section's engine runs, though a
    later section's run of a family replaces the earlier in
    ``last_run_info()``: a trace scan counts its phases' cycles, an
    adaptive run its sequential depth, cells and certified cells."""
    from repro.core import ADAPTIVE_SIM, flitsim
    from repro.core.report import ReportSpec, build_report
    spec = ReportSpec(sections=("sim_phy", "serving", "phy"),
                      sim=ADAPTIVE_SIM, options={
        "sim_phy": {"n_fracs": 3, "backlogs": (2.0, 64.0)},
        "serving": {"deployment": SMALL, "arrivals": ["poisson"],
                    "qps_points": [1.0], "n_ticks": 256, "n_phases": 4,
                    "seed": 5}})
    build_report(spec)
    engines = flitsim.last_run_info()["report"]["engines"]
    assert engines["serving"] == {"sequential_depth": 4 * (2048 + 4096),
                                  "cells": 0, "certified_cells": 0}
    assert engines["phy"] == {"sequential_depth": 0, "cells": 0,
                              "certified_cells": 0}
    sim = engines["sim_phy"]
    assert 0 < sim["sequential_depth"] <= 2048 + 4096
    assert 0 <= sim["certified_cells"] <= sim["cells"]
    assert sim["cells"] > 0
