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


def _loop_replay(spec, dep, *, qps, n_ticks, arrival, seed, resident):
    """The session replay as a plain loop over ticks (the program's
    reference): per-tick reads, writes, backlog, and hits, misses and
    prefill chunks; ``resident=False`` prefills every repeat again."""
    from collections import deque
    from repro.traces.synthetic import ARRIVALS
    starts = ARRIVALS[arrival](qps / dep.mean_asks, n_ticks, seed=seed)
    rng = np.random.default_rng(seed + 1)
    sessions, due, queue = [], {}, deque()
    sess, ctx, left = [-1] * dep.batch_slots, [0] * dep.batch_slots, \
        [0] * dep.batch_slots
    prefilling, hits, misses, chunks = [], 0, 0, 0
    kv = spec.kv_write_bytes_per_token
    token = (spec.state_bytes_per_token / 2.0
             + spec.moe_shuffle_bytes_per_token / 2.0)
    reads_t, writes_t, backlog = [], [], []
    for t in range(n_ticks):
        queue.extend(sorted(due.pop(t, ())))
        for _ in range(int(starts[t])):
            prompt = dep.prompt.draw(rng)
            k = int(rng.integers(dep.asks_per_prompt[0],
                                 dep.asks_per_prompt[1] + 1))
            answers = [dep.answer.draw(rng) for _ in range(k)]
            gaps = [float(rng.exponential(dep.ask_gap_ticks))
                    for _ in range(k - 1)]
            queue.append(len(sessions))
            sessions.append([prompt, answers, gaps, 0])
        for i in range(dep.batch_slots):
            if queue and sess[i] < 0:
                s = queue.popleft()
                sess[i], left[i] = s, sessions[s][1][sessions[s][3]]
                if resident and sessions[s][3] > 0:
                    ctx[i], hits = sessions[s][0], hits + 1
                else:
                    ctx[i], misses = 0, misses + 1
                    prefilling.append(i)
        reads = writes = 0.0
        tokens, budget = 0, dep.chunk_tokens
        while prefilling and budget:
            i = prefilling[0]
            c = min(sessions[sess[i]][0] - ctx[i], budget)
            r, w = spec.prefill_chunk_bytes(ctx[i], c)
            reads, writes = reads + r, writes + w
            ctx[i], budget, tokens, chunks = (ctx[i] + c, budget - c,
                                              tokens + c, chunks + 1)
            if ctx[i] == sessions[sess[i]][0]:
                prefilling.pop(0)
        held = sum(s >= 0 for s in sess)
        decoding = [i for i in range(dep.batch_slots)
                    if sess[i] >= 0 and i not in prefilling]
        for i in decoding:
            reads += ctx[i] * kv + token
            writes += kv + token
            ctx[i], left[i] = ctx[i] + 1, left[i] - 1
            if left[i] == 0:
                rec = sessions[sess[i]]
                rec[3] += 1
                if rec[3] < len(rec[1]):
                    due.setdefault(t + 1 + int(rec[2][rec[3] - 1]),
                                   []).append(sess[i])
                sess[i] = -1
        tokens += len(decoding)
        if tokens:
            reads += spec.tick_weight_bytes(tokens)
        reads_t.append(reads)
        writes_t.append(writes)
        backlog.append(float(len(queue) + held))
    return (np.asarray(reads_t), np.asarray(writes_t), np.asarray(backlog),
            hits, misses, chunks)


def test_replay_hits_skip_prefill_and_chunks_keep_the_budget(monkeypatch):
    from repro.traces import synthetic
    spec = SMALL.spec()
    load = dict(qps=SMALL.service_rate(), n_ticks=256, arrival="poisson",
                seed=11)

    def same_as_the_loop(got, resident):
        want = _loop_replay(spec, SMALL, resident=resident, **load)
        np.testing.assert_array_equal(got.read_bytes, want[0])
        np.testing.assert_array_equal(got.write_bytes, want[1])
        np.testing.assert_array_equal(got.backlog, want[2])
        assert (got.hits, got.misses, got.prefill_chunks) == want[3:]

    rep = replay_sessions(spec, SMALL, **load)
    same_as_the_loop(rep, resident=True)
    c = rep.counters()
    assert c["prefix_hits"] > 0 and c["asks_admitted"] > c["prefix_hits"]
    # prompts of 300-2400 tokens in chunks of at most 512
    assert c["prefill_chunks"] > c["asks_admitted"] - c["prefix_hits"]
    # the same sessions with every repeat prefilled again write more
    monkeypatch.setattr(synthetic._Session, "resident",
                        property(lambda self: False))
    cold = replay_sessions(spec, SMALL, **load)
    same_as_the_loop(cold, resident=False)
    assert cold.counters()["prefix_hits"] == 0
    assert cold.write_bytes.sum() > rep.write_bytes.sum()
    # a tick's cache writes never exceed the chunk budget plus a token a
    # slot; the kv part of the writes bounds it
    kv = spec.kv_write_bytes_per_token + spec.moe_shuffle_bytes_per_token / 2
    assert rep.write_bytes.max() <= (512 + 4) * kv * (1 + 1e-12)


#: the deployment of the benchmark's ``bridge.report`` cell
BRIDGE = ServingDeployment(DSV3)

#: sha256 of the session replay's per-tick float64 reads, writes and
#: backlog and its counters at 0.25, 1 and 2 times the service rate over
#: 512 ticks, pinned from the per-tick Python loop the device program
#: replaced
PINNED_REPLAY = {
    ("bridge", "poisson", 11):
        "9284244fbad94c9f6e54a05bd6c7f3ed1cc86d286bdba2046105e865e52b832b",
    ("bridge", "poisson", 2 ** 31 + 3):
        "c4181adcb2e7b77456262a683e09d906ad3747949ce58e9c5ec03bcfd8c129bc",
    ("bridge", "bursty", 11):
        "3a55f2de77f3d20b5ae284a3a7d40f662c133a13413b7983f4289d532ca4b2eb",
    ("bridge", "bursty", 2 ** 31 + 3):
        "6da7559c2c6660794e14ca6d6224ada88efe795908080bff42bf3fedcd49c06a",
    ("small", "poisson", 11):
        "ea2fbff047abaf160a20390489325a2f971696d6a5ae9e2631f305f4a76c52dc",
    ("small", "poisson", 2 ** 31 + 3):
        "fb51f3c4b31698c0c6bed91efff84ba8237e17c90b0746d3463b0ec0460650f3",
    ("small", "bursty", 11):
        "514cc516722190262085e10103c41b50454ad645b895dca41b4b72d54ec09f31",
    ("small", "bursty", 2 ** 31 + 3):
        "1ea317ed38eabf729be6714b9712928f8767ee14414ef80ddd2bbad666ac1052",
}


@pytest.mark.parametrize("case", sorted(PINNED_REPLAY))
def test_replay_bit_for_bit_as_pinned(case):
    from repro.traces.synthetic import replay_sessions_batch
    name, arrival, seed = case
    dep = {"bridge": BRIDGE, "small": SMALL}[name]
    reps, capacity = replay_sessions_batch(
        dep.spec(), dep, [(x * dep.service_rate(), arrival)
                          for x in (0.25, 1.0, 2.0)], n_ticks=512, seed=seed)
    h = hashlib.sha256()
    for rep in reps:
        for a in (rep.read_bytes, rep.write_bytes, rep.backlog):
            h.update(np.ascontiguousarray(a, np.float64).tobytes())
        h.update(repr((rep.prefill_chunks, rep.hits, rep.misses,
                       rep.busy_ticks, rep.union_sum)).encode())
    assert h.hexdigest() == PINNED_REPLAY[case]
    assert capacity & (capacity - 1) == 0


def test_serving_frontier_replays_in_one_program():
    """Every trace of a deployment's section replays in one program run;
    a seed whose sessions fit the same padded axis compiles nothing."""
    from repro.core import flitsim, space
    from repro.traces import serving_frontier
    space.clear_cache(["traces.replay"])
    seen = []
    for seed in (5, 6):
        serving_frontier(deployment=SMALL, arrivals=["poisson", "bursty"],
                         qps_points=[0.5, 2.0], n_ticks=256, n_phases=2,
                         protocols=["hbm_asym"], seed=seed)
        info = flitsim.last_run_info()["traces.replay"]
        assert info["traces"] == info["device_traces"] == 4
        seen.append((info["session_capacity"],
                     space.cache_stats(["traces.replay"])))
    (cap5, first), (cap6, second) = seen
    assert cap5 == cap6
    assert (first.misses, first.hits) == (1, 0)
    assert (second.misses, second.hits) == (1, 1)


def test_lengths_are_heavy_tailed_and_truncated():
    rng = np.random.default_rng(3)
    dist = ServingDeployment(DSV3).prompt
    draws = np.asarray([dist.draw(rng) for _ in range(4000)])
    assert draws.min() >= 16384 and draws.max() <= 131072
    assert np.mean(draws >= 32768) == pytest.approx(dist.survival(32768),
                                                    abs=0.03)
    assert draws.mean() > np.median(draws)
    assert dist.mean() == pytest.approx(draws.mean(), rel=0.03)


def test_bulk_draws_take_the_same_stream():
    """``draws(rng, n)`` is ``n`` calls of ``draw``, rejections included,
    and leaves the generator where they leave it."""
    dist = ServingDeployment(DSV3).prompt          # ~13% drawn below lo
    a, b = np.random.default_rng(9), np.random.default_rng(9)
    for n in (1, 4, 50):
        assert dist.draws(a, n) == [dist.draw(b) for _ in range(n)]
    assert a.random() == b.random()


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
    assert info["traces.replay"]["device_traces"] == 2
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
