"""Per-model serving frontier: which memory approach wins at which QPS.

For every (model, QPS) point a synthetic serving trace is generated
(:func:`~repro.traces.synthetic.synthetic_serving_trace` — config shapes
only, no weights), the whole batch is evaluated through the ``trace``
axis in ONE design-space evaluation per engine family, and the winning
flit-simulated protocol (duration-weighted ``trace_bandwidth_gbs`` on
the target PHY) is mapped to its catalog memory approach.  The report is
the ``serving_frontier`` section of ``design_space.json``; its winner
labels are gated by the CI summary golden.

QPS sensitivity is the point: low-QPS traces sit at drained backlogs and
decode-heavy read fractions, high-QPS traces saturate the queue and mix
in prefill write bursts, so the winning approach can flip along the QPS
axis — a frontier the static-mix sections cannot express.

Given a :class:`~repro.traces.deployment.ServingDeployment`, the section
answers for that one deployment instead: its model, every arrival
process of ``arrivals``, and QPS points given as multiples of the
deployment's service rate; the payload records the deployment, the
per-trace bandwidths and per-phase efficiencies behind each winner.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.traces.deployment import ServingDeployment
from repro.traces.model_traffic import ModelTrafficSpec
from repro.traces.synthetic import (replay_sessions_batch,
                                    synthetic_serving_trace)
from repro.traces.trace import TrafficTrace

#: model configs the committed artifact sweeps: a dense decoder, a MoE
#: (expert-shuffle bytes), and an SSM (context-independent state reads)
DEFAULT_MODELS: Tuple[str, ...] = ("smollm-360m", "olmoe-1b-7b",
                                   "mamba2-2.7b")
#: requests per engine tick — drained, at-capacity, and saturated
#: regimes (the default batch has 32 slots serving ~128-token decodes,
#: so its service rate is 0.25 req/tick: 0.05 drains to a shallow queue
#: where the asymmetric approaches win, 1.0 and 4.0 pile up backlog
#: where the optimized symmetric protocol takes over)
DEFAULT_QPS: Tuple[float, ...] = (0.05, 1.0, 4.0)


def serving_frontier(models: Sequence[str] = DEFAULT_MODELS,
                     qps_points: Sequence[float] = DEFAULT_QPS, *,
                     phy: Any = None,
                     protocols: Optional[Sequence[str]] = None,
                     n_phases: int = 6, n_ticks: int = 384,
                     batch_slots: int = 32, arrival: str = "diurnal",
                     seed: int = 0, sim=None,
                     deployment: Optional[ServingDeployment] = None,
                     arrivals: Optional[Sequence[str]] = None
                     ) -> Dict[str, Any]:
    """Build the per-(model, QPS) serving-frontier report.

    ``phy`` defaults to the paper's UCIe-A 32G point; ``sim`` is the
    trace engine's :class:`~repro.core.space.SimConfig` (fixed trace-scan
    core by default).  Winner labels are catalog approach keys
    (``A:lpddr6-asym`` ...), the vocabulary the summary golden gates.

    With ``deployment``, ``models`` is ignored, ``qps_points`` are
    multiples of the deployment's service rate, and every process of
    ``arrivals`` (default ``[arrival]``) is replayed at each of them.
    """
    from repro.core import UCIE_A_32G_55U, flitsim
    from repro.core.space import DesignSpace, axis

    if phy is None:
        phy = UCIE_A_32G_55U
    t0 = time.perf_counter()
    replay: Dict[str, Any] = {}
    if deployment is None:
        specs = {m: ModelTrafficSpec.from_name(m) for m in models}
        traces = []
        for i, (m, q) in enumerate((m, q) for m in models
                                   for q in qps_points):
            with TraceAnnotation("repro.traces.replay", index=i):
                traces.append(synthetic_serving_trace(
                    specs[m], qps=q, n_ticks=n_ticks, n_phases=n_phases,
                    batch_slots=batch_slots, arrival=arrival, seed=seed,
                    name=f"{m}@q{q:g}"))
    else:
        models = [deployment.model]
        mu = deployment.service_rate()
        arrivals = list(arrivals) if arrivals is not None else [arrival]
        points = [(x * mu, a) for a in arrivals for x in qps_points]
        names = [f"{deployment.model}@{a}x{x:g}" for a in arrivals
                 for x in qps_points]
        with TraceAnnotation("repro.traces.replay", index=0):
            reps, capacity = replay_sessions_batch(
                deployment.spec(), deployment, points, n_ticks=n_ticks,
                seed=seed)
            traces = [TrafficTrace.from_ticks(
                name, r.read_bytes, r.write_bytes, r.backlog,
                n_phases=n_phases) for name, r in zip(names, reps)]
        for r in reps:
            for key, v in r.counters().items():
                replay[key] = replay.get(key, 0) + v
        replay.update(device_traces=len(reps), session_capacity=capacity)
    flitsim.record_counters(
        "traces.replay", traces=len(traces), ticks=len(traces) * n_ticks,
        replay_s=time.perf_counter() - t0, **_replay_shares(replay))

    before = flitsim.compile_cache_stats()
    axes = [axis("trace", traces)]
    if protocols is not None:
        axes.append(axis("protocol", protocols))
    res = DesignSpace(axes, phy=phy, sim=sim).evaluate(
        metrics=("trace_phase_efficiency", "trace_bandwidth_gbs"))
    after = flitsim.compile_cache_stats()
    if deployment is not None:
        return _deployment_payload(deployment, arrivals, qps_points, phy,
                                   n_ticks, traces, res,
                                   after.misses - before.misses)
    from repro.core.selector import approach_key_for

    bw = res["trace_bandwidth_gbs"]             # [protocol, trace]
    best = bw.argbest("protocol")               # [trace]
    best_gbs = bw.best("protocol")
    names = list(bw.coord("trace"))

    winner: Dict[str, Dict[str, str]] = {}
    proto: Dict[str, Dict[str, str]] = {}
    gbs: Dict[str, Dict[str, float]] = {}
    for i, m in enumerate(models):
        winner[m], proto[m], gbs[m] = {}, {}, {}
        for j, q in enumerate(qps_points):
            k = str(best.values[i * len(qps_points) + j])
            qkey = f"{q:g}"
            proto[m][qkey] = k
            winner[m][qkey] = approach_key_for(k)
            gbs[m][qkey] = float(
                best_gbs.values[i * len(qps_points) + j])

    tele = {fam: info for fam, info in flitsim.last_run_info().items()
            if info.get("mode") == "trace"}
    return {
        "models": list(models),
        "qps_points": [float(q) for q in qps_points],
        "phy": phy.name,
        "arrival": arrival,
        "n_ticks": int(n_ticks),
        "n_phases": int(max(t.n_phases for t in traces)),
        "protocols": list(bw.coord("protocol")),
        "trace_names": names,
        "winner_by_model_qps": winner,
        "protocol_by_model_qps": proto,
        "winner_gbs_by_model_qps": gbs,
        "qps_sensitive": {
            m: len(set(winner[m].values())) > 1 for m in models},
        "traces": {
            t.name: {"durations": list(t.durations),
                     "read_fractions": list(t.read_fractions),
                     "backlogs": list(t.backlogs)}
            for t in traces},
        "telemetry": tele,
        "compiles": after.misses - before.misses,
    }


def _replay_shares(replay: Dict[str, Any]) -> Dict[str, Any]:
    """The session replays' counts as the ``traces.replay`` counters:
    prefill chunks, the share of admitted asks that hit a resident
    prompt, the mean expert union of the ticks that did work, the traces
    the device program replayed and its padded session axis."""
    if not replay:
        return {}
    asks, busy = replay["asks_admitted"], replay["busy_ticks"]
    return {"prefill_chunks": replay["prefill_chunks"],
            "prefix_hit_share": (replay["prefix_hits"] / asks
                                 if asks else 0.0),
            "expert_union_mean": (replay["expert_union_sum"] / busy
                                  if busy else 0.0),
            "device_traces": replay["device_traces"],
            "session_capacity": replay["session_capacity"]}


def _deployment_payload(dep: ServingDeployment, arrivals, multiples, phy,
                        n_ticks: int, traces, res,
                        compiles: int) -> Dict[str, Any]:
    """The serving section of one deployment: winners per trace (arrival
    and multiple of the service rate), with the bandwidths and per-phase
    efficiencies behind them and the deployment it answered."""
    from repro.core import flitsim
    from repro.core.selector import approach_key_for
    bw = res["trace_bandwidth_gbs"]             # [protocol, trace]
    eff = res["trace_phase_efficiency"]         # [protocol, trace, phase]
    best = bw.argbest("protocol")
    best_gbs = bw.best("protocol")
    names = [t.name for t in traces]
    protocols = list(bw.coord("protocol"))
    model = dep.model
    keys = [f"{a}@{x:g}" for a in arrivals for x in multiples]
    proto = {k: str(best.values[i]) for i, k in enumerate(keys)}
    winner = {k: approach_key_for(p) for k, p in proto.items()}
    return {
        "deployment": dep.record(),
        "models": [model],
        "arrival": list(arrivals),
        "qps_multiples": [float(x) for x in multiples],
        "qps_points": [float(x) * dep.service_rate() for x in multiples],
        "phy": phy.name,
        "n_ticks": int(n_ticks),
        "n_phases": int(max(t.n_phases for t in traces)),
        "protocols": protocols,
        "trace_names": names,
        "winner_by_model_qps": {model: winner},
        "protocol_by_model_qps": {model: proto},
        "winner_gbs_by_model_qps": {model: {
            k: float(best_gbs.values[i]) for i, k in enumerate(keys)}},
        "qps_sensitive": {model: len(set(winner.values())) > 1},
        "trace_bandwidth_gbs": {
            p: np.asarray(bw.values[j], np.float64).tolist()
            for j, p in enumerate(protocols)},
        "phase_efficiency": {
            p: np.asarray(eff.values[j], np.float64).tolist()
            for j, p in enumerate(protocols)},
        "traces": {
            t.name: {"durations": list(t.durations),
                     "read_fractions": list(t.read_fractions),
                     "backlogs": list(t.backlogs)}
            for t in traces},
        "replay": flitsim.last_run_info()["traces.replay"],
        "telemetry": {fam: info for fam, info
                      in flitsim.last_run_info().items()
                      if info.get("mode") == "trace"},
        "compiles": compiles,
    }
