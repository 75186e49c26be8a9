"""Synthetic serving traces: the no-weights tier-1 fallback.

Replays a continuous-batching serving engine (fixed decode slots, FIFO
admission — the same lifecycle as ``repro.serve.ServingEngine``) as a
pure-numpy queueing simulation over a model's
:class:`~repro.traces.model_traffic.ModelTrafficSpec`, then compiles the
per-tick byte/backlog records into a :class:`TrafficTrace`.  No model is
built and no weights exist, so CI and tier-1 tests can sweep full-size
architectures (the byte model needs only config shapes).

One tick is one decode step for every active slot.  Arrivals come from
:mod:`repro.traces.arrival`; queue depth plus active sequences is the
recorded backlog, which is what makes the compiled trace QPS-sensitive:
past the service rate the queue (and the simulated flit backlog) grows,
and prefill admissions pull the read fraction down from the decode
stream's read-heavy steady state.

With a :class:`~repro.traces.deployment.ServingDeployment` the replay is
:func:`replay_sessions`: sessions that ask one long prompt several
times (the repeats hit the resident cache), heavy-tailed lengths, and
chunked prefill under a per-tick token budget.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, Optional

import numpy as np

from repro.traces.arrival import (bursty_arrivals, diurnal_arrivals,
                                  poisson_arrivals)
from repro.traces.deployment import ServingDeployment
from repro.traces.model_traffic import ModelTrafficSpec
from repro.traces.trace import TrafficTrace

ARRIVALS = {
    "poisson": poisson_arrivals,
    "diurnal": diurnal_arrivals,
    "bursty": bursty_arrivals,
}


def synthetic_serving_trace(spec: ModelTrafficSpec, *, qps: float,
                            n_ticks: int = 384, n_phases: int = 6,
                            batch_slots: int = 32, prompt_len: int = 512,
                            decode_len: int = 128,
                            arrival: str = "diurnal", seed: int = 0,
                            name: Optional[str] = None,
                            deployment: Optional[ServingDeployment] = None,
                            counters: Optional[Dict[str, Any]] = None
                            ) -> TrafficTrace:
    """Generate a phase-compiled trace for ``spec`` under ``qps``
    requests per tick.

    The queueing replay admits arrivals into ``batch_slots`` decode
    slots (prompt/decode lengths jittered around ``prompt_len`` /
    ``decode_len``), prices every prefill and decode step through the
    spec's byte model, and records per-tick read/write bytes plus the
    outstanding-request backlog.  ``arrival`` picks the process:
    ``"poisson"`` (stationary), ``"diurnal"`` (day/night swing) or
    ``"bursty"`` (flash crowds).

    With ``deployment`` the replay is :func:`replay_sessions` at ``qps``
    asks a tick, and the deployment's slots and lengths replace
    ``batch_slots`` / ``prompt_len`` / ``decode_len``; its counters are
    written into ``counters`` when given.
    """
    if arrival not in ARRIVALS:
        raise ValueError(f"unknown arrival process {arrival!r}; choose "
                         f"from {sorted(ARRIVALS)}")
    if qps < 0:
        raise ValueError(f"qps must be >= 0, got {qps}")
    label = name if name is not None else \
        f"{spec.name}@qps{qps:g}-{arrival}"
    if deployment is not None:
        rep = replay_sessions(spec, deployment, qps=qps, n_ticks=n_ticks,
                              arrival=arrival, seed=seed)
        if counters is not None:
            counters.update(rep.counters())
        return TrafficTrace.from_ticks(label, rep.read_bytes,
                                       rep.write_bytes, rep.backlog,
                                       n_phases=n_phases)
    n_ticks = int(n_ticks)
    arrivals = ARRIVALS[arrival](qps, n_ticks, seed=seed)
    rng = np.random.default_rng(seed + 1)

    queue: deque = deque()          # pending prompt lengths
    positions = np.zeros(batch_slots, np.int64)      # context per slot
    remaining = np.zeros(batch_slots, np.int64)      # decode tokens left
    active = np.zeros(batch_slots, bool)

    read_b = np.zeros(n_ticks, np.float64)
    write_b = np.zeros(n_ticks, np.float64)
    backlog = np.zeros(n_ticks, np.float64)

    def jitter(mean: int) -> int:
        return max(int(rng.integers(max(mean // 2, 1),
                                    mean + mean // 2 + 1)), 1)

    for t in range(n_ticks):
        for _ in range(int(arrivals[t])):
            queue.append(jitter(prompt_len))
        # admit into free slots; prefill is the write burst
        for slot in np.flatnonzero(~active):
            if not queue:
                break
            plen = queue.popleft()
            r, w = spec.prefill_bytes(plen)
            read_b[t] += r
            write_b[t] += w
            positions[slot] = plen
            remaining[slot] = jitter(decode_len)
            active[slot] = True
        # decode one token for every active slot
        slots = np.flatnonzero(active)
        for slot in slots:
            r, w = spec.decode_bytes(int(positions[slot]))
            read_b[t] += r
            write_b[t] += w
            positions[slot] += 1
            remaining[slot] -= 1
            if remaining[slot] <= 0:
                active[slot] = False
        if slots.size:
            # weights stream once per tick, amortized over the batch
            read_b[t] += spec.weight_stream_bytes
        backlog[t] = len(queue) + slots.size

    return TrafficTrace.from_ticks(label, read_b, write_b, backlog,
                                   n_phases=n_phases)


@dataclasses.dataclass
class SessionReplay:
    """Per-tick records of one session replay and its counts."""

    read_bytes: np.ndarray
    write_bytes: np.ndarray
    backlog: np.ndarray
    prefill_chunks: int = 0
    hits: int = 0
    misses: int = 0
    union_sum: float = 0.0
    busy_ticks: int = 0

    def counters(self) -> Dict[str, Any]:
        asks = self.hits + self.misses
        return {"prefill_chunks": self.prefill_chunks,
                "prefix_hits": self.hits, "asks_admitted": asks,
                "expert_union_sum": self.union_sum,
                "busy_ticks": self.busy_ticks}


@dataclasses.dataclass
class _Session:
    """One session's draws and how many of its asks have completed."""

    prompt: int
    answers: list
    gaps: list
    asked: int = 0

    @property
    def resident(self) -> bool:
        """Whether the prompt's cache is resident: a repeat is queued
        only after the previous ask, and so the prefill, completed."""
        return self.asked > 0


def replay_sessions(spec: ModelTrafficSpec, dep: ServingDeployment, *,
                    qps: float, n_ticks: int, arrival: str = "poisson",
                    seed: int = 0) -> SessionReplay:
    """Replay ``dep``'s sessions at ``qps`` asks a tick for ``n_ticks``.

    Sessions start at ``qps / dep.mean_asks`` a tick (``arrival``'s
    process, seeded by ``seed``).  A new session draws, from
    ``default_rng(seed + 1)`` in this order, its prompt length, its ask
    count, each ask's answer length, and each gap before a repeat.  A
    tick then:

    1. queues the repeats due this tick (by session), then the first
       asks of the sessions starting now;
    2. admits the queue in order into free slots (lowest first): a first
       ask starts its prefill, a repeat finds its prompt's cache resident
       and starts decoding at the prompt's length;
    3. prefills, in admission order, chunks of at most the remaining
       ``chunk_tokens`` budget; a slot whose prompt is done decodes this
       tick;
    4. decodes one token for every decoding slot (slot order); a slot
       whose answer is done frees, and its session's next ask is due
       ``1 + floor(gap)`` ticks later;
    5. reads the weights once, with the union of experts its tokens
       touch; the backlog is the queue plus the slots held.
    """
    n_ticks = int(n_ticks)
    arrivals = ARRIVALS[arrival](qps / dep.mean_asks, n_ticks, seed=seed)
    rng = np.random.default_rng(seed + 1)
    lo_asks, hi_asks = dep.asks_per_prompt
    sessions: list = []
    due: Dict[int, list] = {}
    queue: deque = deque()                 # session ids
    slots = dep.batch_slots
    sess = [-1] * slots                    # session of each held slot
    ctx = [0] * slots                      # cached tokens of the slot
    left = [0] * slots                     # decode tokens left
    prefilling: list = []                  # slots in prefill, in order
    kv = spec.kv_write_bytes_per_token
    token = (spec.state_bytes_per_token / 2.0
             + spec.moe_shuffle_bytes_per_token / 2.0)
    out = SessionReplay(np.zeros(n_ticks), np.zeros(n_ticks),
                        np.zeros(n_ticks))
    for t in range(n_ticks):
        queue.extend(sorted(due.pop(t, ())))
        for _ in range(int(arrivals[t])):
            prompt = dep.prompt.draw(rng)
            k = int(rng.integers(lo_asks, hi_asks + 1))
            answers = [dep.answer.draw(rng) for _ in range(k)]
            gaps = [float(rng.exponential(dep.ask_gap_ticks))
                    for _ in range(k - 1)]
            queue.append(len(sessions))
            sessions.append(_Session(prompt, answers, gaps))
        for slot in range(slots):
            if not queue:
                break
            if sess[slot] >= 0:
                continue
            sid = queue.popleft()
            session = sessions[sid]
            sess[slot], left[slot] = sid, session.answers[session.asked]
            if session.resident:
                ctx[slot] = session.prompt
                out.hits += 1
            else:
                ctx[slot] = 0
                prefilling.append(slot)
                out.misses += 1
        reads = writes = 0.0
        tokens, budget = 0, dep.chunk_tokens
        while prefilling and budget:
            slot = prefilling[0]
            prompt = sessions[sess[slot]].prompt
            c = min(prompt - ctx[slot], budget)
            r, w = spec.prefill_chunk_bytes(ctx[slot], c)
            reads, writes = reads + r, writes + w
            ctx[slot] += c
            budget -= c
            tokens += c
            out.prefill_chunks += 1
            if ctx[slot] == prompt:
                prefilling.pop(0)
        held = sum(1 for s in sess if s >= 0)
        decoding = [i for i in range(slots)
                    if sess[i] >= 0 and i not in prefilling]
        for slot in decoding:
            reads += ctx[slot] * kv + token
            writes += kv + token
            ctx[slot] += 1
            left[slot] -= 1
            if left[slot] == 0:
                session = sessions[sess[slot]]
                session.asked += 1
                if session.asked < len(session.answers):
                    at = t + 1 + int(session.gaps[session.asked - 1])
                    due.setdefault(at, []).append(sess[slot])
                sess[slot] = -1
        tokens += len(decoding)
        if tokens:
            reads += spec.tick_weight_bytes(tokens)
            if spec.expert_bytes:
                out.union_sum += spec.expert_union(tokens)
            out.busy_ticks += 1
        out.read_bytes[t] = reads
        out.write_bytes[t] = writes
        out.backlog[t] = len(queue) + held
    return out
