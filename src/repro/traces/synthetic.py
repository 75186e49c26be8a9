"""Synthetic serving traces: the no-weights tier-1 fallback.

Replays a continuous-batching serving engine (fixed decode slots, FIFO
admission — the same lifecycle as ``repro.serve.ServingEngine``) as a
queueing simulation over a model's
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

The older models' replay (:func:`synthetic_serving_trace`) is a
numpy loop on the host.  With a
:class:`~repro.traces.deployment.ServingDeployment` the replay is
:func:`replay_sessions_batch`: sessions that ask one long prompt several
times (the repeats hit the resident cache), heavy-tailed lengths, and
chunked prefill under a per-tick token budget.  Its draws are made on
the host, its slot and queue dynamics run on the device as one integer
program for all of a query's traces (``jit_traces_replay_sessions``),
and its bytes are priced on the host in float64 from the program's
records.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
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


def _check_load(qps: float, arrival: str) -> None:
    """Refuse an unknown arrival process or a negative load."""
    if arrival not in ARRIVALS:
        raise ValueError(f"unknown arrival process {arrival!r}; choose "
                         f"from {sorted(ARRIVALS)}")
    if qps < 0:
        raise ValueError(f"qps must be >= 0, got {qps}")


def synthetic_serving_trace(spec: ModelTrafficSpec, *, qps: float,
                            n_ticks: int = 384, n_phases: int = 6,
                            batch_slots: int = 32, prompt_len: int = 512,
                            decode_len: int = 128,
                            arrival: str = "diurnal", seed: int = 0,
                            name: Optional[str] = None) -> TrafficTrace:
    """Generate a phase-compiled trace for ``spec`` under ``qps``
    requests per tick.

    The queueing replay admits arrivals into ``batch_slots`` decode
    slots (prompt/decode lengths jittered around ``prompt_len`` /
    ``decode_len``), prices every prefill and decode step through the
    spec's byte model, and records per-tick read/write bytes plus the
    outstanding-request backlog.  ``arrival`` picks the process:
    ``"poisson"`` (stationary), ``"diurnal"`` (day/night swing) or
    ``"bursty"`` (flash crowds).  A
    :class:`~repro.traces.deployment.ServingDeployment`'s sessions replay
    through :func:`replay_sessions_batch` instead.
    """
    _check_load(qps, arrival)
    label = name if name is not None else \
        f"{spec.name}@qps{qps:g}-{arrival}"
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
    """One session's draws: its prompt length, each ask's answer length,
    and the gap before each repeat."""

    prompt: int
    answers: list
    gaps: list

    @property
    def resident(self) -> bool:
        """Whether the session's repeats find the prompt's cache resident:
        a repeat is queued only after the previous ask, and so the
        prefill, completed, and the deployment keeps every prompt."""
        return True


def _draw_sessions(dep: ServingDeployment, qps: float, arrival: str,
                   n_ticks: int, seed: int):
    """The start tick and draws of every session of one trace, in order.

    Sessions start at ``qps / dep.mean_asks`` a tick (``arrival``'s
    process, seeded by ``seed``).  Each draws, from
    ``default_rng(seed + 1)`` in this order, its prompt length, its ask
    count, each ask's answer length, and each gap before a repeat; no
    draw depends on the replay's dynamics."""
    starts = ARRIVALS[arrival](qps / dep.mean_asks, n_ticks, seed=seed)
    rng = np.random.default_rng(seed + 1)
    lo_asks, hi_asks = dep.asks_per_prompt
    sessions = []
    for _ in range(int(starts.sum())):
        prompt = dep.prompt.draw(rng)
        k = int(rng.integers(lo_asks, hi_asks + 1))
        answers = dep.answer.draws(rng, k)
        gaps = rng.exponential(dep.ask_gap_ticks, k - 1).tolist()
        sessions.append(_Session(prompt, answers, gaps))
    return np.repeat(np.arange(n_ticks), starts), sessions


def _session_tables(draws, n_ticks: int, max_asks: int):
    """The traces' sessions as tables, the session axis padded to a
    power of two (so that seeds share programs): the tick each session's
    first ask is due, its prompt, whether its repeats find the prompt
    resident, and per ask ``[trace, ask, session]`` the answer and the
    delay from the ask's end to the next ask being due.  ``n_ticks``
    stands for "never": a padded session, and the ask after the last."""
    cap = 1 << max(max(len(s) for _, s in draws) - 1, 0).bit_length()
    shape = (len(draws), cap)
    due = np.full(shape, n_ticks, np.int32)
    prompt = np.zeros(shape, np.int32)
    resident = np.zeros(shape, bool)
    answers = np.zeros((len(draws), max_asks, cap), np.int32)
    delays = np.full((len(draws), max_asks, cap), n_ticks, np.int32)
    for i, (starts, sessions) in enumerate(draws):
        n = len(sessions)
        due[i, :n] = starts
        prompt[i, :n] = [s.prompt for s in sessions]
        resident[i, :n] = [s.resident for s in sessions]
        # session by session, ask by ask
        asks = np.asarray([len(s.answers) for s in sessions])[:, None]
        ask = np.arange(max_asks)
        answers[i, :, :n].T[ask < asks] = [a for s in sessions
                                            for a in s.answers]
        delays[i, :, :n].T[ask < asks - 1] = np.minimum(1 + np.floor(
            [g for s in sessions for g in s.gaps]), n_ticks)
    return due, prompt, resident, answers, delays


#: a prefill admission number no slot holds: the slot is not prefilling
_NOT_PREFILLING = np.iinfo(np.int32).max


def _replay_dynamics(due, prompt, resident, answers, delays, *,
                     n_ticks: int, slots: int, chunk_tokens: int):
    """One trace's slot and queue dynamics, a tick a step, in integers.

    The state: per session the tick its next ask is due, the asks it has
    completed and its place in the queue's order of arrival (``qpos``;
    queued while at or past the head ``qhead``); per slot the session it
    holds (-1: free), its cached tokens, the answer tokens left, its
    prefill admission number and its prompt's length.

    Returns, per slot and tick, the prefill chunks' offsets and tokens in
    the order they were processed (padded with zero-token chunks at offset
    0) and each slot's context before its decode step (-1 where it does
    not decode); the backlog per tick; and the replay's hits and misses.
    Every step is dense masks over ``[slots, sessions]`` and ``[slots,
    slots]``, with no scatter, sort or gather."""
    i32 = jnp.int32
    session_ids = jnp.arange(due.shape[0], dtype=i32)
    slot_ids = jnp.arange(slots, dtype=i32)
    ask_ids = jnp.arange(answers.shape[0], dtype=i32)[:, None]
    # the running count of due sessions, as a 0/1 product with a triangle
    # (exact: counts stay far below 2**24), takes the matrix unit one op
    # where a cumsum takes several
    upto = (session_ids[:, None] <= session_ids[None, :]).astype(jnp.float32)

    def take(pick, v):                  # v[s] of each slot's pick
        return jnp.sum(jnp.where(pick, v[None, :], 0), axis=1)

    def step(state, t):
        (due, asked, qpos, qhead, qtail, sid, ctx, left, seq, length,
         hits, misses) = state
        # 1. queue the asks due now in session order: the repeats, then
        # the sessions starting now (the newest, so the largest ids)
        now = due == t
        qpos = jnp.where(now, qtail + jnp.dot(now.astype(jnp.float32),
                                              upto).astype(i32) - 1, qpos)
        qtail = qtail + jnp.sum(now, dtype=i32)
        # 2. free slots, lowest first, take the queue's head in order; a
        # repeat whose prompt is resident decodes now (its prompt comes
        # negated), a first ask prefills in admission order
        free = sid < 0
        rank = jnp.cumsum(free, dtype=i32) - 1
        admit = free & (rank < qtail - qhead)
        pick = admit[:, None] & (qpos[None, :] == (qhead + rank)[:, None])
        this_ask = ask_ids == asked[None, :]
        signed = take(pick, jnp.where((asked > 0) & resident, -prompt,
                                      prompt))
        hit = signed < 0
        sid = jnp.where(admit, take(pick, session_ids), sid)
        length = jnp.where(admit, jnp.abs(signed), length)
        left = jnp.where(admit, take(pick, jnp.sum(
            jnp.where(this_ask, answers, 0), axis=0)), left)
        ctx = jnp.where(admit, jnp.where(hit, length, 0), ctx)
        miss = admit & ~hit
        seq = jnp.where(miss, t * slots + slot_ids, seq)
        qhead = qhead + jnp.sum(admit, dtype=i32)
        hits = hits + jnp.sum(admit & hit, dtype=i32)
        misses = misses + jnp.sum(miss, dtype=i32)
        # 3. prefill chunks in admission order under the tick's budget
        prefilling = seq != _NOT_PREFILLING
        need = jnp.where(prefilling, length - ctx, 0)
        ahead = prefilling[None, :] & (seq[None, :] < seq[:, None])
        chunk = jnp.clip(chunk_tokens - take(ahead, need), 0, need)
        record = ((jnp.sum(ahead, axis=1)[None, :] == slot_ids[:, None])
                  & (chunk > 0)[None, :])
        chunk_offset = take(record, ctx)
        chunk_tokens_ = take(record, chunk)
        ctx = ctx + chunk
        seq = jnp.where(prefilling & (ctx == length), _NOT_PREFILLING, seq)
        # 4. every held slot out of prefill decodes a token; a finished
        # ask frees its slot, and its session's next ask falls due
        held = sid >= 0
        decoding = held & (seq == _NOT_PREFILLING)
        decode_ctx = jnp.where(decoding, ctx, -1)
        ctx = ctx + decoding
        left = left - decoding
        done = decoding & (left == 0)
        ended = jnp.any(done[:, None]
                        & (sid[:, None] == session_ids[None, :]), axis=0)
        due = jnp.where(ended, t + jnp.sum(jnp.where(this_ask, delays, 0),
                                           axis=0), due)
        asked = asked + ended
        sid = jnp.where(done, -1, sid)
        backlog = qtail - qhead + jnp.sum(held, dtype=i32)
        return ((due, asked, qpos, qhead, qtail, sid, ctx, left, seq,
                 length, hits, misses),
                (chunk_offset, chunk_tokens_, decode_ctx, backlog))

    zero, per_slot = jnp.zeros((), i32), jnp.zeros(slots, i32)
    state = (due, jnp.zeros_like(due), jnp.full_like(due, -1), zero, zero,
             per_slot - 1, per_slot, per_slot,
             jnp.full(slots, _NOT_PREFILLING, i32), per_slot, zero, zero)
    state, (offset, chunk, ctx, backlog) = jax.lax.scan(
        step, state, jnp.arange(n_ticks, dtype=i32))
    # slot-major, so that the host reads each slot's ticks contiguously
    return (offset.T, chunk.T, ctx.T, backlog), state[-2], state[-1]


def _replay_program(n_traces: int, tables, *, n_ticks: int, slots: int,
                    chunk_tokens: int):
    """The compiled replay of ``n_traces`` traces in lockstep (one
    program, ``jit_traces_replay_sessions``, per table shape)."""
    from repro.core import space
    fn = functools.partial(_replay_dynamics, n_ticks=n_ticks, slots=slots,
                           chunk_tokens=chunk_tokens)
    key = (n_traces, tables[-1].shape[1:], n_ticks, slots, chunk_tokens)
    return space.cached_program("traces.replay", key, jax.vmap(fn),
                                tables, path="sessions")


def replay_sessions_batch(spec: ModelTrafficSpec, dep: ServingDeployment,
                          points: Sequence[Tuple[float, str]], *,
                          n_ticks: int, seed: int = 0
                          ) -> Tuple[List[SessionReplay], int]:
    """Replay ``dep``'s sessions at each ``(qps, arrival)`` of ``points``
    for ``n_ticks``, all with ``seed``; returns a :class:`SessionReplay`
    per point and the padded session axis the program ran.

    Sessions start at ``qps / dep.mean_asks`` a tick and draw their
    lengths, asks and gaps up front on the host (:func:`_draw_sessions`).
    The dynamics run on the device, every trace in lockstep in one
    program; a tick:

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

    The bytes are priced on the host in float64 from the program's
    integer records, added in the order above (chunks, then decoding
    slots, then weights)."""
    for qps, arrival in points:
        _check_load(qps, arrival)
    n_ticks = int(n_ticks)
    draws = [_draw_sessions(dep, qps, arrival, n_ticks, seed)
             for qps, arrival in points]
    tables = _session_tables(draws, n_ticks, dep.asks_per_prompt[1])
    program = _replay_program(len(points), tables, n_ticks=n_ticks,
                              slots=dep.batch_slots,
                              chunk_tokens=dep.chunk_tokens)
    records, hits, misses = program(*tables)
    offset, chunk, decode_ctx, backlog = (np.asarray(a) for a in records)
    # each tick's sums [trace, tick], term by term in the order the replay
    # adds them: its chunks, its decoding slots in slot order, its
    # weights; a masked term adds 0.0
    kv = spec.kv_write_bytes_per_token
    token = (spec.state_bytes_per_token / 2.0
             + spec.moe_shuffle_bytes_per_token / 2.0)
    reads, writes = np.zeros(backlog.shape), np.zeros(backlog.shape)
    tokens = np.zeros(backlog.shape, np.int64)
    for p in range(int((chunk > 0).sum(axis=1).max(initial=0))):
        r, w = spec.prefill_chunk_bytes(offset[:, p], chunk[:, p])
        reads, writes = reads + r, writes + w
        tokens += chunk[:, p]
    for i in range(dep.batch_slots):
        decoding = decode_ctx[:, i] >= 0
        reads = reads + np.where(decoding, decode_ctx[:, i] * kv + token,
                                 0.0)
        writes = writes + np.where(decoding, kv + token, 0.0)
        tokens += decoding
    # the weights are a function of the tick's tokens: one scalar call a
    # distinct count, 0.0 on idle ticks
    values = np.flatnonzero(np.bincount(tokens.ravel()))
    values = values[values > 0]
    weights = np.zeros(tokens.max(initial=0) + 1)
    weights[values] = [spec.tick_weight_bytes(int(v)) for v in values]
    reads = reads + weights[tokens]
    busy = tokens > 0
    union = np.zeros(weights.shape)
    if spec.expert_bytes:
        union[values] = [spec.expert_union(int(v)) for v in values]
    union = union[tokens]
    replays = [SessionReplay(
        reads[i], writes[i], backlog[i].astype(np.float64),
        prefill_chunks=int((chunk[i] > 0).sum()), hits=int(hits[i]),
        misses=int(misses[i]),
        union_sum=float(np.cumsum(union[i])[-1]) if n_ticks else 0.0,
        busy_ticks=int(busy[i].sum())) for i in range(len(points))]
    return replays, tables[0].shape[1]


def replay_sessions(spec: ModelTrafficSpec, dep: ServingDeployment, *,
                    qps: float, n_ticks: int, arrival: str = "poisson",
                    seed: int = 0) -> SessionReplay:
    """Replay ``dep``'s sessions at ``qps`` asks a tick for ``n_ticks``
    (:func:`replay_sessions_batch` of one trace)."""
    return replay_sessions_batch(spec, dep, [(qps, arrival)],
                                 n_ticks=n_ticks, seed=seed)[0][0]
