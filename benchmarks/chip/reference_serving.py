"""Plain reference of the ``bridge.report`` cell's serving section, kept
apart from the program: it imports nothing of ``repro`` and reuses only
the protocol tables of :mod:`reference`.

* The byte model of DeepSeek-V3 on one chip of an expert-parallel
  deployment, written from the numbers of the configuration file
  (``benchmarks/chip/configs/bridge_deepseek_v3.json``): a latent cache of
  ``kv_lora_rank + qk_rope_head_dim`` values a token a layer, weights
  read whole each tick except the routed experts, the expected union of
  held experts a tick's global batch touches, and the tokens routed to
  the held experts.
* The session replay and its compilation into phases, in plain Python
  and numpy float64, from the same seeded draws in the same order as the
  traffic file describes.
* The two slot simulators of :mod:`reference`, run over a phase sequence
  with the queue and credit state carried from phase to phase: every
  phase runs the full horizon, phase 0 counts from a quarter of it and
  later phases count every cycle.
* Duration-weighted bandwidth on the PHY and the winning protocol of each
  trace.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference

#: a compiled phase's backlog floor (a drained engine still has one
#: request in flight)
MIN_BACKLOG = 1.0


# -- the byte model -----------------------------------------------------------


class ByteModel:
    """Per-token and per-tick bytes of one chip, from the configuration
    file's model numbers and deployment."""

    def __init__(self, cfg: Dict[str, Any]):
        dep = cfg["deployment"]
        d = cfg["hidden_size"]
        layers = cfg["num_hidden_layers"]
        dense = cfg["first_k_dense_replace"]
        moe = layers - dense
        heads = cfg["num_attention_heads"]
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        v_dim, q_rank = cfg["v_head_dim"], cfg["q_lora_rank"]
        kv_rank = cfg["kv_lora_rank"]
        experts = cfg["published"]["n_routed_experts"]
        held = cfg["n_routed_experts"]
        top_k = cfg["num_experts_per_tok"]
        ep = dep["expert_parallel"]
        wbytes, abytes = dep["weight_bytes"], 2
        # one latent-attention block: query down/up with its norm, the
        # joint KV down-projection with the shared rope key, its norm,
        # the per-head KV up-projection, the output projection
        attention = (d * q_rank + q_rank + q_rank * heads * (nope + rope)
                     + d * (kv_rank + rope) + kv_rank
                     + kv_rank * heads * (nope + v_dim)
                     + heads * v_dim * d)
        expert = 3 * d * cfg["moe_intermediate_size"]
        streamed = (layers * (attention + 2 * d)
                    + dense * 3 * d * cfg["intermediate_size"]
                    + moe * (cfg["n_shared_experts"] * expert
                             + d * experts)
                    + cfg["vocab_size"] * d + d)
        self.kv = float(layers * (kv_rank + rope) * abytes)
        self.shuffle = 2.0 * moe * d * (ep * top_k * held / experts) * abytes
        self.weights = float(streamed * wbytes)
        self.expert = float(expert * wbytes)
        self.moe_layers, self.held, self.ep = moe, held, ep
        self.miss = 1.0 - top_k / experts

    def union(self, tokens: int) -> float:
        """Expected held experts of one layer touched by a tick whose
        global batch is ``ep * tokens`` tokens, routed uniformly."""
        return self.held * (1.0 - self.miss ** (self.ep * tokens))

    def tick_weights(self, tokens: int) -> float:
        return self.weights + self.moe_layers * self.union(tokens) \
            * self.expert

    def decode(self, ctx: int) -> Tuple[float, float]:
        return ctx * self.kv + self.shuffle / 2.0, \
            self.kv + self.shuffle / 2.0

    def prefill(self, offset: int, tokens: int) -> Tuple[float, float]:
        return (offset + tokens) * self.kv + tokens * self.shuffle / 2.0, \
            tokens * (self.kv + self.shuffle / 2.0)


# -- lengths, arrivals, the service rate ---------------------------------------


def draw_length(rng: np.random.Generator, dist: Dict[str, float]) -> int:
    """A lognormal length truncated to [lo, hi] (redrawn until inside),
    rounded half up."""
    while True:
        x = rng.lognormal(math.log(dist["median"]), dist["sigma"])
        if dist["lo"] <= x <= dist["hi"]:
            return int(math.floor(x + 0.5))


def _survival(dist: Dict[str, float], t: float) -> float:
    z = lambda v: (math.log(v) - math.log(dist["median"])) / dist["sigma"]
    phi = lambda v: 0.5 * (1.0 + math.erf(z(v) / math.sqrt(2.0)))
    t = min(max(t, dist["lo"]), dist["hi"])
    return (phi(dist["hi"]) - phi(t)) / (phi(dist["hi"]) - phi(dist["lo"]))


def service_rate(dep: Dict[str, Any], sessions: Dict[str, Any]) -> float:
    """Slots over the mean ticks an ask holds one: its answer's decode
    ticks plus, for the first ask of a session, its prefill ticks but
    the one that also decodes."""
    ans, prm = sessions["answer"], sessions["prompt"]
    answer = ans["lo"] + sum(_survival(ans, n - 0.5)
                             for n in range(ans["lo"] + 1, ans["hi"] + 1))
    chunk = dep["chunk_tokens"]
    chunks = sum(_survival(prm, k * chunk + 0.5)
                 for k in range(math.ceil(prm["hi"] / chunk)))
    asks = sum(sessions["asks_per_prompt"]) / 2.0
    return dep["batch_slots"] / (answer + (chunks - 1.0) / asks)


def poisson(rate: float, n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).poisson(rate, n)


def bursty(rate: float, n: int, seed: int, factor: float = 8.0,
           enter: float = 0.05, mean_len: float = 16.0) -> np.ndarray:
    """Quiet at ``rate``, bursts at ``factor * rate``, entered with
    probability ``enter`` a tick and left with ``1 / mean_len``."""
    rng = np.random.default_rng(seed)
    rates, burst = [], False
    for _ in range(n):
        if burst:
            burst = not rng.random() < 1.0 / mean_len
        else:
            burst = rng.random() < enter
        rates.append(rate * (factor if burst else 1.0))
    return rng.poisson(np.asarray(rates))


ARRIVALS = {"poisson": poisson, "bursty": bursty}


# -- the replay ------------------------------------------------------------------


def replay(model: ByteModel, dep: Dict[str, Any], sessions: Dict[str, Any],
           qps: float, arrival: str, n_ticks: int, seed: int
           ) -> Dict[str, List[float]]:
    """Per-tick read bytes, write bytes and backlog of one trace."""
    asks_lo, asks_hi = sessions["asks_per_prompt"]
    starts = ARRIVALS[arrival](qps / ((asks_lo + asks_hi) / 2.0), n_ticks,
                               seed)
    rng = np.random.default_rng(seed + 1)
    n_slots = dep["batch_slots"]
    sess: List[Dict[str, Any]] = []
    due: Dict[int, List[int]] = collections.defaultdict(list)
    queue: collections.deque = collections.deque()
    slot = [None] * n_slots       # per slot: dict of its ask, or None
    order: List[int] = []         # slots in prefill, in admission order
    reads, writes, backlog = [], [], []
    for t in range(n_ticks):
        for s in sorted(due.pop(t, [])):
            queue.append(s)
        for _ in range(int(starts[t])):
            prompt = draw_length(rng, sessions["prompt"])
            k = int(rng.integers(asks_lo, asks_hi + 1))
            answers = [draw_length(rng, sessions["answer"])
                       for _ in range(k)]
            gaps = [rng.exponential(sessions["ask_gap_ticks"])
                    for _ in range(k - 1)]
            sess.append({"prompt": prompt, "answers": answers,
                         "gaps": gaps, "asked": 0})
            queue.append(len(sess) - 1)
        for i in range(n_slots):
            if slot[i] is None and queue:
                s = queue.popleft()
                first = sess[s]["asked"] == 0
                slot[i] = {"s": s, "left": sess[s]["answers"][
                    sess[s]["asked"]],
                    "ctx": 0 if first else sess[s]["prompt"]}
                if first:
                    order.append(i)
        r = w = 0.0
        tokens, budget = 0, dep["chunk_tokens"]
        while order and budget > 0:
            i = order[0]
            need = sess[slot[i]["s"]]["prompt"] - slot[i]["ctx"]
            c = min(need, budget)
            pr, pw = model.prefill(slot[i]["ctx"], c)
            r, w = r + pr, w + pw
            slot[i]["ctx"] += c
            budget -= c
            tokens += c
            if c == need:
                order.pop(0)
        held = sum(a is not None for a in slot)
        for i in range(n_slots):
            a = slot[i]
            if a is None or i in order:
                continue
            dr, dw = model.decode(a["ctx"])
            r, w = r + dr, w + dw
            a["ctx"] += 1
            a["left"] -= 1
            tokens += 1
            if a["left"] == 0:
                rec = sess[a["s"]]
                rec["asked"] += 1
                if rec["asked"] < len(rec["answers"]):
                    due[t + 1 + int(rec["gaps"][rec["asked"] - 1])].append(
                        a["s"])
                slot[i] = None
        if tokens:
            r += model.tick_weights(tokens)
        reads.append(r)
        writes.append(w)
        backlog.append(float(len(queue) + held))
    return {"read": reads, "write": writes, "backlog": backlog}


def phases(ticks: Dict[str, List[float]], n_phases: int
           ) -> Dict[str, List[float]]:
    """Equal slices of ticks: duration, byte-weighted read share (an idle
    slice takes the whole record's) and mean backlog (floored)."""
    r = np.asarray(ticks["read"], np.float64)
    w = np.asarray(ticks["write"], np.float64)
    b = np.asarray(ticks["backlog"], np.float64)
    whole = r.sum() / (r.sum() + w.sum())
    out = {"durations": [], "read_fractions": [], "backlogs": []}
    for rs, ws, bs in zip(np.array_split(r, n_phases),
                          np.array_split(w, n_phases),
                          np.array_split(b, n_phases)):
        tot = rs.sum() + ws.sum()
        out["durations"].append(float(len(rs)))
        out["read_fractions"].append(float(rs.sum() / tot) if tot > 0
                                     else float(whole))
        out["backlogs"].append(max(float(bs.mean()), MIN_BACKLOG))
    return out


# -- the slot simulators over phases ------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_flits", "dtype"))
def symmetric_trace(p, xs, ys, bls, *, n_flits: int, dtype=jnp.float32):
    """Per-phase efficiency ``[C, N]`` of symmetric cells ``p`` ``[C, 10]``
    over phase mixes and backlogs ``[C, N]``, state carried."""
    f = lambda a: jnp.asarray(a, dtype)
    (g, h, rph, sph, rpg, spg, dpl, fbits, cl, wbl) = (
        f(p[:, i]) for i in range(10))
    xs, ys, bls = f(xs), f(ys), f(bls)
    rdata_limit, wbuf_limit = cl * g, wbl * g
    hdr_cap, resp_cap = rph * h + rpg * g, sph * h + spg * g
    rpg_safe = jnp.maximum(rpg, f(1e-9))
    spg_safe = jnp.maximum(spg, f(1e-9))
    n_cells, n_phases = xs.shape

    def phase(n, carry):
        state, effs = carry
        x, y, backlog = xs[:, n], ys[:, n], bls[:, n]
        xr, yr = x / (x + y), y / (x + y)
        warm_from = jnp.where(n == 0, n_flits // 4, 0)

        def cycle(t, s):
            rq, wq, wdata, rdata, resp, cr, cw, delivered = s
            deficit = jnp.maximum(backlog - (rq + wq), f(0))
            cr = cr + deficit * xr
            cw = cw + deficit * yr
            new_r, new_w = jnp.floor(cr), jnp.floor(cw)
            cr, cw = cr - new_r, cw - new_w
            rq, wq = rq + new_r, wq + new_w
            rq_ok = jnp.minimum(rq, jnp.maximum(rdata_limit - rdata, f(0))
                                / dpl)
            wq_ok = jnp.minimum(wq, jnp.maximum(wbuf_limit - wdata, f(0))
                                / dpl)
            sent = jnp.minimum(rq_ok + wq_ok, hdr_cap)
            share = jnp.maximum(rq_ok + wq_ok, f(1e-9))
            sent_r, sent_w = sent * rq_ok / share, sent * wq_ok / share
            g_hdr = jnp.maximum(sent - rph * h, f(0)) / rpg_safe
            up = jnp.minimum(wdata, g - g_hdr)
            rq, wq = rq - sent_r, wq - sent_w
            wdata = wdata + sent_w * dpl - up
            rdata = rdata + sent_r * dpl
            resp = resp + sent_r + sent_w
            sent_resp = jnp.minimum(resp, resp_cap)
            g_resp = jnp.maximum(sent_resp - sph * h, f(0)) / spg_safe
            down = jnp.minimum(rdata, g - g_resp)
            resp, rdata = resp - sent_resp, rdata - down
            delivered = jnp.where(t >= warm_from, delivered + (up + down),
                                  delivered)
            return rq, wq, wdata, rdata, resp, cr, cw, delivered

        zero = jnp.zeros((n_cells,), dtype)
        s = jax.lax.fori_loop(0, n_flits, cycle, state + (zero,))
        warm = (n_flits - warm_from).astype(dtype)
        eff = (s[7] * f(128)) / (f(2) * warm * fbits)
        return s[:7], effs.at[:, n].set(eff)

    zero = jnp.zeros((n_cells,), dtype)
    _, effs = jax.lax.fori_loop(0, n_phases, phase,
                                ((zero,) * 7,
                                 jnp.zeros((n_cells, n_phases), dtype)))
    return effs


@functools.partial(jax.jit, static_argnames=("n_accesses", "dtype"))
def asymmetric_trace(p, xs, ys, *, n_accesses: int, dtype=jnp.float32):
    """Per-phase efficiency ``[C, N]`` of asymmetric cells: lane clocks
    and the read credit carry; a phase's efficiency is from its lane-time
    increment."""
    f = lambda a: jnp.asarray(a, dtype)
    total, rl, wl, cl, cbits, abits = (f(p[:, i]) for i in range(6))
    xs, ys = f(xs), f(ys)
    r_ui, w_ui, c_ui = abits / rl, abits / wl, cbits / cl
    n_cells, n_phases = xs.shape

    def phase(n, carry):
        state, before, effs = carry
        xr = xs[:, n] / (xs[:, n] + ys[:, n])

        def access(_, s):
            t_r, t_w, t_c, credit = s
            credit = credit + xr
            read = credit >= f(1)
            credit = jnp.where(read, credit - f(1), credit)
            t_r = t_r + jnp.where(read, r_ui, f(0))
            t_w = t_w + jnp.where(read, f(0), w_ui)
            return t_r, t_w, t_c + c_ui, credit

        state = jax.lax.fori_loop(0, n_accesses, access, state)
        busiest = jnp.maximum(jnp.maximum(state[0], state[1]), state[2])
        eff = f(512 * n_accesses) / (total * (busiest - before))
        return state, busiest, effs.at[:, n].set(eff)

    zero = jnp.zeros((n_cells,), dtype)
    _, _, effs = jax.lax.fori_loop(
        0, n_phases, phase,
        ((zero,) * 4, zero, jnp.zeros((n_cells, n_phases), dtype)))
    return effs


def trace_efficiency(protocols: Sequence[str], traces: Sequence[Dict],
                     *, n_flits: int, n_accesses: int, dtype=jnp.float32
                     ) -> np.ndarray:
    """Per-phase efficiency ``[protocol, trace, phase]`` (float32)."""
    rf = np.asarray([t["read_fractions"] for t in traces], np.float64)
    xs = (100.0 * rf).astype(np.float32)
    ys = (100.0 - xs).astype(np.float32)
    bls = np.asarray([t["backlogs"] for t in traces], np.float32)
    n_t = len(traces)
    out = []
    for key in protocols:
        if key in reference.SYMMETRIC:
            p = np.repeat(reference.sym_params([key], [{}]), n_t, axis=0)
            eff = symmetric_trace(p, xs, ys, bls, n_flits=n_flits,
                                  dtype=dtype)
        else:
            p = np.repeat(reference.asym_params([key], [{}]), n_t, axis=0)
            eff = asymmetric_trace(p, xs, ys, n_accesses=n_accesses,
                                   dtype=dtype)
        out.append(np.asarray(eff, np.float32))
    return np.stack(out)


def bandwidth(eff: np.ndarray, traces: Sequence[Dict], raw_gbs: float
              ) -> np.ndarray:
    """Duration-weighted bandwidth ``[protocol, trace]`` on a PHY of
    ``raw_gbs``."""
    d = np.asarray([t["durations"] for t in traces], np.float64)
    w = d / d.sum(axis=1, keepdims=True)
    return np.einsum("ptn,tn->pt", eff.astype(np.float64), w) * raw_gbs
