"""A serving deployment: one model on one chip, and the traffic it serves.

:class:`ServingDeployment` fixes what the session replay
(:func:`repro.traces.synthetic.replay_sessions`) needs beyond the byte
model: decode slots, the prefill chunk budget a tick, the length
distributions of prompts and answers, how often a prompt is asked again
and after how long, and the chip's share of an expert-parallel model.
Its :meth:`~ServingDeployment.service_rate` is the replay's nominal
rate, so load points can be given as multiples of it, and
:meth:`~ServingDeployment.record` is what a report writes down about the
traffic it answered.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class LengthDist:
    """A lognormal length (``median``, log-space ``sigma``) truncated to
    ``[lo, hi]`` tokens and rounded to a whole token."""

    median: float
    sigma: float
    lo: int
    hi: int

    def __post_init__(self):
        if not (self.median > 0 and self.sigma > 0 and 1 <= self.lo
                <= self.median <= self.hi):
            raise ValueError(f"bad length distribution {self}")

    def draw(self, rng: np.random.Generator) -> int:
        """One length: lognormal draws until one lies in ``[lo, hi]``."""
        mu = math.log(self.median)
        while True:
            x = rng.lognormal(mu, self.sigma)
            if self.lo <= x <= self.hi:
                return int(x + 0.5)

    def draws(self, rng: np.random.Generator, n: int) -> List[int]:
        """``n`` lengths, from the same lognormal draws of ``rng`` as
        ``n`` calls of :meth:`draw` (a round never draws past the last
        length it needs)."""
        mu = math.log(self.median)
        out: List[int] = []
        while len(out) < n:
            for x in rng.lognormal(mu, self.sigma, n - len(out)).tolist():
                if self.lo <= x <= self.hi:
                    out.append(int(x + 0.5))
        return out

    def survival(self, t: float) -> float:
        """``P(X >= t)`` of the truncated (unrounded) length."""
        cdf = lambda v: 0.5 * math.erfc(
            -(math.log(v) - math.log(self.median))
            / (self.sigma * math.sqrt(2.0)))
        lo, hi = cdf(self.lo), cdf(self.hi)
        t = min(max(t, self.lo), self.hi)
        return (hi - cdf(t)) / (hi - lo)

    def mean(self) -> float:
        """Expected whole-token length."""
        return self.lo + sum(self.survival(n - 0.5)
                             for n in range(self.lo + 1, self.hi + 1))

    def mean_chunks(self, chunk: int) -> float:
        """Expected ``ceil(length / chunk)``."""
        top = -(-self.hi // chunk)
        return sum(self.survival(k * chunk + 0.5) for k in range(top))


@dataclasses.dataclass(frozen=True)
class ServingDeployment:
    """One chip of a serving deployment of ``model`` (a traffic id of
    :func:`repro.configs.registry.traffic_config`).

    Sessions start as an arrival process; each asks its prompt
    ``asks_per_prompt`` times (drawn uniformly from the inclusive range),
    every next ask an exponential gap of mean ``ask_gap_ticks`` after the
    previous one ends.  The first ask prefills the prompt in chunks of at
    most ``chunk_tokens`` tokens a tick (the budget is shared by every
    slot in prefill); the repeats find its latent cache resident, write
    nothing and skip prefill.  ``expert_parallel`` chips split the routed
    experts and are as loaded as this one; weights take ``weight_bytes``
    a parameter."""

    model: str
    batch_slots: int = 16
    chunk_tokens: int = 8192
    prompt: LengthDist = LengthDist(32768.0, 0.6, 16384, 131072)
    answer: LengthDist = LengthDist(256.0, 0.5, 64, 1024)
    asks_per_prompt: Tuple[int, int] = (3, 5)
    ask_gap_ticks: float = 256.0
    expert_parallel: int = 32
    weight_bytes: int = 1

    def __post_init__(self):
        lo, hi = self.asks_per_prompt
        if not 1 <= lo <= hi:
            raise ValueError(f"asks_per_prompt {self.asks_per_prompt}")
        if self.batch_slots < 1 or self.chunk_tokens < 1:
            raise ValueError("batch_slots and chunk_tokens must be >= 1")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ServingDeployment":
        """From a JSON record (length distributions as dicts)."""
        kw = dict(d)
        for key in ("prompt", "answer"):
            if isinstance(kw.get(key), dict):
                kw[key] = LengthDist(**kw[key])
        if "asks_per_prompt" in kw:
            kw["asks_per_prompt"] = tuple(kw["asks_per_prompt"])
        return cls(**kw)

    @property
    def mean_asks(self) -> float:
        return 0.5 * (self.asks_per_prompt[0] + self.asks_per_prompt[1])

    def spec(self):
        """The model's byte model as this chip's share."""
        from repro.traces.model_traffic import ModelTrafficSpec
        return ModelTrafficSpec.from_name(
            self.model, expert_parallel=self.expert_parallel,
            weight_bytes=self.weight_bytes)

    def mean_lifetime(self) -> float:
        """Expected ticks an ask holds a slot, prefilled alone: its
        answer's decode ticks, plus for a first ask (one in
        ``mean_asks``) the prefill ticks before the one that also
        decodes its first token."""
        prefill = self.prompt.mean_chunks(self.chunk_tokens) - 1.0
        return self.answer.mean() + prefill / self.mean_asks

    def service_rate(self) -> float:
        """Asks a tick the slots complete: ``batch_slots`` over the mean
        lifetime."""
        return self.batch_slots / self.mean_lifetime()

    def record(self) -> Dict[str, Any]:
        """What a report writes down about the traffic it answered."""
        d = dataclasses.asdict(self)
        d["asks_per_prompt"] = list(self.asks_per_prompt)
        d["held_experts"] = self.spec().held_experts
        d["service_rate"] = self.service_rate()
        return d
