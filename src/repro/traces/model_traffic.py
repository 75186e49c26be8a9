"""First-order memory-traffic model of one decode/prefill step.

:class:`ModelTrafficSpec` reduces a :class:`repro.configs.ModelConfig` to
the per-token byte flows the serving recorder and the synthetic trace
generator both price:

* KV cache — attention (and MoE-attention) layers write
  ``2 * kv_heads * head_dim`` values per token and read the whole
  per-sequence cache back every decode step (reads grow with context).
* Recurrent state — SSM / recurrent layers read + write a
  context-independent state per token instead.
* MoE expert shuffle — dispatch + combine move each token's activations
  to/from its routed experts (``2 * d_model * experts_per_token``),
  priced half read / half write.
* Weight streaming — active parameters are read once per engine tick
  (amortized across the decode batch), the dominant read flow at small
  batch.

The numbers are first-order by design: the trace axis only consumes the
per-phase *read fraction* and *backlog* these flows imply, not absolute
bandwidth, so layout/replication constants cancel.

Configurations that declare a routed-expert width (``moe_d_ff``) or a
latent cache (``kv_lora_rank``) are priced as ONE chip of an
expert-parallel deployment (:meth:`ModelTrafficSpec.from_config` with
``expert_parallel`` chips and ``weight_bytes`` a parameter):

* Latent cache — an MLA layer caches ``kv_lora_rank + qk_rope_head_dim``
  values a token (not ``2 * kv_heads * head_dim``).
* Weights read whole every tick — attention, dense layers, shared
  experts, routers and the output head (the embedding table is only
  gathered, so it is not streamed).
* The union of experts a tick touches — the chip holds
  ``num_experts / expert_parallel`` routed experts of each MoE layer; a
  held expert is read in a tick if any token of the global batch
  (``expert_parallel`` chips as loaded as this one) routes to it: under
  uniform top-k routing ``held * (1 - (1 - k / E) ** G)`` of them.
* Expert shuffle — the tokens routed to the held experts,
  ``expert_parallel * k * held / E`` a local token in expectation.

Other configurations price exactly as before.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelTrafficSpec:
    """Per-token byte costs of a model, derived from its config shapes."""

    name: str
    dtype_bytes: int = 2
    #: KV bytes written per generated/prefilled token (all attn layers)
    kv_write_bytes_per_token: float = 0.0
    #: recurrent-state bytes read AND written per token (SSM/rec layers)
    state_bytes_per_token: float = 0.0
    #: MoE dispatch+combine bytes per token (half read, half write)
    moe_shuffle_bytes_per_token: float = 0.0
    #: weights streamed (read) once per engine tick: all active
    #: parameters, or with ``expert_bytes`` everything but the routed
    #: experts
    weight_stream_bytes: float = 0.0
    #: bytes of one routed expert of one layer (0: no expert union)
    expert_bytes: float = 0.0
    moe_layers: int = 0
    #: routed experts of each MoE layer held on this chip
    held_experts: int = 0
    num_experts: int = 0
    experts_per_token: int = 0
    #: chips the routed experts are spread over, each as loaded as this
    expert_parallel: int = 1

    @classmethod
    def from_config(cls, cfg, *, expert_parallel: int = 1,
                    weight_bytes: int = 2) -> "ModelTrafficSpec":
        """Price a :class:`repro.configs.ModelConfig` (full or reduced).

        A configuration with a routed-expert width or a latent cache is
        priced as one of ``expert_parallel`` chips that split its routed
        experts, with ``weight_bytes`` a parameter; any other prices as
        before and takes neither."""
        if cfg.moe_d_ff or cfg.is_mla:
            return cls._deployment_share(cfg, expert_parallel,
                                         weight_bytes)
        dtype_bytes = 2
        kinds = list(cfg.layer_kinds())
        n_attn = sum(1 for k in kinds if k in ("attn", "moe"))
        n_moe = sum(1 for k in kinds if k == "moe")
        n_ssm = sum(1 for k in kinds if k == "ssm")
        n_rec = sum(1 for k in kinds if k == "rec")
        kv = n_attn * cache_values_per_token(cfg) * dtype_bytes
        state = 0.0
        if n_ssm:
            state += n_ssm * 2.0 * cfg.d_inner * cfg.ssm_state * dtype_bytes
        if n_rec:
            state += n_rec * 2.0 * cfg.d_model * dtype_bytes
        moe = (2.0 * n_moe * cfg.d_model * cfg.experts_per_token
               * dtype_bytes) if n_moe else 0.0
        return cls(name=cfg.name, dtype_bytes=dtype_bytes,
                   kv_write_bytes_per_token=float(kv),
                   state_bytes_per_token=float(state),
                   moe_shuffle_bytes_per_token=float(moe),
                   weight_stream_bytes=float(cfg.active_param_count()
                                             * dtype_bytes))

    @classmethod
    def _deployment_share(cls, cfg, expert_parallel: int,
                          weight_bytes: int) -> "ModelTrafficSpec":
        """One chip's share of an expert-parallel deployment (module
        docstring)."""
        act_bytes = 2
        kinds = cfg.layer_kinds()
        n_attn = sum(1 for k in kinds if k in ("attn", "moe"))
        n_moe = sum(1 for k in kinds if k == "moe")
        e, k, ep = cfg.num_experts, cfg.experts_per_token, expert_parallel
        if n_moe and (ep < 1 or e % ep):
            raise ValueError(f"{cfg.name}: {e} routed experts do not "
                             f"split over {ep} chips")
        held = e // ep if n_moe else 0
        expert_p = cfg.d_model * cfg.expert_d_ff * (
            3 if cfg.mlp_gated else 2)
        embedding = 0 if cfg.tie_embeddings else \
            cfg.vocab_size * cfg.d_model
        streamed = cfg.param_count() - embedding - n_moe * e * expert_p
        routed_in = ep * k * held / e if n_moe else 0.0
        return cls(name=cfg.name, dtype_bytes=act_bytes,
                   kv_write_bytes_per_token=float(
                       n_attn * cache_values_per_token(cfg) * act_bytes),
                   moe_shuffle_bytes_per_token=(
                       2.0 * n_moe * cfg.d_model * routed_in * act_bytes),
                   weight_stream_bytes=float(streamed * weight_bytes),
                   expert_bytes=float(expert_p * weight_bytes),
                   moe_layers=n_moe, held_experts=held, num_experts=e,
                   experts_per_token=k, expert_parallel=ep)

    @classmethod
    def from_name(cls, arch_id: str, **share) -> "ModelTrafficSpec":
        """Price a registered architecture or traffic-only configuration
        by id — config shapes only, no model weights (the tier-1
        synthetic-trace path); ``share`` as for :meth:`from_config`."""
        from repro.configs.registry import traffic_config
        return cls.from_config(traffic_config(arch_id), **share)

    # -- per-tick weight reads --------------------------------------------

    def expert_union(self, tokens: int) -> float:
        """Expected held experts of one MoE layer that a tick of
        ``tokens`` local tokens touches, under uniform top-k routing of
        the global batch (``expert_parallel * tokens`` tokens)."""
        miss = 1.0 - self.experts_per_token / self.num_experts
        return self.held_experts * (
            1.0 - miss ** (self.expert_parallel * int(tokens)))

    def tick_weight_bytes(self, tokens: int) -> float:
        """Weight bytes read in a tick that processes ``tokens`` local
        tokens: the streamed weights, plus the held experts the tick
        touches where the spec prices the expert union."""
        if not self.expert_bytes:
            return self.weight_stream_bytes
        return (self.weight_stream_bytes + self.moe_layers
                * self.expert_union(tokens) * self.expert_bytes)

    # -- per-event byte flows (read_bytes, write_bytes) -------------------

    def decode_bytes(self, context_len: int) -> Tuple[float, float]:
        """One decode step of one sequence at ``context_len``: read the
        KV cache back, write one token's KV, cycle the recurrent state,
        shuffle the token through its experts."""
        ctx = max(int(context_len), 0)
        reads = (ctx * self.kv_write_bytes_per_token
                 + self.state_bytes_per_token / 2.0
                 + self.moe_shuffle_bytes_per_token / 2.0)
        writes = (self.kv_write_bytes_per_token
                  + self.state_bytes_per_token / 2.0
                  + self.moe_shuffle_bytes_per_token / 2.0)
        return reads, writes

    def prefill_chunk_bytes(self, offset: int, tokens: int
                            ) -> Tuple[float, float]:
        """One chunk of ``tokens`` prompt tokens at ``offset``: write
        their cache entries, read the cached prefix and the chunk back
        once (flash-style), and shuffle the chunk through the experts.
        ``offset`` and ``tokens`` may be integer arrays of chunks."""
        n = np.maximum(tokens, 0)
        per_token = (self.state_bytes_per_token / 2.0
                     + self.moe_shuffle_bytes_per_token / 2.0)
        reads = ((np.maximum(offset, 0) + n) * self.kv_write_bytes_per_token
                 + n * per_token)
        writes = n * (self.kv_write_bytes_per_token + per_token)
        return reads, writes

    def prefill_bytes(self, prompt_len: int) -> Tuple[float, float]:
        """One prompt prefill: fill ``prompt_len`` tokens of KV (the
        write burst the decode stream never shows), read each filled
        entry back once (causal attention over the prompt, flash-style
        single pass), and shuffle every prompt token through the
        experts."""
        n = max(int(prompt_len), 0)
        reads = n * (self.kv_write_bytes_per_token
                     + self.state_bytes_per_token / 2.0
                     + self.moe_shuffle_bytes_per_token / 2.0)
        writes = n * (self.kv_write_bytes_per_token
                      + self.state_bytes_per_token / 2.0
                      + self.moe_shuffle_bytes_per_token / 2.0)
        return reads, writes


def cache_values_per_token(cfg) -> int:
    """Cached values a token adds to one attention layer: the latent and
    the shared rope key of an MLA layer, a key and a value per KV head
    otherwise."""
    if cfg.is_mla:
        return cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return 2 * cfg.num_kv_heads * cfg.head_dim
