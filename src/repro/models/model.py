"""Public model API: build(cfg) -> Model with init / loss / prefill / decode
and per-shape abstract input specs (the dry-run's ShapeDtypeStruct source).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSpec
from repro.models import encdec as encdec_mod
from repro.models import schema as schema_mod
from repro.models import transformer as tf_mod
from repro.models.layers import COMPUTE_DTYPE
from repro.models.sharding import ShardingCtx


def cross_entropy(logits, labels, mask=None, z_loss: float = 1e-4):
    """Mean CE over valid tokens; fp32; optional z-loss regularizer."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- schema / params -----------------------------------------------------
    @property
    def schema(self):
        if self.cfg.is_encdec:
            return encdec_mod.encdec_schema(self.cfg)
        return tf_mod.model_schema(self.cfg)

    def init(self, key: jax.Array):
        return schema_mod.init_params(self.schema, key)

    def param_specs(self, ctx: ShardingCtx):
        return schema_mod.param_specs(self.schema, ctx)

    def param_shardings(self, ctx: ShardingCtx):
        return schema_mod.param_shardings(self.schema, ctx)

    def abstract_params(self):
        return schema_mod.abstract_params(self.schema)

    def param_count(self) -> int:
        return schema_mod.param_count(self.schema)

    # -- forwards --------------------------------------------------------------
    def _forward(self, params, inputs, ctx, *, mode, caches=None,
                 positions=None):
        if self.cfg.is_encdec:
            return encdec_mod.forward_encdec(
                params, inputs, self.cfg, ctx, mode=mode, caches=caches,
                positions=positions)
        return tf_mod.forward(params, inputs, self.cfg, ctx, mode=mode,
                              caches=caches, positions=positions)

    def loss(self, params, batch: Dict[str, Any], ctx: ShardingCtx):
        """-> (loss, metrics).  batch must contain 'labels' aligned with the
        token positions of the logits (frontends prepend unlabeled prefix)."""
        logits, _, aux = self._forward(params, batch, ctx, mode="train")
        labels = batch["labels"]
        if self.cfg.frontend == "vision" and "patch_embeds" in batch:
            # logits cover [patches; tokens] — score text positions only
            p = batch["patch_embeds"].shape[1]
            logits = logits[:, p:, :]
        # next-token prediction: shift
        ce = cross_entropy(logits[:, :-1, :], labels[:, 1:],
                           mask=(labels[:, 1:] >= 0))
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}

    def prefill(self, params, inputs: Dict[str, Any], ctx: ShardingCtx,
                pad_cache_to: Optional[int] = None):
        logits, caches, _ = self._forward(params, inputs, ctx, mode="prefill")
        if pad_cache_to is not None:
            caches = self.pad_caches(caches, pad_cache_to)
        return logits, caches

    def pad_caches(self, caches, target_len: int):
        """Extend attention KV caches' seq dim to target_len (for decode
        continuation after prefill).  Ring (local) caches and recurrent
        states are fixed-size and left untouched."""
        cfg = self.cfg

        def pad_kv(kv, axis):
            def _p(t):
                cur = t.shape[axis]
                if cur >= target_len:
                    return t
                pad = [(0, 0)] * t.ndim
                pad[axis] = (0, target_len - cur)
                return jnp.pad(t, pad)
            return jax.tree.map(_p, kv)

        if cfg.is_encdec:
            return jax.tree.map_with_path(
                lambda path, t: (pad_kv(t, 2)
                                 if any(getattr(p, "key", None) == "self"
                                        for p in path) else t),
                caches)
        if cfg.family == "ssm" or cfg.attention == "local":
            # pure-SSM states are seqlen-free; hybrids use ring + states
            if cfg.block_pattern:
                out = {}
                for name, c in caches.items():
                    out[name] = c          # rings/states fixed-size
                return out
            return caches
        axis = 2 if (cfg.scan_layers and cfg.homogeneous()) else 1
        return pad_kv(caches, axis)

    def decode_step(self, params, tokens, caches, positions,
                    ctx: ShardingCtx):
        """tokens [B,1] int32; positions [B,1] int32 (absolute)."""
        logits, new_caches, _ = self._forward(
            params, {"tokens": tokens}, ctx, mode="decode", caches=caches,
            positions=positions)
        return logits, new_caches

    # -- abstract inputs for the dry-run ---------------------------------------
    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        tok = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        emb = functools.partial(jax.ShapeDtypeStruct, dtype=COMPUTE_DTYPE)

        if shape.kind == "train":
            if cfg.is_encdec:
                return {"frames": emb((b, s, cfg.d_model)),
                        "tokens": tok((b, s)), "labels": tok((b, s))}
            if cfg.frontend == "vision":
                p = cfg.frontend_tokens
                return {"tokens": tok((b, s - p)),
                        "patch_embeds": emb((b, p, cfg.d_model)),
                        "labels": tok((b, s - p))}
            return {"tokens": tok((b, s)), "labels": tok((b, s))}

        if shape.kind == "prefill":
            if cfg.is_encdec:
                return {"frames": emb((b, s, cfg.d_model)),
                        "tokens": tok((b, s))}
            if cfg.frontend == "vision":
                p = cfg.frontend_tokens
                return {"tokens": tok((b, s - p)),
                        "patch_embeds": emb((b, p, cfg.d_model))}
            return {"tokens": tok((b, s))}

        # decode: one token against caches of length s
        caches = jax.eval_shape(
            lambda: self.init_decode_caches(b, s))
        return {"tokens": tok((b, 1)),
                "positions": tok((b, 1)),
                "caches": caches}

    def init_decode_caches(self, batch: int, max_len: int):
        if self.cfg.is_encdec:
            cfg = self.cfg
            kv = {"k": jnp.zeros((batch, max_len, cfg.num_kv_heads,
                                  cfg.head_dim), COMPUTE_DTYPE),
                  "v": jnp.zeros((batch, max_len, cfg.num_kv_heads,
                                  cfg.head_dim), COMPUTE_DTYPE)}
            per_layer = {"self": kv, "cross": jax.tree.map(jnp.copy, kv)}
            return jax.tree.map(
                lambda t: jnp.zeros((cfg.num_layers,) + t.shape, t.dtype),
                per_layer)
        return tf_mod.init_decode_caches(self.cfg, batch, max_len)

    # -- sharding for inputs ----------------------------------------------------
    def input_shardings(self, shape: ShapeSpec, ctx: ShardingCtx,
                        specs: Dict[str, Any]):
        """NamedShardings matching input_specs structure."""
        stacked = (self.cfg.is_encdec
                   or (self.cfg.scan_layers and self.cfg.homogeneous()))

        def shard_one(path_leaf):
            path, leaf = path_leaf
            nd = len(leaf.shape)
            name = path[0]
            if name == "caches":
                axes = ["layers"] if stacked else []
                rest = nd - len(axes)
                axes = axes + ["batch"] + [None] * (rest - 1)
                if rest == 4:
                    # attn KV caches [B, S, K, hd] (context-parallel decode:
                    # seq over TP) — also shards SSM state [B, H, P, N] on H
                    axes[-3] = "seq_kv"
                return ctx.sharding(tuple(axes), leaf.shape)
            axes = ["batch"] + [None] * (nd - 1)
            if name in ("patch_embeds", "frames"):
                axes = ["batch", None, "embed_act"]
            return ctx.sharding(tuple(axes), leaf.shape)

        flat, treedef = jax.tree.flatten_with_path(specs)
        out = []
        for path, leaf in flat:
            names = tuple(getattr(p, "key", getattr(p, "idx", None))
                          for p in path)
            out.append(shard_one((names, leaf)))
        return jax.tree.unflatten(treedef, out)


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
