"""Model facade: family dispatch + dry-run input specs.

``build_model(cfg)`` returns a `Model` whose methods are pure functions over
param pytrees; ``model.input_specs(shape)`` returns ShapeDtypeStruct stand-ins
for every model input of that (arch x shape) cell — weak-type-correct,
shardable, no device allocation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import hybrid, hybrid_moe, ssm, transformer, whisper
from repro.models import layers as L


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    @property
    def _mod(self):
        return {
            "dense": transformer, "moe": transformer, "vlm": transformer,
            "ssm": ssm, "hybrid": hybrid, "hybrid_moe": hybrid_moe,
            "encdec": whisper,
        }[self.cfg.family]

    # ------------------------------------------------------------- params
    def init(self, key: jax.Array):
        return self._mod.init(self.cfg, key)

    def abstract_params(self, key: Optional[jax.Array] = None):
        """Shape-only params (dry-run: no allocation)."""
        key = key if key is not None else jax.random.key(0)
        return jax.eval_shape(self._mod.init, self.cfg, key)

    def param_specs(self, *, fsdp: bool = True):
        return self._mod.specs(self.cfg, fsdp=fsdp)

    # -------------------------------------------------------------- apply
    def forward(self, params, tokens, **kw):
        return self._mod.forward(self.cfg, params, tokens, **kw)

    def decode_step(self, params, cache, tokens, **kw):
        if self.cfg.family == "ssm":
            return ssm.decode_step(self.cfg, params, cache, tokens, **kw)
        return self._mod.decode_step(self.cfg, params, cache, tokens, **kw)

    def loss(self, params, tokens, labels, **kw):
        logits = self.forward(params, tokens, **kw)
        # VLM/audio prefixes carry no labels: score only the token positions.
        if logits.shape[1] != labels.shape[1]:
            logits = logits[:, logits.shape[1] - labels.shape[1]:]
        return L.cross_entropy(logits, labels, self.cfg.vocab_size)

    # -------------------------------------------------------------- cache
    def init_cache_shape(self, batch: int, max_len: int):
        if self.cfg.family == "ssm":
            return ssm.init_state_shape(self.cfg, batch)
        return self._mod.init_cache_shape(self.cfg, batch, max_len)

    def init_cache(self, batch: int, max_len: int):
        sh = self.init_cache_shape(batch, max_len)
        return {k: jnp.zeros(v.shape, v.dtype) for k, v in sh.items()}

    def cache_specs(self, *, batch_axes: Tuple[str, ...], seq_axes: Tuple[str, ...]):
        if self.cfg.family == "ssm":
            return ssm.state_specs(self.cfg, batch_axes=batch_axes)
        return self._mod.cache_specs(self.cfg, batch_axes=batch_axes, seq_axes=seq_axes)

    # ----------------------------------------------------------- dry-run
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for a (this arch x shape) cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        d = cfg.d_model
        tok = jnp.int32
        out: Dict[str, Any] = {}
        n_front = cfg.frontend.num_embeds
        if shape.kind in ("train", "prefill"):
            s_tok = s - n_front if cfg.frontend.kind == "vision_stub" else s
            out["tokens"] = jax.ShapeDtypeStruct((b, s_tok), tok)
            if cfg.frontend.kind == "vision_stub":
                out["embeds"] = jax.ShapeDtypeStruct((b, n_front, d), jnp.bfloat16)
            elif cfg.frontend.kind == "audio_stub":
                out["embeds"] = jax.ShapeDtypeStruct((b, n_front, d), jnp.bfloat16)
            if shape.kind == "train":
                out["labels"] = jax.ShapeDtypeStruct((b, s_tok), tok)
        else:  # decode
            out["tokens"] = jax.ShapeDtypeStruct((b,), tok)
            out["cache"] = self.init_cache_shape(b, s)
        return out

    def input_sharding_specs(self, shape: ShapeConfig, *,
                             batch_axes: Tuple[str, ...],
                             seq_axes: Tuple[str, ...] = ()) -> Dict[str, Any]:
        """PartitionSpecs matching ``input_specs`` leaves."""
        bt = batch_axes if batch_axes else None
        out: Dict[str, Any] = {}
        if shape.kind in ("train", "prefill"):
            out["tokens"] = P(bt, None)
            if self.cfg.frontend.kind in ("vision_stub", "audio_stub"):
                out["embeds"] = P(bt, None, None)
            if shape.kind == "train":
                out["labels"] = P(bt, None)
        else:
            out["tokens"] = P(bt)
            out["cache"] = self.cache_specs(batch_axes=batch_axes, seq_axes=seq_axes)
        return out


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
