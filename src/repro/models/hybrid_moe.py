"""Granite-4.0-H-style hybrid: a Mamba-2 or an attention mixer at each
layer, as ``cfg.layer_types`` lists them, every mixer followed by a
dropless routed MoE (``layers.held_moe``, over the experts this chip holds)
plus a shared SwiGLU expert. Granite scalars: the embedding times
``embedding_multiplier``, both residual adds of a layer times
``residual_multiplier``, the logits over ``logits_scaling``; attention
scaled by ``attention_multiplier``, without rotary positions when
``cfg.nope``.

Parameters, each group stacked over the layers of its kind:

  embed [Vpad, d], final_norm [d]           (the head is the tied embedding)
  mamba  Mamba-2 blocks (``ssm.init_block``)            [n_mamba, ...]
  attn   ln1, wq, wk, wv, wo                            [n_attn, ...]
  moe    ln2, router [d, E], e_wgu [Eh, d, 2f] (gate | up), e_wd [Eh, f, d],
         s_wg, s_wu [d, fs], s_wd [fs, d]               [num_layers, ...]

where E routes over every expert and Eh = ``moe.held`` are the ones held.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T

Params = Dict[str, Any]
MAMBA, ATTENTION = "mamba", "attention"


def _check(cfg: ModelConfig) -> None:
    kinds = cfg.kinds
    if len(kinds) != cfg.num_layers or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"{cfg.arch}: layer_types must give 'mamba' or "
                         f"'attention' for each of {cfg.num_layers} layers")


def init(cfg: ModelConfig, key: jax.Array) -> Params:
    _check(cfg)
    d, hd, m = cfg.d_model, cfg.resolved_head_dim, cfg.moe
    h, kv = cfg.num_heads, cfg.num_kv_heads
    n_attn = cfg.kinds.count(ATTENTION)
    nl, dt = cfg.num_layers, jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 16))
    res = 0.02 / math.sqrt(2 * nl)

    def nrm(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dt)

    return {
        "embed": nrm(L.pad_vocab(cfg.vocab_size), d),
        "final_norm": jnp.ones((d,), dt),
        "mamba": S.init_block(cfg, next(keys), cfg.kinds.count(MAMBA)),
        "attn": {"ln1": jnp.ones((n_attn, d), dt),
                 "wq": nrm(n_attn, d, h * hd), "wk": nrm(n_attn, d, kv * hd),
                 "wv": nrm(n_attn, d, kv * hd),
                 "wo": nrm(n_attn, h * hd, d, std=res)},
        "moe": {"ln2": jnp.ones((nl, d), dt),
                "router": nrm(nl, d, m.num_experts),
                "e_wgu": nrm(nl, m.held, d, 2 * m.d_expert),
                "e_wd": nrm(nl, m.held, m.d_expert, d, std=res),
                "s_wg": nrm(nl, d, m.d_shared), "s_wu": nrm(nl, d, m.d_shared),
                "s_wd": nrm(nl, m.d_shared, d, std=res)},
    }


def specs(cfg: ModelConfig, *, fsdp: bool = True) -> Params:
    """Layers replicate (a stage's layers live on its chip whole; the
    held experts are already this chip's share); the embedding shards its
    vocabulary over the TP axis like the other families'."""
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    out = jax.tree.map(lambda a: P(*([None] * a.ndim)), tree)
    out["embed"] = P("model", None)
    return out


def attn_proj(cfg: ModelConfig, lp: Params, x: jax.Array, rope=None):
    """Pre-norm q, k, v [B, C, heads, hd] of one attention mixer; rotary
    positions only when ``rope`` = (cos, sin) is given."""
    b, c, _ = x.shape
    hd = cfg.resolved_head_dim
    hn = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = (jnp.einsum("bcd,dq->bcq", hn, lp[w]) for w in ("wq", "wk", "wv"))
    q, k, v = (a.reshape(b, c, a.shape[-1] // hd, hd) for a in (q, k, v))
    if rope is not None:
        q, k = L.apply_rope(q, *rope), L.apply_rope(k, *rope)
    return q, k, v


def moe_block(cfg: ModelConfig, moe: Params, li: int, x: jax.Array):
    """x + residual_multiplier * (held routed experts + shared expert) of
    RMSNorm(x), for layer ``li`` (static) of the ``moe`` stack; the expert
    weights are read in place from the stack (``layers.held_moe``'s
    ``layer``). Returns (x, held (token, pick) pairs)."""
    m = cfg.moe
    lp = {k: a[li] for k, a in moe.items() if k not in ("e_wgu", "e_wd")}
    with jax.named_scope("layer.moe_route"):
        hn = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    routed, picks = L.held_moe(
        {"router": lp["router"], "e_wgu": moe["e_wgu"], "e_wd": moe["e_wd"]},
        hn, top_k=m.top_k, held_first=m.held_first, layer=li)
    with jax.named_scope("layer.moe_shared"):
        shared = L.swiglu({"wg": lp["s_wg"], "wu": lp["s_wu"],
                           "wd": lp["s_wd"]}, hn)
    with jax.named_scope("layer.moe_combine"):
        x = x + cfg.residual_multiplier * (routed + shared)
    return x, picks


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            embeds=None, topo=None, impl="xla_flash", remat=True,
            return_cache=False):
    """Full-sequence forward: logits [B, S, Vpad] (fp32). The layers run in
    pattern order, unrolled (kinds are static)."""
    assert embeds is None and not return_cache, \
        "hybrid_moe serves token prompts only (no frontend, no decode cache)"
    _check(cfg)
    x = T.embed_tokens(cfg, params, tokens, topo=topo)
    idx = {MAMBA: 0, ATTENTION: 0}
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    for li, kind in enumerate(cfg.kinds):
        if kind == MAMBA:
            x, _ = S.block_apply(cfg, at(params["mamba"], idx[kind]), x)
        else:
            x, _, _ = T.attn_block(cfg, at(params["attn"], idx[kind]), x,
                                   impl=impl, topo=topo)
        idx[kind] += 1
        x, _ = moe_block(cfg, params["moe"], li, x)
    return T.logits_head(cfg, params, x, topo=topo)
