"""Unified decoder-only transformer: dense (GQA, qk_norm, granite scalars),
MoE, and VLM (embedding splice). Functional: ``init`` builds a stacked-layer
param pytree, ``specs`` builds a matching PartitionSpec pytree, apply fns are
pure and scan over layers.

KV cache layout: dict(k=[L,B,S,K,Dh], v=[L,B,S,K,Dh], pos=[B]).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.topology import Topology

Params = Dict[str, Any]


@dataclass(frozen=True)
class ManualTPApply:
    """Manual-TP hooks for the block fns (DESIGN.md §3.6): which param
    groups arrive SHARDED (so the matching contraction needs ``reduce`` — a
    psum over the manual TP axes, supplied by the caller as a transport
    closure) and, for manual expert parallelism, the mesh axes to derive the
    local expert range from. ``None`` (the default everywhere) is the plain
    single-device / GSPMD path, bit-identical to before."""
    reduce: Callable[[jax.Array], jax.Array]
    attn: bool = False        # wq/wk/wv/wo head-sharded -> psum after wo
    dense: bool = False       # wg/wu/wd f-sharded -> psum after wd
    moe: bool = False         # expert output partial (f- or expert-sharded)
    shared: bool = False      # shared-experts s_w* f-sharded
    ep_axes: Optional[Tuple[str, ...]] = None  # manual EP: slice my experts


def manual_tp_apply(mtp, reduce: Callable[[jax.Array], jax.Array]
                    ) -> ManualTPApply:
    """The ONE mapping from a ``staging.ManualTP`` plan (duck-typed — this
    layer sits below core) to the block-fn hooks; both drivers (stage
    programs and gpipe) build through here so the flag semantics cannot
    drift between them."""
    return ManualTPApply(
        reduce=reduce, attn=mtp.attn, dense=mtp.ffn,
        moe=(mtp.moe_ffn or mtp.moe_ep), shared=mtp.shared_moe,
        ep_axes=(mtp.axes if mtp.moe_ep else None))


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------------------- init

def init(cfg: ModelConfig, key: jax.Array) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    nl = cfg.num_layers
    vpad = L.pad_vocab(cfg.vocab_size)
    dt = _dtype(cfg)
    keys = iter(jax.random.split(key, 32))

    def nrm(k, *shape, std=None):
        std = std if std is not None else 0.02
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    lp: Params = {
        "ln1": jnp.ones((nl, d), dt),
        "ln2": jnp.ones((nl, d), dt),
        "wq": nrm(next(keys), nl, d, h * hd),
        "wk": nrm(next(keys), nl, d, kv * hd),
        "wv": nrm(next(keys), nl, d, kv * hd),
        "wo": nrm(next(keys), nl, h * hd, d, std=0.02 / math.sqrt(2 * nl)),
    }
    if cfg.qk_norm:
        lp["q_norm"] = jnp.ones((nl, hd), dt)
        lp["k_norm"] = jnp.ones((nl, hd), dt)
    if cfg.moe is None:
        lp["wg"] = nrm(next(keys), nl, d, cfg.d_ff)
        lp["wu"] = nrm(next(keys), nl, d, cfg.d_ff)
        lp["wd"] = nrm(next(keys), nl, cfg.d_ff, d, std=0.02 / math.sqrt(2 * nl))
    else:
        m = cfg.moe
        fe = m.d_expert or cfg.d_ff
        lp["router"] = nrm(next(keys), nl, d, m.num_experts)
        lp["e_wg"] = nrm(next(keys), nl, m.num_experts, d, fe)
        lp["e_wu"] = nrm(next(keys), nl, m.num_experts, d, fe)
        lp["e_wd"] = nrm(next(keys), nl, m.num_experts, fe, d, std=0.02 / math.sqrt(2 * nl))
        if m.num_shared_experts:
            fs = fe * m.num_shared_experts
            lp["s_wg"] = nrm(next(keys), nl, d, fs)
            lp["s_wu"] = nrm(next(keys), nl, d, fs)
            lp["s_wd"] = nrm(next(keys), nl, fs, d, std=0.02 / math.sqrt(2 * nl))
    params: Params = {
        "embed": nrm(next(keys), vpad, d),
        "final_norm": jnp.ones((d,), dt),
        "layers": lp,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = nrm(next(keys), d, vpad)
    return params


def specs(cfg: ModelConfig, *, fsdp: bool = True) -> Params:
    """PartitionSpec tree matching ``init``. TP axis: "model"; FSDP: "data"."""
    FD = "data" if fsdp else None
    MD = "model"
    lp: Params = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wq": P(None, FD, MD),
        "wk": P(None, FD, MD),
        "wv": P(None, FD, MD),
        "wo": P(None, MD, FD),
    }
    if cfg.qk_norm:
        lp["q_norm"] = P(None, None)
        lp["k_norm"] = P(None, None)
    if cfg.moe is None:
        lp["wg"] = P(None, FD, MD)
        lp["wu"] = P(None, FD, MD)
        lp["wd"] = P(None, MD, FD)
    else:
        lp["router"] = P(None, FD, None)
        lp["e_wg"] = P(None, None, FD, MD)
        lp["e_wu"] = P(None, None, FD, MD)
        lp["e_wd"] = P(None, None, MD, FD)
        if cfg.moe.num_shared_experts:
            lp["s_wg"] = P(None, FD, MD)
            lp["s_wu"] = P(None, FD, MD)
            lp["s_wd"] = P(None, MD, FD)
    out: Params = {
        "embed": P(MD, None),
        "final_norm": P(None),
        "layers": lp,
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = P(None, MD)
    return out


# ------------------------------------------------------------------ blocks

def attn_block(cfg: ModelConfig, lp: Params, x: jax.Array, *,
               k_cache=None, v_cache=None, positions=None,
               causal_offset=0, impl="xla_flash",
               topo: Optional[Topology] = None,
               tp: Optional[ManualTPApply] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-norm attention block. Returns (residual_out, k, v) where k/v are the
    NEW keys/values of these positions (for caching). ``k_cache``/``v_cache``,
    when given, are prepended (chunked prefill against a prefix). Head counts
    come from the param shapes, so under the manual TP lowering (``tp``)
    this computes the LOCAL heads and psums the o-projection."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    hn = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dq->bsq", hn, lp["wq"])
    k = jnp.einsum("bsd,dq->bsq", hn, lp["wk"])
    v = jnp.einsum("bsd,dq->bsq", hn, lp["wv"])
    q = q.reshape(b, s, q.shape[-1] // hd, hd)
    k = k.reshape(b, s, k.shape[-1] // hd, hd)
    v = v.reshape(b, s, v.shape[-1] // hd, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if not cfg.nope:
        if positions is None:
            positions = jnp.arange(s)[None, :] + (
                0 if causal_offset is None else causal_offset)
        cos, sin = L.rope_angles(positions, hd, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    if topo is not None and cfg.num_heads % topo.tp_size == 0:
        q = jax.lax.with_sharding_constraint(
            q, topo.sharding(topo.batch_axes, None, topo.tp_axis, None))
    k_all = k if k_cache is None else jnp.concatenate([k_cache, k], axis=1)
    v_all = v if v_cache is None else jnp.concatenate([v_cache, v], axis=1)
    scale = cfg.attention_multiplier or None
    off = None if causal_offset is None else (
        causal_offset if k_cache is None else k_cache.shape[1])
    att = L.attention(q, k_all, v_all, causal_offset=off, scale=scale, impl=impl)
    h_loc = att.shape[2]
    out = jnp.einsum("bsq,qd->bsd", att.reshape(b, s, h_loc * hd), lp["wo"])
    if tp is not None and tp.attn:
        out = tp.reduce(out)
    return x + cfg.residual_multiplier * out, k, v


def ffn_block(cfg: ModelConfig, lp: Params, x: jax.Array, *,
              topo: Optional[Topology] = None, ep_axis=None,
              tp: Optional[ManualTPApply] = None) -> jax.Array:
    """FFN / MoE block. Under manual TP (``tp``) the SHARDED parts (per the
    flags) are summed and reduced with ONE psum; unsharded parts add after
    the reduce so replication is never double-counted."""
    hn = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    partial, replicated = None, None

    def acc(out, sharded):
        nonlocal partial, replicated
        if tp is not None and sharded:
            partial = out if partial is None else partial + out
        else:
            replicated = out if replicated is None else replicated + out

    if cfg.moe is None:
        acc(L.swiglu({"wg": lp["wg"], "wu": lp["wu"], "wd": lp["wd"]}, hn),
            tp is not None and tp.dense)
    else:
        m = cfg.moe
        acc(L.moe_layer(
            {"router": lp["router"], "wg": lp["e_wg"], "wu": lp["e_wu"],
             "wd": lp["e_wd"]},
            hn, num_experts=m.num_experts, top_k=m.top_k,
            capacity_factor=m.capacity_factor, topo=topo,
            num_real=m.real_experts, ep_axis=ep_axis,
            ep_axes=tp.ep_axes if tp is not None else None),
            tp is not None and tp.moe)
        if m.num_shared_experts:
            acc(L.swiglu({"wg": lp["s_wg"], "wu": lp["s_wu"],
                          "wd": lp["s_wd"]}, hn),
                tp is not None and tp.shared)
    out = replicated
    if partial is not None:
        out = tp.reduce(partial) if out is None else tp.reduce(partial) + out
    assert out is not None, "ffn_block produced no parts"
    return x + cfg.residual_multiplier * out


def layer_apply(cfg: ModelConfig, lp: Params, x: jax.Array, *,
                k_cache=None, v_cache=None, positions=None, causal_offset=0,
                impl="xla_flash", topo=None, tp=None):
    x, k, v = attn_block(cfg, lp, x, k_cache=k_cache, v_cache=v_cache,
                         positions=positions, causal_offset=causal_offset,
                         impl=impl, topo=topo, tp=tp)
    x = ffn_block(cfg, lp, x, topo=topo, tp=tp)
    return x, k, v


# ----------------------------------------------------------------- forward

def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
                 embeds: Optional[jax.Array] = None, topo=None) -> jax.Array:
    """tokens [B,St]; embeds [B,Si,d] (VLM/audio stub) spliced in FRONT."""
    x = L.embed_lookup(params["embed"], tokens, topo=topo)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def logits_head(cfg: ModelConfig, params: Params, x: jax.Array, *, topo=None):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return L.unembed_logits(x, w, topo=topo, scale=cfg.logits_scaling)


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            embeds=None, topo=None, impl="xla_flash", remat=True,
            return_cache=False):
    """Full-sequence forward (training / baseline prefill).
    Returns logits [B,S,Vpad] (fp32, vocab-sharded); with ``return_cache``
    also returns dict(k=[L,B,S,K,Dh], v=..., pos=[B])."""
    x = embed_tokens(cfg, params, tokens, embeds=embeds, topo=topo)

    def body(xc, lp):
        xo, k, v = layer_apply(cfg, lp, xc, impl=impl, topo=topo)
        if topo is not None:
            xo = jax.lax.with_sharding_constraint(
                xo, topo.sharding(topo.batch_axes, None, None))
        return xo, (k, v) if return_cache else None

    f = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) if remat else body
    x, kvs = jax.lax.scan(f, x, params["layers"])
    logits = logits_head(cfg, params, x, topo=topo)
    if return_cache:
        pos = jnp.full((tokens.shape[0],), x.shape[1], jnp.int32)
        return logits, {"k": kvs[0], "v": kvs[1], "pos": pos}
    return logits


# ------------------------------------------------------------------ decode

def init_cache_shape(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStructs for the KV cache (dry-run) + sharding spec builder."""
    hd = cfg.resolved_head_dim
    dt = _dtype(cfg)
    return {
        "k": jax.ShapeDtypeStruct((cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd), dt),
        "v": jax.ShapeDtypeStruct((cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd), dt),
        "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, *, batch_axes, seq_axes) -> Dict[str, P]:
    kvspec = P(None, batch_axes if batch_axes else None, seq_axes if seq_axes else None, None, None)
    return {"k": kvspec, "v": kvspec, "pos": P(batch_axes if batch_axes else None)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    sh = init_cache_shape(cfg, batch, max_len)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in sh.items()}


def decode_attn_update(cfg, q, k_new, v_new, ck, cv, pos, *, topo,
                        seq_axes: Tuple[str, ...]):
    """Write (k_new,v_new) at ``pos`` into seq-sharded cache shards and run
    distributed flash decoding. Runs inside shard_map over ``seq_axes``
    (cache seq dim) with batch dims sharded over topo.batch_axes."""
    def local(q, k_new, v_new, ck, cv, pos):
        s_loc = ck.shape[1]
        idx = jnp.int32(0)
        mul = 1
        for ax in reversed(seq_axes):
            idx = idx + jax.lax.axis_index(ax) * mul
            mul = mul * topo.mesh.shape[ax]
        start = idx * s_loc
        # masked single-position write into my shard
        lpos = jnp.clip(pos - start, 0, s_loc - 1)  # [B]
        mine = (pos >= start) & (pos < start + s_loc)

        def write(c, new):
            b = c.shape[0]
            upd = jnp.where(mine[:, None, None, None], new, jnp.take_along_axis(
                c, lpos[:, None, None, None], axis=1))
            return jax.vmap(lambda cb, ub, pb: jax.lax.dynamic_update_slice(
                cb, ub, (pb, 0, 0)))(c, upd, lpos)

        ck = write(ck, k_new)
        cv = write(cv, v_new)
        out = L.decode_attention_seqsharded(q, ck, cv, pos + 1, axis_name=seq_axes,
                                            scale=cfg.attention_multiplier or None)
        return out, ck, cv

    bt = topo.batch_axes
    qspec = P(bt, None, None, None)
    cspec = P(bt, seq_axes, None, None)
    kvnew = P(bt, None, None, None)
    return compat.shard_map(
        local, mesh=topo.mesh,
        in_specs=(qspec, kvnew, kvnew, cspec, cspec, P(bt)),
        out_specs=(qspec, cspec, cspec),
    )(q, k_new, v_new, ck, cv, pos)


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                tokens: jax.Array, *, topo: Optional[Topology] = None,
                seq_axes: Tuple[str, ...] = ()):
    """One-token decode. tokens [B] int32. Returns (logits [B,Vpad], cache)."""
    b = tokens.shape[0]
    pos = cache["pos"]  # [B]
    x = embed_tokens(cfg, params, tokens[:, None], topo=topo)
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    def body(xc, layer_in):
        lp, ck, cv = layer_in
        hn = L.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dq->bsq", hn, lp["wq"]).reshape(b, 1, h, hd)
        k = jnp.einsum("bsd,dq->bsq", hn, lp["wk"]).reshape(b, 1, kv, hd)
        v = jnp.einsum("bsd,dq->bsq", hn, lp["wv"]).reshape(b, 1, kv, hd)
        if cfg.qk_norm:
            q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
        cos, sin = L.rope_angles(pos[:, None], hd, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        if topo is not None and seq_axes:
            att, ck, cv = decode_attn_update(cfg, q, k, v, ck, cv, pos,
                                              topo=topo, seq_axes=seq_axes)
        else:
            ck = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0)))(
                ck, k, pos)
            cv = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0)))(
                cv, v, pos)
            pv, l, _ = L.decode_attention_local(q, ck, cv, pos + 1,
                                                scale=cfg.attention_multiplier or None)
            att = (pv / jnp.maximum(l, 1e-30)[:, :, None].reshape(b, 1, h, 1)).astype(q.dtype)
        out = jnp.einsum("bsq,qd->bsd", att.reshape(b, 1, h * hd), lp["wo"])
        xc = xc + cfg.residual_multiplier * out
        xc = ffn_block(cfg, lp, xc, topo=topo)
        return xc, (ck, cv)

    x, (ck, cv) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    logits = logits_head(cfg, params, x, topo=topo)
    new_cache = {"k": ck, "v": cv, "pos": pos + 1}
    return logits[:, 0], new_cache
