"""Parameter staging: flat model params -> per-stage stacked params + specs.

Sits between the model definitions (``repro.models``) and the pipeline
driver: restacks flat ``[L, ...]`` layer params into ``[N, lps, ...]``
(zero-padded — zero-param transformer/SSM blocks are exact identities via the
residual), derives the matching PartitionSpecs for the mesh topology,
allocates the per-stage paged KV pool (``repro.kvstore``) the stage programs
write into, and implements the two exact zero-padding transforms the
kv_split perf variant needs (query-head padding per kv group, routed-expert
padding for EP). See DESIGN.md §2 (layering), §3 (mesh mapping) and §6
(memory tiers).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.plan import PipelinePlan
from repro.kvstore import pages as kvpages
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.topology import Topology

Params = Dict[str, Any]


# ------------------------------------------------- manual TP lowering plan

@dataclass(frozen=True)
class ManualTP:
    """Static description of the MANUAL tensor-parallel lowering
    (``PipelinePlan.tp_lowering == "manual"``, DESIGN.md §3.6): which param
    groups are sharded over the (now fully-manual) TP mesh axes, and hence
    where the stage programs must insert explicit transport psums.

    A group is sharded only when the split is HEAD/ROW-exact (a GSPMD-auto
    axis can shard elementwise; a manual lowering cannot cut a head or an
    expert in half) — otherwise that group's params replicate and its
    compute needs no collective. This keeps the manual path correct for
    every family at any tp, degrading sharding rather than failing."""
    axes: Tuple[str, ...]   # flattened TP mesh axis names (all manual)
    tp: int                 # product of their sizes
    attn: bool              # q/k/v/o head-sharded -> psum after the o-proj
    kv_div: int             # kv-head shard factor (1 when attn is False)
    ffn: bool               # dense SwiGLU f-sharded -> psum after down-proj
    moe_ffn: bool           # expert FFN f-sharded (plain TP axis)
    moe_ep: bool            # experts sharded over the axes (kv_split view)
    shared_moe: bool        # shared-experts SwiGLU f-sharded


def manual_tp_plan(cfg: ModelConfig, plan: PipelinePlan,
                   topo: Optional[Topology]) -> Optional[ManualTP]:
    """None unless the plan asks for manual lowering AND tp > 1."""
    if topo is None or plan.tp_lowering != "manual" or topo.tp_size <= 1:
        return None
    md = topo.tp_axis
    axes = md if isinstance(md, tuple) else (md,)
    tp = topo.tp_size
    kvh, h = cfg.num_kv_heads, cfg.num_heads
    if isinstance(md, tuple):
        kv_ax = topo.mesh.shape[md[0]]
        qg_ax = tp // kv_ax
        attn = (kvh > 0 and kvh % kv_ax == 0
                and (h // max(kvh, 1)) % qg_ax == 0)
        kv_div = kv_ax if attn else 1
    else:
        attn = kvh > 0 and kvh % tp == 0
        kv_div = tp if attn else 1
    ffn = cfg.d_ff > 0 and cfg.d_ff % tp == 0
    moe_ffn = moe_ep = shared_moe = False
    if cfg.moe is not None:
        fe = cfg.moe.d_expert or cfg.d_ff
        if isinstance(md, tuple):
            moe_ep = cfg.moe.num_experts % tp == 0
        else:
            moe_ffn = fe % tp == 0
        if cfg.moe.num_shared_experts:
            shared_moe = (fe * cfg.moe.num_shared_experts) % tp == 0
    if cfg.family == "ssm":
        attn, ffn = False, False
    return ManualTP(axes=tuple(axes), tp=tp, attn=attn, kv_div=kv_div,
                    ffn=ffn, moe_ffn=moe_ffn, moe_ep=moe_ep,
                    shared_moe=shared_moe)


def _strip_axes(spec: P, axes) -> P:
    """Drop the given mesh axes from a PartitionSpec (replicate there)."""
    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a not in axes)
            return kept if kept else None
        return None if e in axes else e
    return P(*(keep(e) for e in spec))


def _apply_manual_tp(cfg: ModelConfig, out: Params, mtp: ManualTP) -> Params:
    """Replicate every param group the manual lowering does not shard (see
    ``ManualTP``); embed / lm_head always replicate under manual (the gather
    and unembed run replicated inside the body — vocab sharding is a
    GSPMD-auto-only optimization)."""
    drop = set(mtp.axes)
    strip = {"embed", "lm_head"}
    if not mtp.attn:
        strip |= {"wq", "wk", "wv", "wo", "xwq", "xwk", "xwv", "xwo"}
    if not mtp.ffn:
        strip |= {"wg", "wu", "wd"}
    if not (mtp.moe_ffn or mtp.moe_ep):
        strip |= {"e_wg", "e_wu", "e_wd"}
    if not mtp.shared_moe:
        strip |= {"s_wg", "s_wu", "s_wd"}
    # SSM blocks never TP-shard under manual (out_proj's row split would
    # need an activation slice + psum inside the scan; replication is exact)
    ssm_keys = {"in_proj", "out_proj", "conv_w", "conv_b", "a_log",
                "dt_bias", "d_skip", "gate_norm", "ln"}
    strip |= ssm_keys

    def walk(tree):
        if isinstance(tree, dict):
            return {k: (jax.tree.map(
                        lambda p: _strip_axes(p, drop), v,
                        is_leaf=lambda x: isinstance(x, P))
                        if k in strip else walk(v))
                    for k, v in tree.items()}
        return tree
    return walk(out)


def alloc_kv_pool(cfg: ModelConfig, plan: PipelinePlan, b: int,
                  topo: Topology = None, *,
                  mtp: Optional[ManualTP] = None) -> kvpages.PagedPool:
    """One stage's paged KV pool, zero-initialized in the plan's storage
    codec; kv_split meshes get the pool sharded by kv head (payloads AND
    scales carry kvh on axis 3). Under the MANUAL lowering the body is
    mapped over the TP axes too, so the pool is allocated with the LOCAL
    kv-head count and no sharding hint."""
    kvh = cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    if mtp is not None:
        kvh //= mtp.kv_div
    pool = kvpages.alloc_pool(plan.page_geometry, plan.codec,
                              plan.layers_per_stage, b, kvh, hd)
    if (topo is not None and isinstance(topo.tp_axis, tuple)
            and auto_tp(topo, mtp)):
        spec = P(None, None, None, topo.tp_axis[0], None, None)
        shard = lambda a: (jax.lax.with_sharding_constraint(a, spec)
                           if a is not None else None)
        pool = kvpages.PagedPool(shard(pool.k), shard(pool.v),
                                 shard(pool.k_scale), shard(pool.v_scale))
    return pool


def stage_params(cfg: ModelConfig, params: Params, plan: PipelinePlan) -> Params:
    """Restack flat [L, ...] layer params into [N, lps, ...] (zero-padded:
    zero-param transformer/SSM blocks are exact identities via the residual).
    Embedding / head / norms are replicated across stages (SPMD: every stage
    computes the masked embed; only stage 0's result is used)."""
    n, lps = plan.num_stages, plan.layers_per_stage

    def restack(tree, nl):
        def one(a):
            pad = n * lps - nl
            if pad:
                a = jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
            return a.reshape((n, lps) + a.shape[1:])
        return jax.tree.map(one, tree)

    if cfg.family == "hybrid":
        h = cfg.hybrid
        pg = h.ssm_per_group
        groups = params["mamba_groups"]        # [G, pg, ...]
        tail = params["mamba_tail"]            # [tail, ...]
        # tail becomes pseudo-group G (pad its layer dim to pg)
        def fold(g, t):
            t = jnp.concatenate(
                [t, jnp.zeros((pg - t.shape[0],) + t.shape[1:], t.dtype)])[None]
            g = jnp.concatenate([g, t])        # [G+1, pg, ...]
            pad = n * plan.layers_per_stage - g.shape[0]
            if pad:
                g = jnp.concatenate([g, jnp.zeros((pad,) + g.shape[1:], g.dtype)])
            return g.reshape((n, plan.layers_per_stage) + g.shape[1:])
        staged_groups = jax.tree.map(fold, groups, tail)
        return {
            "embed": params["embed"], "final_norm": params["final_norm"],
            "stage_layers": staged_groups, "shared": params["shared"],
        }
    if cfg.family == "encdec":
        out = {
            "embed": params["embed"], "final_norm": params["final_norm"],
            "stage_layers": restack(params["dec_layers"], cfg.num_layers),
            "enc_layers": params["enc_layers"], "enc_norm": params["enc_norm"],
        }
        return out
    out = {
        "embed": params["embed"], "final_norm": params["final_norm"],
        "stage_layers": restack(params["layers"], cfg.num_layers),
    }
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


def init_stage_params(cfg: ModelConfig, plan: PipelinePlan,
                      topo: Topology, key: jax.Array) -> Params:
    """Random parameters built directly in the ``stage_params`` layout: ONE
    jitted init + restack whose outputs carry the ``stage_param_specs``
    shardings, so each device only ever holds its stage's layer slice plus
    the replicated embedding / head — no full flat copy on the first device
    and no second copy from a host-side restack."""
    from jax.sharding import NamedSharding
    from repro.models.api import build_model
    model = build_model(cfg)
    shardings = jax.tree.map(lambda s: NamedSharding(topo.mesh, s),
                             stage_param_specs(cfg, plan, topo),
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(lambda k: stage_params(cfg, model.init(k), plan),
                   out_shardings=shardings)(key)


def stage_param_specs(cfg: ModelConfig, plan: PipelinePlan, topo: Topology) -> Params:
    """PartitionSpecs for ``stage_params`` output: stage dim over the stage
    axis, TP dims over the model axis, embed d-sharded (gather stays local).
    Under the manual TP lowering the sharding degrades per ``ManualTP``
    (head/row-exact splits only; the rest replicates)."""
    out = _stage_param_specs(cfg, plan, topo)
    mtp = manual_tp_plan(cfg, plan, topo)
    if mtp is not None:
        out = _apply_manual_tp(cfg, out, mtp)
    return out


def _stage_param_specs(cfg: ModelConfig, plan: PipelinePlan, topo: Topology) -> Params:
    st, md = topo.stage_axis, topo.tp_axis

    def lift(spec: P) -> P:
        return P(st, None, *spec[1:])  # [L,...] -> [N, lps, ...]

    if cfg.family == "hybrid":
        bs = S.block_specs(cfg, fsdp=False)
        g_specs = jax.tree.map(lambda p: P(st, None, None, *p[1:]), bs,
                               is_leaf=lambda x: isinstance(x, P))
        shared = jax.tree.map(
            lambda p: P(*p[1:]), T.specs(_hyb_scfg(cfg), fsdp=False)["layers"],
            is_leaf=lambda x: isinstance(x, P))
        out = {"embed": P(None, md), "final_norm": P(None),
               "stage_layers": g_specs, "shared": shared}
        return _rename_model(out, md)
    if cfg.family == "encdec":
        from repro.models import whisper as W
        ws = W.specs(cfg, fsdp=False)
        dec = jax.tree.map(lift, ws["dec_layers"], is_leaf=lambda x: isinstance(x, P))
        out = {"embed": P(None, md), "final_norm": P(None),
               "stage_layers": dec, "enc_layers": ws["enc_layers"],
               "enc_norm": P(None)}
        return _rename_model(out, md)
    base = T.specs(cfg, fsdp=False)["layers"] if cfg.family != "ssm" \
        else S.block_specs(cfg, fsdp=False)
    layers = jax.tree.map(lift, base, is_leaf=lambda x: isinstance(x, P))
    out = {"embed": P(None, md), "final_norm": P(None), "stage_layers": layers}
    if not cfg.tie_embeddings and cfg.family in ("dense", "moe", "vlm"):
        out["lm_head"] = P(None, md)
    out = _rename_model(out, md)
    if isinstance(md, tuple) and cfg.family in ("dense", "moe", "vlm"):
        # K/V projections shard by KV HEAD only (replicated over "qg") so the
        # [B,C,kvh,hd] reshape keeps full head_dim per chip (no hd split)
        for k in ("wk", "wv"):
            out["stage_layers"][k] = P(topo.stage_axis, None, None, md[0])
        if cfg.moe is not None:
            # EXPERT parallelism: experts over the full TP axis, FFN local
            for k in ("e_wg", "e_wu", "e_wd"):
                out["stage_layers"][k] = P(topo.stage_axis, None, md, None, None)
    return out


def batch_specs(topo: Topology, mtp: Optional[ManualTP] = None):
    """(manual shard_map axis_names, batch axes outside the stage axis).
    The manual TP lowering adds the TP axes to the manual set, and so does
    a size-1 TP axis: the whole mesh is then manual, which is where Mosaic
    (Pallas TPU) kernels lower — they cannot be auto-partitioned."""
    pod_axes = tuple(a for a in topo.batch_axes if a != topo.stage_axis)
    manual = set(pod_axes) | {topo.stage_axis}
    if mtp is not None or topo.tp_size == 1:
        md = topo.tp_axis
        manual |= set(md if isinstance(md, tuple) else (md,))
    return manual, pod_axes


def auto_tp(topo: Topology, mtp: Optional[ManualTP]) -> bool:
    """GSPMD partitions the TP axes inside the pipeline region (the only
    case in which sharding constraints may name them there)."""
    return mtp is None and topo.tp_size > 1


def manual_only(spec: P, manual) -> P:
    """shard_map in_specs may only name MANUAL axes; auto-axis (TP) sharding
    flows through from the argument's actual sharding instead."""
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in manual)
            return kept if kept else None
        return entry if entry in manual else None
    return P(*(keep(e) for e in spec))


def manual_tree(tree, manual):
    return jax.tree.map(lambda p: manual_only(p, manual), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _hyb_scfg(cfg: ModelConfig) -> ModelConfig:
    from repro.models.hybrid import T_single_cfg
    return T_single_cfg(cfg)


def _rename_model(tree, tp_axis):
    """Model specs hardcode the "model" axis; rename to the topology's TP
    axis (possibly the split ("kv","qg") view)."""
    if tp_axis == "model":
        return tree

    def one(spec: P) -> P:
        return P(*(tp_axis if e == "model" else e for e in spec))
    return jax.tree.map(one, tree, is_leaf=lambda x: isinstance(x, P))


def kv_split_axes(cfg: ModelConfig, tp: int):
    """Factor the TP degree into (kv, qg) so attention shards by kv head and
    query group with NO collectives. Returns (kv_ax, qg_ax, padded_g) —
    padded_g > g means q heads are zero-padded per kv group (wq/wo pads are
    exact identities). None if kv heads don't divide."""
    if cfg.attn_free or cfg.num_kv_heads == 0:
        return None
    kvh, h = cfg.num_kv_heads, cfg.num_heads
    g = h // kvh
    kv_ax = min(kvh, tp)
    if tp % kv_ax or kvh % kv_ax:
        return None
    qg_ax = tp // kv_ax
    g_pad = -(-g // qg_ax) * qg_ax
    return kv_ax, qg_ax, g_pad


def pad_q_heads(cfg: ModelConfig, params: Params, g_pad: int) -> Tuple[ModelConfig, Params]:
    """Zero-pad query heads per kv group: H = kvh*g -> kvh*g_pad. Padded
    heads have zero wq (uniform attention) and zero wo rows (no contribution)
    — bit-exact with the unpadded model."""
    from repro.configs.base import replace as cfg_replace
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // kvh
    if g_pad == g:
        return cfg, params
    lp = dict(params["layers"])
    L_, d = lp["wq"].shape[0], lp["wq"].shape[1]
    wq = lp["wq"].reshape(L_, d, kvh, g, hd)
    wq = jnp.pad(wq, ((0, 0), (0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    lp["wq"] = wq.reshape(L_, d, kvh * g_pad * hd)
    wo = lp["wo"].reshape(L_, kvh, g, hd, d)
    wo = jnp.pad(wo, ((0, 0), (0, 0), (0, g_pad - g), (0, 0), (0, 0)))
    lp["wo"] = wo.reshape(L_, kvh * g_pad * hd, d)
    out = dict(params)
    out["layers"] = lp
    return cfg_replace(cfg, num_heads=kvh * g_pad), out


def pad_experts(cfg: ModelConfig, params: Params, e_pad: int) -> Tuple[ModelConfig, Params]:
    """Zero-pad routed experts to ``e_pad`` for expert parallelism. Padded
    experts' router logits are masked (MoEConfig.num_real_experts), so they
    are never routable — bit-exact."""
    import dataclasses
    from repro.configs.base import replace as cfg_replace
    m = cfg.moe
    if m is None or e_pad == m.num_experts:
        return cfg, params
    e0 = m.num_experts
    lp = dict(params["layers"])
    lp["router"] = jnp.pad(lp["router"], ((0, 0), (0, 0), (0, e_pad - e0)))
    for k in ("e_wg", "e_wu", "e_wd"):
        lp[k] = jnp.pad(lp[k], ((0, 0), (0, e_pad - e0)) + ((0, 0),) * (lp[k].ndim - 2))
    out = dict(params)
    out["layers"] = lp
    moe2 = dataclasses.replace(m, num_experts=e_pad,
                               num_real_experts=m.real_experts)
    return cfg_replace(cfg, moe=moe2), out
