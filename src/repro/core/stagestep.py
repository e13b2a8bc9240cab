"""Per-family stage programs: what ONE stage computes in ONE pipeline tick.

Three programs share the ``StageCtx`` contract and the backend-routed
``attend_chunk`` attention composition (own-pool prefix + remote prefix +
causal self block):

- ``tfm_stage_step``     transformer families (dense / moe / vlm / encdec
                         decoder with optional cross-attention),
- ``ssm_stage_step``     Mamba2: conv/SSD state carried tick-to-tick,
- ``hybrid_stage_step``  Zamba2: SSM groups + a shared attention block whose
                         KV participates in MBKR (one "layer" per group).

Every cross-chip byte goes through ``ctx.transport`` (core.transport) and
the stage programs thread the CollectiveLedger through their layer scans.
Under the MANUAL TP lowering (``ctx.mtp`` set, DESIGN.md §3.6) the programs
insert the explicit tensor-parallel psums GSPMD would otherwise derive: one
after each attention o-projection, one after each FFN down-projection (the
residual stream stays replicated; head/row counts come from the LOCAL param
shapes, so the same code traces both lowerings).

New model families plug in here without touching the driver (DESIGN.md §2.4).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import costmodel as cm
from repro.core import remote
from repro.core.attention import (attn_finish, attn_init, get_backend,
                                  group_queries, pool_scan)
from repro.core.plan import PipelinePlan
from repro.core.staging import ManualTP, _hyb_scfg, auto_tp
from repro.core import transport as tx
from repro.core.transport import Ledger, Transport
from repro.obs import telemetry as obs_t
from repro.obs.telemetry import StageTelemetry
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.topology import Topology

Params = Dict[str, Any]


@dataclass
class StageCtx:
    """Per-trace context threaded through the tick body."""
    cfg: ModelConfig
    plan: PipelinePlan
    topo: Topology
    stage: jax.Array          # my stage id (traced)
    phase: jax.Array          # my chunk index this tick (traced; may be OOR)
    first_half: jax.Array     # bool: stage < N/2
    pair_perm: Sequence[Tuple[int, int]]
    scale: float
    transport: Transport = None
    mtp: Optional[ManualTP] = None  # manual TP lowering plan (None = GSPMD)
    x_spec: Any = P(None, None, None)  # residual-stream sharding (SP variant)
    # STATIC hit-prefix length (chunks): the first k chunk writes redirect to
    # the scratch slot because the pool was SEEDED with cached prefix KV
    # (kvstore.prefix / DESIGN.md §11). 0 = prefix path disarmed; the traced
    # program is then byte-identical to pre-prefix builds.
    prefix_chunks: int = 0

    @property
    def active(self):
        """My phase is a real chunk this tick (not fill/drain garbage)."""
        return (self.phase >= 0) & (self.phase < self.plan.num_chunks)

    @property
    def auto_tp(self) -> bool:
        """Sharding constraints may name the TP axes."""
        return auto_tp(self.topo, self.mtp)


def _tp_apply(ctx: StageCtx) -> Optional[T.ManualTPApply]:
    """Build the model-layer manual-TP hooks (psum closures) from the plan.
    Ledger charges for these reduces happen at the stage-program level (the
    closures stay ledger-free so they can run inside ``models`` code)."""
    mtp = ctx.mtp
    if mtp is None:
        return None
    tr = ctx.transport
    return T.manual_tp_apply(mtp, lambda y: tr.tp_psum(y, mtp.axes, None)[0])


def _psum_bytes(ctx: StageCtx, x: jax.Array) -> float:
    """Ring-all-reduce wire bytes of one manual tp_psum of ``x`` (per chip)."""
    k = ctx.mtp.tp
    return 2.0 * (k - 1) / k * tx.nbytes(x)


def _rep(ctx: StageCtx) -> int:
    """Telemetry count replication: manual TP chips charge 1/tp each so the
    collect psum restores logical per-stage counts."""
    return ctx.mtp.tp if ctx.mtp is not None else 1


def attend_chunk(ctx: StageCtx, l_idx: jax.Array, q: jax.Array,
                 k_new: jax.Array, v_new: jax.Array,
                 pool, led: Ledger = None, tel: StageTelemetry = None):
    """Full MOCAP attention for one layer of the current chunk:
    own-pool prefix + (MBKR) remote prefix + causal self block. Returns
    ``(att, ledger, telemetry)``.

    q [B,C,H,D]; k_new/v_new [B,C,K,D]; ``pool`` is the stage's paged KV
    store (``kvstore.pages.PagedPool``: payloads [P, lps, B, K, pt, D] +
    per-head scales when quantized). Under manual TP the shapes are the
    LOCAL shards (heads grouped per local kv head).

    Backends mix per SOURCE (the combine chain is backend-independent):
    the causal self block runs ``plan.attn_backend``; every POOL-sourced
    partial — the own-pool scan, fetch'd chunks, the creditor-side qship
    scan — runs ``plan.pool_backend`` (= attn_backend unless overridden
    via RunConfig.pool_backend). Under pallas the pool scan is one batched
    slot-grid kernel launch per (layer, tick), O(1) in pool depth."""
    plan = ctx.plan
    backend = get_backend(plan.attn_backend)
    pool_be = backend if plan.pool_backend == plan.attn_backend \
        else get_backend(plan.pool_backend)
    b, c, h, d = q.shape
    kvh = k_new.shape[2]
    qg = group_queries(q, kvh)
    st = attn_init(b, c, kvh, h // kvh, d)

    pool_l = remote._pool_layer(pool, l_idx)

    # telemetry: actual attention work this (layer, tick) — the LBCP cost
    # term with the TRACED prefix (phase * c tokens behind this chunk)
    if tel is not None:
        prefix = jnp.clip(ctx.phase, 0, plan.num_chunks - 1) * c
        tel = obs_t.charge(tel, "attn_work",
                           cm.attn_flops(ctx.cfg, c, prefix),
                           ctx.active, _rep(ctx))

    # 1. own local prefix: chunks j < min(phase, p2)
    limit = jnp.minimum(ctx.phase, plan.p2)
    st = pool_scan(pool_be, qg, pool_l, plan.slot_pages, plan.slot_own_chunk,
                   limit, ctx.scale, st)
    # lockstep: the pool scan launches every tick (batched = one slot-grid
    # block; streamed = one block per slot)
    tel = obs_t.charge(tel, "launches",
                       1.0 if pool_be.batched_pool else float(plan.num_slots),
                       None, _rep(ctx))

    # 2. remote prefix: chunks p2 <= j < phase live at my pair
    if plan.p2 < plan.num_chunks and plan.mode == "mocap":
        if plan.remote_attn == "fetch":
            st, led, tel = remote.fetch_remote(ctx, pool_be, qg, pool_l, st,
                                               led, tel)
        else:
            st, led, tel = remote.qship_remote(ctx, pool_be, qg, pool_l, st,
                                               led, tel)

    # 3. self block (causal)
    st = backend.self_block(qg, k_new, v_new, ctx.scale, st)
    tel = obs_t.charge(tel, "launches", 1.0, None, _rep(ctx))
    att = attn_finish(st, q.dtype)
    return att, led, tel


# --------------------------------------------------------- transformer step

def tfm_stage_step(ctx: StageCtx, layers: Params, x: jax.Array,
                   pool, led: Ledger = None, tel: StageTelemetry = None, *,
                   cross: Optional[Tuple] = None):
    """Apply this stage's layers to chunk ``ctx.phase``. Returns
    (x_out, pool, ledger, telemetry). ``cross`` = (enc_xk, enc_xv)
    [lps,B,F,K,D] for whisper decoder stages."""
    cfg, plan, mtp = ctx.cfg, ctx.plan, ctx.mtp
    tr = ctx.transport
    b, c, dm = x.shape
    hd = cfg.resolved_head_dim
    positions = jnp.clip(ctx.phase, 0, plan.num_chunks - 1) * plan.chunk_len \
        + jnp.arange(c)[None, :]
    cos, sin = L.rope_angles(positions, hd, cfg.rope_theta)
    tp_apply = _tp_apply(ctx)
    # mirrors ffn_block's psum condition exactly: ONE reduce iff any FFN
    # part is actually sharded for THIS config (dense for non-MoE; expert
    # and/or present shared-expert parts for MoE)
    ffn_reduced = tp_apply is not None and (
        tp_apply.dense if cfg.moe is None else
        (tp_apply.moe or (cfg.moe.num_shared_experts > 0
                          and tp_apply.shared)))

    def layer_body(carry, xs):
        xc, li, led, tel = carry
        lp = xs if cross is None else xs[0]
        hn = L.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        # LOCAL head counts come from the (possibly TP-sharded) params
        q = jnp.einsum("bcd,dq->bcq", hn, lp["wq"])
        k = jnp.einsum("bcd,dq->bcq", hn, lp["wk"])
        v = jnp.einsum("bcd,dq->bcq", hn, lp["wv"])
        q = q.reshape(b, c, q.shape[-1] // hd, hd)
        k = k.reshape(b, c, k.shape[-1] // hd, hd)
        v = v.reshape(b, c, v.shape[-1] // hd, hd)
        if cfg.qk_norm:
            q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        if ctx.auto_tp:
            q = jax.lax.with_sharding_constraint(
                q, P(None, None, ctx.topo.tp_axis, None))
            if isinstance(ctx.topo.tp_axis, tuple):
                kv_ax = ctx.topo.tp_axis[0]
                k = jax.lax.with_sharding_constraint(k, P(None, None, kv_ax, None))
                v = jax.lax.with_sharding_constraint(v, P(None, None, kv_ax, None))
        att, led, tel = attend_chunk(ctx, li, q, k, v, pool, led, tel)
        h_loc = att.shape[2]
        upd = jnp.einsum("bcq,qd->bcd", att.reshape(b, c, h_loc * hd),
                         lp["wo"])
        if mtp is not None and mtp.attn:
            upd, led = tr.tp_psum(upd, mtp.axes, led, active=ctx.active)
        xc = xc + cfg.residual_multiplier * upd
        if cross is not None:
            xk_l = jax.lax.dynamic_index_in_dim(cross[0], li, 0, keepdims=False)
            xv_l = jax.lax.dynamic_index_in_dim(cross[1], li, 0, keepdims=False)
            hnx = L.rms_norm(xc, lp["lnx"], cfg.norm_eps)
            qx = jnp.einsum("bcd,dq->bcq", hnx, lp["xwq"])
            qx = qx.reshape(b, c, qx.shape[-1] // hd, hd)
            if plan.attn_backend == "pallas":
                # non-causal chunk_attention: decoder chunk vs the whole
                # encoder output through the flash kernel (ROADMAP item)
                from repro.kernels import ops as kops
                attx = kops.full_attention(qx, xk_l, xv_l)
            else:
                attx = L.flash_attention_xla(qx, xk_l, xv_l, causal_offset=None)
            hx_loc = attx.shape[2]
            updx = jnp.einsum("bcq,qd->bcd", attx.reshape(b, c, hx_loc * hd),
                              lp["xwo"])
            if mtp is not None and mtp.attn:
                updx, led = tr.tp_psum(updx, mtp.axes, led, active=ctx.active)
            xc = xc + updx
            tel = obs_t.charge(tel, "launches", 1.0, None, _rep(ctx))
        ep_axis = ctx.topo.tp_axis if (cfg.moe is not None and isinstance(
            ctx.topo.tp_axis, tuple) and ctx.auto_tp) else None
        if ep_axis is not None:
            # EP dispatch gathers tokens arbitrarily: replicate x first
            xc = jax.lax.with_sharding_constraint(xc, P(None, None, None))
        xc = T.ffn_block(cfg, lp, xc, topo=None, ep_axis=ep_axis, tp=tp_apply)
        if ffn_reduced:
            # one [B,C,d] psum inside ffn_block — charge it here
            led = tx.charge(led, "tp", _psum_bytes(ctx, xc), ctx.active)
        # kv_split: keep the residual stream SEQUENCE-SHARDED between layers
        # (Megatron-SP): psums become reduce-scatters and the stage-boundary
        # ring permute moves C/tp tokens per chip instead of C
        if ctx.auto_tp:
            xc = jax.lax.with_sharding_constraint(xc, ctx.x_spec)
        return (xc, li + 1, led, tel), (k, v)

    xs = layers if cross is None else (layers,)
    (x, _, led, tel), (ks, vs) = jax.lax.scan(
        layer_body, (x, jnp.int32(0), led, tel), xs)
    pool, led, tel = remote.write_pools(ctx, pool, ks, vs, led, tel)
    return x, pool, led, tel


# --------------------------------------------------------------- SSM step

def ssm_stage_step(ctx: StageCtx, layers: Params, x: jax.Array, state,
                   led: Ledger = None, tel: StageTelemetry = None):
    """Mamba2 stage: lps blocks; SSM/conv state carried tick-to-tick and
    zeroed at phase 0 (start of the request). The SSD inner loop routes
    through ``plan.ssm_backend`` (jnp reference | kernels.ops.ssd), the same
    knob pattern as attention. SSM blocks replicate under manual TP (no
    collectives — see staging.ManualTP), so the ledger passes through."""
    cfg, impl = ctx.cfg, ctx.plan.ssm_backend
    fresh = ctx.phase <= 0
    if tel is not None:
        lps = ctx.plan.layers_per_stage
        tel = obs_t.charge(tel, "attn_work",
                           lps * cm.attn_flops(cfg, x.shape[1], 0),
                           ctx.active, _rep(ctx))
        if impl == "pallas":
            tel = obs_t.charge(tel, "launches", float(lps), None, _rep(ctx))

    def layer_body(xc, xs):
        lp, conv_st, ssd_st = xs
        conv_st = jnp.where(fresh, jnp.zeros_like(conv_st), conv_st)
        ssd_st = jnp.where(fresh, jnp.zeros_like(ssd_st), ssd_st)
        xo, st2 = S.block_apply(cfg, lp, xc,
                                state={"conv": conv_st, "ssd": ssd_st},
                                ssd_impl=impl)
        return xo, (st2["conv"], st2["ssd"])

    x, (conv2, ssd2) = jax.lax.scan(layer_body, x, (layers, state[0], state[1]))
    return x, (conv2, ssd2), led, tel


# ------------------------------------------------------------- hybrid step

def hybrid_stage_step(ctx: StageCtx, groups: Params, shared: Params,
                      x: jax.Array, state, pool, led: Ledger = None,
                      tel: StageTelemetry = None):
    """Zamba2 stage = up to lps groups of (pg Mamba2 + shared attn block).
    The shared block's KV participates in MBKR (1 'layer' per group)."""
    cfg, plan, mtp = ctx.cfg, ctx.plan, ctx.mtp
    tr = ctx.transport
    ssd_impl = plan.ssm_backend
    scfg = _hyb_scfg(cfg)
    b, c, dm = x.shape
    hd = cfg.resolved_head_dim
    n_groups = cfg.hybrid.num_groups
    fresh = ctx.phase <= 0
    positions = jnp.clip(ctx.phase, 0, plan.num_chunks - 1) * plan.chunk_len \
        + jnp.arange(c)[None, :]
    cos, sin = L.rope_angles(positions, hd, cfg.rope_theta)
    tp_apply = _tp_apply(ctx)

    def group_body(carry, xs):
        xc, gi, led, tel = carry
        g_lp, conv_st, ssd_st = xs

        def mamba_body(xm, ms):
            lp, cst, sst = ms
            cst = jnp.where(fresh, jnp.zeros_like(cst), cst)
            sst = jnp.where(fresh, jnp.zeros_like(sst), sst)
            xo, st2 = S.block_apply(cfg, lp, xm,
                                    state={"conv": cst, "ssd": sst},
                                    ssd_impl=ssd_impl)
            return xo, (st2["conv"], st2["ssd"])

        xc2, (conv2, ssd2) = jax.lax.scan(mamba_body, xc, (g_lp, conv_st, ssd_st))
        # shared attention: only for REAL groups (global group id < n_groups)
        gid = ctx.stage * plan.layers_per_stage + gi
        has_attn = gid < n_groups
        hn = L.rms_norm(xc2, shared["ln1"], cfg.norm_eps)
        q = jnp.einsum("bcd,dq->bcq", hn, shared["wq"])
        k = jnp.einsum("bcd,dq->bcq", hn, shared["wk"])
        v = jnp.einsum("bcd,dq->bcq", hn, shared["wv"])
        q = q.reshape(b, c, q.shape[-1] // hd, hd)
        k = k.reshape(b, c, k.shape[-1] // hd, hd)
        v = v.reshape(b, c, v.shape[-1] // hd, hd)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        att, led, tel = attend_chunk(ctx, gi, q, k, v, pool, led, tel)
        h_loc = att.shape[2]
        upd = jnp.einsum("bcq,qd->bcd", att.reshape(b, c, h_loc * hd),
                         shared["wo"])
        if mtp is not None and mtp.attn:
            upd, led = tr.tp_psum(upd, mtp.axes, led, active=ctx.active)
        xc3 = xc2 + jnp.where(has_attn, upd, 0.0)
        ffn = T.ffn_block(scfg, shared, xc3, topo=None,
                          tp=tp_apply) - xc3  # isolate update
        if tp_apply is not None and tp_apply.dense:
            led = tx.charge(led, "tp", _psum_bytes(ctx, xc3), ctx.active)
        xc3 = xc3 + jnp.where(has_attn, ffn, 0.0)
        return (xc3, gi + 1, led, tel), (conv2, ssd2, k, v)

    (x, _, led, tel), (conv2, ssd2, ks, vs) = jax.lax.scan(
        group_body, (x, jnp.int32(0), led, tel), (groups, state[0], state[1]))
    pool, led, tel = remote.write_pools(ctx, pool, ks, vs, led, tel)
    return x, (conv2, ssd2), pool, led, tel
