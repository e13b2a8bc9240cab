"""Chunked-pipeline prefill driver — MOCAP's execution model in JAX.

This module is the THIN top of a layered execution stack (DESIGN.md §2):

    core.plan       PipelinePlan / build_plan    (static geometry + MBKR)
    core.staging    stage_params / specs / pads  (params -> [N, lps, ...])
    core.attention  online-softmax state + the pluggable backend registry
                    (``jnp`` reference | ``pallas`` flash kernel)
    core.remote     spill / fetch / qship collectives
    core.stagestep  per-family stage programs (tfm / ssm / hybrid)
    core.gpipe      the GPipe microbatch baseline driver
    core.pipeline   (this file) the lax.scan tick loop + shard_map lowering

The paper's WSC pipeline maps onto the TPU mesh as (DESIGN.md §3): pipeline
stage = one slice of the mesh's ``stage`` axis; chunk flow = scan over ticks
with a ring ppermute at stage boundaries (the 1-hop D2D transfer); KV
residency = a per-stage slot pool sized by the MBKR plan; remote access =
fetch or qship (DESIGN.md §3.4). SPMD lockstep: every stage executes every
tick; stages outside their active window compute masked garbage — that is
the pipeline *bubble*, visible in the dry-run's HLO-to-model-FLOPs ratio.

The public planning/staging API is re-exported here so existing callers
(`runtime.engine`, `launch/{serve,dryrun,cells}.py`, roofline, tests) keep
importing ``repro.core.pipeline``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig
from repro.core import transport as tx
from repro.core.attention import NEG_INF  # noqa: F401  (re-export)
from repro.core.gpipe import gpipe_prefill
from repro.core.plan import PipelinePlan, build_plan  # noqa: F401
from repro.core.staging import (Params, alloc_kv_pool,  # noqa: F401
                                auto_tp, batch_specs, init_stage_params,
                                kv_split_axes, manual_only,
                                manual_tp_plan, manual_tree, pad_experts,
                                pad_q_heads, stage_param_specs, stage_params)
from repro.kvstore.pages import PagedPool
from repro.core.stagestep import (StageCtx, attend_chunk,  # noqa: F401
                                  hybrid_stage_step, ssm_stage_step,
                                  tfm_stage_step)
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.topology import Topology
from repro.obs import telemetry as obs_t

__all__ = [
    "PipelinePlan", "build_plan", "stage_params", "stage_param_specs",
    "kv_split_axes", "pad_q_heads", "pad_experts", "prefill_pipeline",
    "NEG_INF",
]


# ---------------------------------------------------------------- the driver

def prefill_pipeline(cfg: ModelConfig, staged: Params, tokens: jax.Array,
                     plan: PipelinePlan, topo: Topology, *,
                     embeds: Optional[jax.Array] = None,
                     return_ledger: bool = False,
                     return_telemetry: bool = False,
                     prefix_chunks: int = 0,
                     prefix_pool: Optional[PagedPool] = None,
                     return_kv: bool = False,
                     health=None) -> jax.Array:
    """Chunked-pipeline prefill of ``tokens`` [B, S]; returns next-token
    logits [B, Vpad] (prefill-only: ONE output token, KV is discarded).

    ``embeds``: stub frontend embeddings [B, F, d] (vlm / audio); spliced
    in FRONT of the token embeddings chunk-wise (they occupy the first
    F // C chunks; F must be chunk-aligned for the pipeline path).

    ``return_ledger``: also return the CollectiveLedger — per-category wire
    bytes summed over chips (``core.transport``; validated against the §3.4
    analytic model in tests) as a dict of fp32 scalars.

    ``return_telemetry``: also return the StageTelemetry profile
    (``repro.obs.telemetry``) — per-(stage, tick) ``[N, T]`` fp32 arrays of
    pool occupancy, resident KV bytes, spill/fetch/qship events, attention
    work and backend launches. When False (the default) no telemetry math
    is traced at all: the carry threads ``None`` and every charge
    short-circuits, so the compiled program is identical. Return order is
    ``logits[, ledger][, telemetry]``.

    ``health``: an ``obs.health.HealthMonitor``; arms the non-finite
    activation sentinel. Per-(stage, tick) finite-counts of the stage
    output (gated to ACTIVE phases so bubble garbage never pages anyone)
    ride the scan ys out of the manual region as an ``[N, T]`` int32
    profile, delivered by ONE host callback after the shard_map. The tick
    loop itself adds no collectives; the only armed comms cost is that
    end-of-run delivery gather of one tiny int32 array — O(1), not
    O(ticks).

    It defaults to None, in which case NOTHING extra is traced — the
    compiled program is bit-identical (proven in tests/test_calibration.py,
    same style as the telemetry-off proof).

    ``prefix_chunks`` / ``prefix_pool`` / ``return_kv``: the device half of
    the prefix KV cache (``repro.kvstore.prefix``, DESIGN.md §11). When
    ``prefix_pool`` is given (a stage-stacked ``PagedPool`` snapshot, leading
    axis = stage) it REPLACES the zero-initialized pool, so the first
    ``prefix_chunks`` chunks of every sequence read cached KV instead of the
    KV they just computed; ``core.remote.write_pools`` redirects those
    chunks' writes to the scratch slot (the cached pages stay authoritative)
    and charges the ``prefix_hit`` ledger/telemetry keys. ``return_kv``
    additionally returns the scan-final pool snapshot so the host can seed
    future calls. All three default off, in which case the lowering is
    bit-identical to a build without this feature (the keys exist in the
    ledger/telemetry pytrees unconditionally, so no collective count
    changes). Return order is ``logits[, ledger][, telemetry][, kv]``.
    """
    if plan.mode == "gpipe":
        assert not return_ledger, "gpipe has no MBKR transport ledger"
        assert health is None, \
            "health probes only the chunked-pipeline driver"
        assert prefix_chunks == 0 and prefix_pool is None and not return_kv, \
            "prefix KV cache rides the chunked-pipeline paged pool only"
        return gpipe_prefill(cfg, staged, tokens, plan, topo,
                             return_telemetry=return_telemetry)
    n, m, c = plan.num_stages, plan.num_chunks, plan.chunk_len
    lps = plan.layers_per_stage
    st_ax = topo.stage_axis
    mtp = manual_tp_plan(cfg, plan, topo)
    if prefix_chunks or prefix_pool is not None or return_kv:
        assert cfg.family in ("dense", "moe"), \
            "prefix KV cache needs the pure paged-pool families (dense/moe)"
        # the pool's kvh axis must shard over the FULL manual TP degree, or
        # the host-side snapshot geometry wouldn't round-trip 1:1
        assert mtp is None or mtp.kv_div == mtp.tp, \
            "prefix pool I/O under manual TP requires kv_div == tp"
    if prefix_chunks:
        assert prefix_pool is not None, \
            "prefix_chunks > 0 requires a seeded prefix_pool"
        assert prefix_chunks <= min(plan.p2, plan.num_chunks - 1), \
            "prefix hits must stay within own-resident, non-final chunks"
    manual, pod_axes = batch_specs(topo, mtp)
    transport = tx.get_transport(plan.transport)
    led_axes = (st_ax,) + (mtp.axes if mtp is not None else ())
    attn_free = cfg.family == "ssm"
    kvh = cfg.num_kv_heads if not attn_free else 1
    if mtp is not None and not attn_free:
        kvh //= mtp.kv_div  # pool and stage programs see LOCAL kv heads
    hd = cfg.resolved_head_dim if not attn_free else 1
    dt = jnp.dtype(cfg.dtype)
    pair_perm = [(i, (i + n // 2) % n) for i in range(n)]
    ring_perm = [(i, (i + 1) % n) for i in range(n)]

    is_hybrid = cfg.family == "hybrid"
    is_ssm = cfg.family == "ssm"
    is_encdec = cfg.family == "encdec"

    # whisper: encoder runs OUTSIDE the pipeline (batch-parallel TP pass)
    enc_out = None
    if is_encdec:
        from repro.models import whisper as W
        enc_out = W.encode(cfg, {"enc_layers": staged["enc_layers"],
                                 "enc_norm": staged["enc_norm"]}, embeds)
        embeds = None

    def body(stage_layers, embed, final_norm, extra, tokens):
        stage = jax.lax.axis_index(st_ax)
        b = tokens.shape[0]
        sq = lambda a: jnp.squeeze(a, 0)
        stage_layers = jax.tree.map(sq, stage_layers)
        scale = cfg.attention_multiplier or 1.0 / math.sqrt(hd)

        cross = None
        if is_encdec:
            eo = extra["enc_out"]
            f = eo.shape[1]
            xk = jnp.einsum("bfd,ldq->lbfq", eo,
                            stage_layers["xwk"]).reshape(lps, b, f, kvh, hd)
            xv = jnp.einsum("bfd,ldq->lbfq", eo,
                            stage_layers["xwv"]).reshape(lps, b, f, kvh, hd)
            cross = (xk, xv)

        if is_ssm:  # attention-free: no KV pool at all
            pool = PagedPool(jnp.zeros((0,), dt), jnp.zeros((0,), dt))
        elif "prefix_pool" in extra:
            # seed from the cached snapshot (leading axis = stage, local
            # length 1 under the manual stage mapping) instead of zeros
            pool = jax.tree.map(sq, extra["prefix_pool"])
        else:
            pool = alloc_kv_pool(cfg, plan, b, topo, mtp=mtp)
        x0 = jnp.zeros((b, c, cfg.d_model), dt)
        if is_ssm or is_hybrid:
            d_in, nheads, conv_ch = S.dims(cfg)
            s = cfg.ssm
            if is_hybrid:
                pg = cfg.hybrid.ssm_per_group
                conv0 = jnp.zeros((lps, pg, b, s.conv_kernel - 1, conv_ch), jnp.float32)
                ssd0 = jnp.zeros((lps, pg, b, nheads, s.head_dim, s.d_state), jnp.float32)
            else:
                conv0 = jnp.zeros((lps, b, s.conv_kernel - 1, conv_ch), jnp.float32)
                ssd0 = jnp.zeros((lps, b, nheads, s.head_dim, s.d_state), jnp.float32)
            state0 = (conv0, ssd0)
        else:
            state0 = ()
        x_last0 = jnp.zeros((b, cfg.d_model), jnp.float32)

        # frontend splice: the token stream is [embeds, token-embeddings];
        # chunks may straddle the boundary — exact per-position select below
        emb_in = extra.get("embeds")
        n_front = 0
        embeds_pad = None
        if emb_in is not None:
            n_front = emb_in.shape[1]
            fpad = -(-n_front // c) * c
            embeds_pad = jnp.pad(emb_in, ((0, 0), (0, fpad - n_front), (0, 0)))

        # sequence-parallel residual is a GSPMD-auto-only optimization: the
        # manual lowering keeps the residual stream replicated across TP
        seq_sharded = (auto_tp(topo, mtp) and isinstance(topo.tp_axis, tuple)
                       and c % topo.tp_size == 0 and not is_ssm)
        x_spec = P(None, topo.tp_axis, None) if seq_sharded \
            else P(None, None, None)

        # one chunk's STORED pool bytes (local shard geometry under manual
        # TP — the telemetry collect psum restores logical stage bytes)
        chunk_bytes = 0.0 if is_ssm else obs_t.chunk_stored_bytes(
            plan, lps, b, c, kvh, hd)
        rep = mtp.tp if mtp is not None else 1

        def tick(carry, t):
            x_prev, pool, state, x_last, led, tel = carry
            phase = t - stage
            ctx = StageCtx(cfg=cfg, plan=plan, topo=topo, stage=stage,
                           phase=phase, first_half=stage < n // 2,
                           pair_perm=pair_perm, scale=scale,
                           transport=transport, mtp=mtp, x_spec=x_spec,
                           prefix_chunks=prefix_chunks)
            # ---- input: stage 0 embeds chunk t; others consume the ring buffer
            tc = jnp.clip(t, 0, m - 1)
            if n_front:
                pos = tc * c + jnp.arange(c)               # global positions
                tok_idx = jnp.clip(pos - n_front, 0, tokens.shape[1] - 1)
                tok_chunk = jnp.take(tokens, tok_idx, axis=1)
                x_tok = jnp.take(embed, tok_chunk, axis=0)
                fstart = jnp.minimum(tc * c, embeds_pad.shape[1] - c)
                x_front = jax.lax.dynamic_slice(
                    embeds_pad, (0, fstart, 0), (b, c, cfg.d_model)).astype(x_tok.dtype)
                x_emb = jnp.where((pos < n_front)[None, :, None], x_front, x_tok)
            else:
                tok_chunk = jax.lax.dynamic_slice(tokens, (0, tc * c), (b, c))
                x_emb = jnp.take(embed, tok_chunk, axis=0)
            if cfg.embedding_multiplier != 1.0:
                x_emb = x_emb * cfg.embedding_multiplier
            x = jnp.where(stage == 0, x_emb.astype(dt), x_prev)
            if auto_tp(topo, mtp):
                x = jax.lax.with_sharding_constraint(x, x_spec)
            # ---- stage compute
            if is_ssm:
                x_out, state, led, tel = ssm_stage_step(ctx, stage_layers, x,
                                                        state, led, tel)
            elif is_hybrid:
                x_out, state, pool, led, tel = hybrid_stage_step(
                    ctx, stage_layers, extra["shared"], x, state, pool, led,
                    tel)
            else:
                x_out, pool, led, tel = tfm_stage_step(
                    ctx, stage_layers, x, pool, led, tel, cross=cross)
            # ---- telemetry: this tick's pool-residency deltas + snapshot
            if not is_ssm:
                tel = obs_t.charge_tick_residency(tel, ctx, chunk_bytes, rep)
            tel_ys = None if tel is None else dict(tel)
            # ---- capture the last token's hidden state at the last stage
            take = (stage == n - 1) & (phase == m - 1)
            x_last = jnp.where(take, x_out[:, -1].astype(jnp.float32), x_last)
            # ---- ring transfer to the next stage (useful while my chunk is
            # real and a downstream stage consumes it)
            ring_active = (phase >= 0) & (phase < m) & (stage < n - 1)
            x_next, led = transport.ring_shift(x_out, st_ax, ring_perm, led,
                                               active=ring_active)
            # ---- sentinels: traced ONLY when armed (None = the compiled
            # program is bit-identical, zero extra collectives). Non-finite
            # counts ride the scan ys OUT of the manual region: no host
            # callback may sit inside manual shard_map (Shardy rejects it)
            bad = None
            if health is not None:
                nbad = jnp.sum(~jnp.isfinite(x_out.astype(jnp.float32)))
                bad = jnp.where(ctx.active, nbad, 0).astype(jnp.int32)
            return (x_next, pool, state, x_last, led, tel), (tel_ys, bad)

        tel0 = obs_t.telemetry_init() if return_telemetry else None
        carry0 = (x0, pool, state0, x_last0, tx.ledger_init(), tel0)
        (xf, pool_f, _, x_last, led, _), (tel_ys, bad_ys) = jax.lax.scan(
            tick, carry0, jnp.arange(plan.num_ticks))
        # replicate the final hidden state across stages
        x_last, led = transport.stage_psum(x_last, st_ax, led)
        led = tx.ledger_collect(led, led_axes)
        outs = [x_last, led]
        if return_telemetry:
            tel_ys = obs_t.telemetry_collect(
                tel_ys, mtp.axes if mtp is not None else None)
            outs.append({k: v[None, :] for k, v in tel_ys.items()})  # [1, T]
        if return_kv:
            # scan-final pool, re-stacked on a leading stage axis for the
            # host-side snapshot (mirrors the prefix_pool input layout)
            outs.append(jax.tree.map(lambda a: a[None], pool_f))
        if health is not None:
            # residual is replicated across manual TP, so the count already
            # agrees on every TP shard — no psum, no extra collective
            outs.append(bad_ys[None, :])  # [1, T] local stage row
        return tuple(outs)

    extra: Params = {}
    if is_hybrid:
        extra["shared"] = staged["shared"]
    if is_encdec:
        extra["enc_out"] = enc_out
    if embeds is not None and not is_encdec:
        extra["embeds"] = embeds
    if prefix_pool is not None:
        extra["prefix_pool"] = prefix_pool

    # one spec covers every pool leaf: [n, P, lps, B, kvh, pt|1, hd|1] —
    # stage axis leads, batch is pod-sharded, kv heads carry the manual TP
    # axes (kv_div == tp is asserted above); under GSPMD-auto the kv-split
    # sharding flows from the argument's actual sharding instead
    kv_leaf_spec = P(st_ax, None, None, pod_axes if pod_axes else None,
                     mtp.axes if mtp is not None else None, None, None)

    specs = stage_param_specs(cfg, plan, topo)
    sl_specs = manual_tree(specs["stage_layers"], manual)
    extra_specs: Params = {}
    if is_hybrid:
        extra_specs["shared"] = manual_tree(specs["shared"], manual)
    if is_encdec:
        extra_specs["enc_out"] = P(pod_axes if pod_axes else None, None, None)
    if "embeds" in extra:
        extra_specs["embeds"] = P(pod_axes if pod_axes else None, None, None)
    if "prefix_pool" in extra:
        extra_specs["prefix_pool"] = jax.tree.map(
            lambda _: kv_leaf_spec, extra["prefix_pool"])
    tok_spec = P(pod_axes if pod_axes else None, None)
    out_spec = P(pod_axes if pod_axes else None, None)
    led_specs = {k: P() for k in tx.LEDGER_KEYS}
    tel_specs = {k: P(st_ax, None) for k in obs_t.TELEM_KEYS}
    out_specs_l: list = [out_spec, led_specs]
    if return_telemetry:
        out_specs_l.append(tel_specs)
    if return_kv:
        out_specs_l.append(PagedPool(
            kv_leaf_spec, kv_leaf_spec,
            kv_leaf_spec if plan.codec.quantized else None,
            kv_leaf_spec if plan.codec.quantized else None))
    if health is not None:
        out_specs_l.append(P(st_ax, None))
    out_specs = tuple(out_specs_l)

    outs = compat.shard_map(
        body, mesh=topo.mesh,
        in_specs=(sl_specs, manual_only(specs["embed"], manual),
                  manual_only(specs["final_norm"], manual),
                  extra_specs, tok_spec),
        out_specs=out_specs, axis_names=manual, check_vma=False,
    )(staged["stage_layers"], staged["embed"], staged["final_norm"],
      extra, tokens)
    outs = list(outs)
    x_last, ledger = outs[0], outs[1]
    telem = outs[2] if return_telemetry else None
    kv_out = outs[2 + int(return_telemetry)] if return_kv else None
    if health is not None:
        # operand callbacks are legal HERE (outside the manual region):
        # one host delivery of the full [N, T] non-finite profile
        jax.debug.callback(health.note_nonfinite_profile, outs[-1])

    # final norm + unembed of the single output token (prefill-only)
    from jax.sharding import NamedSharding
    x_last = L.rms_norm(x_last[:, None, :].astype(dt), staged["final_norm"],
                        cfg.norm_eps)
    w = staged["embed"].T if ("lm_head" not in staged) else staged["lm_head"]
    logits = L.unembed_logits(x_last, w, scale=cfg.logits_scaling)
    logits = jax.lax.with_sharding_constraint(
        logits, NamedSharding(topo.mesh, P(
            tuple(a for a in topo.batch_axes if a != topo.stage_axis) or None,
            None, None if mtp is not None else topo.tp_axis)))
    ret = [logits[:, 0]]
    if return_ledger:
        ret.append(ledger)
    if return_telemetry:
        ret.append(telem)
    if return_kv:
        ret.append(kv_out)
    return ret[0] if len(ret) == 1 else tuple(ret)
