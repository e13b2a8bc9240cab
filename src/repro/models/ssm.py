"""Mamba2 (SSD — state-space duality) in pure JAX.

The chunked SSD algorithm here is also the oracle for the Pallas kernel in
``repro.kernels.ssd``. State layout per layer:
  dict(conv=[B, K-1, conv_ch], ssd=[B, H, P, N], pos=[B])
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.topology import Topology

Params = Dict[str, Any]


def dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    return d_in, nheads, conv_ch


# ----------------------------------------------------------------- SSD core

def segsum(x: jax.Array) -> jax.Array:
    """x [..., T] -> [..., T, T] with out[i,j] = sum_{k=j+1..i} x[k], -inf for j>i."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, a_log, b, c, d_skip, *, chunk: int,
                init_state: Optional[jax.Array] = None):
    """Chunked SSD scan (Mamba2 alg. 1 "minimal").

    x [B,T,H,P]; dt [B,T,H] (post-softplus); a_log [H]; b,c [B,T,G,N];
    d_skip [H]. Returns y [B,T,H,P], final_state [B,H,P,N].
    """
    bs, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg = h // g
    nc = -(-t // chunk)
    pad = nc * chunk - t
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    a = -jnp.exp(a_log.astype(jnp.float32))  # [H] negative
    da = dt.astype(jnp.float32) * a  # [B,T,H]
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    def rs(z, extra_dims):
        return z.reshape((bs, nc, chunk) + extra_dims)

    xc = rs(xdt, (h, p))
    dac = rs(da, (h,)).transpose(0, 1, 3, 2)  # [B,nc,H,Q]
    bc = rs(b.astype(jnp.float32), (g, n))
    cc = rs(c.astype(jnp.float32), (g, n))
    bh = jnp.repeat(bc, hg, axis=3)  # groups -> heads: [B,nc,Q,H,N]
    ch = jnp.repeat(cc, hg, axis=3)
    # intra-chunk ("diagonal") term
    lmat = jnp.exp(segsum(dac))  # [B,nc,H,Q,Q]
    cb = jnp.einsum("bcqhn,bckhn->bchqk", ch, bh)
    scores = cb * lmat
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", scores, xc)
    # chunk states: decay from position q to the END of the chunk is
    # exp(cumsum(da)[-1] - cumsum(da)[q])  (Mamba2 Alg. 1 `decay_states`)
    dac_cs = jnp.cumsum(dac, axis=-1)  # [B,nc,H,Q]
    decay_out = jnp.exp(dac_cs[..., -1:] - dac_cs)  # [B,nc,H,Q]
    states = jnp.einsum("bchq,bcqhn,bcqhp->bchpn", decay_out, bh, xc)
    # inter-chunk recurrence
    chunk_decay = jnp.exp(dac_cs[..., -1])  # [B,nc,H] total decay
    s0 = jnp.zeros((bs, h, p, n), jnp.float32) if init_state is None else init_state.astype(jnp.float32)

    def scan_body(carry, inp):
        st_prev = carry
        dec, st_c = inp  # dec [B,H], st_c [B,H,P,N]
        st = st_prev * dec[:, :, None, None] + st_c
        return st, st_prev

    dec_t = chunk_decay.transpose(1, 0, 2)  # [nc,B,H]
    st_t = states.transpose(1, 0, 2, 3, 4)  # [nc,B,H,P,N]
    final_state, prev_states = jax.lax.scan(scan_body, s0, (dec_t, st_t))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # [B,nc,H,P,N]
    # inter-chunk ("off-diagonal") output
    state_decay_in = jnp.exp(dac_cs)  # [B,nc,H,Q]
    y_off = jnp.einsum("bcqhn,bchpn,bchq->bcqhp", ch, prev_states, state_decay_in)
    y = (y_diag + y_off).reshape(bs, nc * chunk, h, p)
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :, None]
    if pad:
        y = y[:, :t]
    return y.astype(x.dtype), final_state


def _ssd_pallas(x, dt, a_log, b, c, d_skip, *, chunk: int,
                init_state: Optional[jax.Array] = None):
    """Pallas SSD kernel behind the ``ssm_backend`` knob (interpret mode
    off-TPU, Mosaic on TPU); same signature/semantics as ``ssd_chunked``."""
    from repro.kernels import ops
    return ops.ssd(x, dt, a_log, b, c, d_skip, chunk=chunk,
                   init_state=init_state)


# SSD inner-loop registry (the ssm/hybrid analogue of the attention-backend
# registry): selected per-plan via ``RunConfig.ssm_backend``.
SSD_IMPLS = {"jnp": ssd_chunked, "pallas": _ssd_pallas}
# what ``ssm_backend="auto"`` runs, by platform: the faster on the chip,
# the jnp reference everywhere else (PERF.md has the TPU timings)
SSD_DEFAULT = {"tpu": "pallas"}


def ssd_impl(requested: str, platform: str) -> str:
    """The SSD backend a plan runs: ``requested``, or for "auto" the
    platform's default (``SSD_DEFAULT``)."""
    if requested == "auto":
        return SSD_DEFAULT.get(platform, "jnp")
    return requested


def ssd_decode_step(x, dt, a_log, b, c, d_skip, state):
    """Single-token SSD update. x [B,H,P]; dt [B,H]; b,c [B,G,N]; state [B,H,P,N]."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    dec = jnp.exp(dt.astype(jnp.float32) * a)  # [B,H]
    g = b.shape[1]
    h = x.shape[1]
    bh = jnp.repeat(b.astype(jnp.float32), h // g, axis=1)  # [B,H,N]
    ch = jnp.repeat(c.astype(jnp.float32), h // g, axis=1)
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]  # [B,H,P]
    new_state = state * dec[:, :, None, None] + jnp.einsum("bhp,bhn->bhpn", xdt, bh)
    y = jnp.einsum("bhn,bhpn->bhp", ch, new_state)
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, :, None]
    return y.astype(x.dtype), new_state


# ------------------------------------------------------------------- conv1d

def causal_conv(x, w, bias, *, init_state=None):
    """Depthwise causal conv. x [B,T,C]; w [K,C]; returns (y, last K-1 inputs)."""
    k = w.shape[0]
    if init_state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([init_state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(k))
    tail = xp[:, xp.shape[1] - (k - 1):, :]
    return y + bias, tail


def causal_conv_step(x, w, bias, conv_state):
    """x [B,C]; conv_state [B,K-1,C] -> (y [B,C], new_state)."""
    k = w.shape[0]
    full = jnp.concatenate([conv_state.astype(x.dtype), x[:, None, :]], axis=1)  # [B,K,C]
    y = jnp.einsum("bkc,kc->bc", full, w) + bias
    return y, full[:, 1:]


# ------------------------------------------------------------------- block

def init_block(cfg: ModelConfig, key: jax.Array, nl: int) -> Params:
    d = cfg.d_model
    s = cfg.ssm
    d_in, nheads, conv_ch = dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 8))

    def nrm(k, *shape, std=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    dt_init = jnp.exp(jax.random.uniform(next(ks), (nl, nheads)) *
                      (math.log(s.dt_max) - math.log(s.dt_min)) + math.log(s.dt_min))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    return {
        "ln": jnp.ones((nl, d), dt),
        "in_proj": nrm(next(ks), nl, d, 2 * d_in + 2 * s.n_groups * s.d_state + nheads),
        "conv_w": nrm(next(ks), nl, s.conv_kernel, conv_ch, std=0.2),
        "conv_b": jnp.zeros((nl, conv_ch), dt),
        "a_log": jnp.log(jnp.tile(jnp.arange(1, nheads + 1, dtype=jnp.float32), (nl, 1))),
        "dt_bias": dt_bias.astype(jnp.float32),
        "d_skip": jnp.ones((nl, nheads), jnp.float32),
        "gate_norm": jnp.ones((nl, d_in), dt),
        "out_proj": nrm(next(ks), nl, d_in, d, std=0.02 / math.sqrt(2 * nl)),
    }


def block_specs(cfg: ModelConfig, *, fsdp: bool = True) -> Params:
    FD = "data" if fsdp else None
    d_in, nheads, conv_ch = dims(cfg)
    tp_ok = "model" if d_in % 16 == 0 else None  # head-dim TP when divisible
    return {
        "ln": P(None, None),
        "in_proj": P(None, FD, None),
        "conv_w": P(None, None, None),
        "conv_b": P(None, None),
        "a_log": P(None, None),
        "dt_bias": P(None, None),
        "d_skip": P(None, None),
        "gate_norm": P(None, None),
        "out_proj": P(None, tp_ok, FD),
    }


def block_apply(cfg: ModelConfig, lp: Params, x: jax.Array, *,
                state: Optional[Dict[str, jax.Array]] = None,
                topo: Optional[Topology] = None, ssd_impl: str = "jnp"):
    """Mamba2 block over a (chunk of a) sequence. Returns (y, new_state);
    the block's output joins the residual scaled by
    ``cfg.residual_multiplier``. ``ssd_impl`` picks the SSD inner loop from
    ``SSD_IMPLS``. The projections, conv and gated norm run under the
    ``layer.ssm_proj`` scope, the scan under ``layer.ssd``."""
    b, t, d = x.shape
    s = cfg.ssm
    d_in, nheads, conv_ch = dims(cfg)
    if ssd_impl not in SSD_IMPLS:
        raise KeyError(f"unknown ssm backend {ssd_impl!r}; "
                       f"registered: {sorted(SSD_IMPLS)}")
    with jax.named_scope("layer.ssm_proj"):
        hn = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        zxbcdt = jnp.einsum("btd,de->bte", hn, lp["in_proj"])
        z, xbc, dtv = jnp.split(zxbcdt, [d_in, d_in + conv_ch], axis=-1)
        conv_init = None if state is None else state["conv"]
        xbc, conv_tail = causal_conv(xbc, lp["conv_w"], lp["conv_b"],
                                     init_state=conv_init)
        xbc = jax.nn.silu(xbc)
        xs, bmat, cmat = jnp.split(xbc, [d_in, d_in + s.n_groups * s.d_state],
                                   axis=-1)
        xh = xs.reshape(b, t, nheads, s.head_dim)
        bmat = bmat.reshape(b, t, s.n_groups, s.d_state)
        cmat = cmat.reshape(b, t, s.n_groups, s.d_state)
        dtv = jax.nn.softplus(dtv.astype(jnp.float32) + lp["dt_bias"])
    with jax.named_scope("layer.ssd"):
        ssd_init = None if state is None else state["ssd"]
        y, new_ssd = SSD_IMPLS[ssd_impl](xh, dtv, lp["a_log"], bmat, cmat,
                                         lp["d_skip"], chunk=s.chunk_size,
                                         init_state=ssd_init)
    with jax.named_scope("layer.ssm_proj"):
        y = y.reshape(b, t, d_in)
        y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                       lp["gate_norm"], cfg.norm_eps)
        out = jnp.einsum("bte,ed->btd", y, lp["out_proj"])
        if cfg.residual_multiplier != 1.0:
            out = cfg.residual_multiplier * out
    new_state = {"conv": conv_tail.astype(jnp.float32), "ssd": new_ssd}
    return x + out, new_state


def block_decode(cfg: ModelConfig, lp: Params, x: jax.Array, state):
    """x [B,1,d] single-token decode."""
    b = x.shape[0]
    s = cfg.ssm
    d_in, nheads, conv_ch = dims(cfg)
    hn = L.rms_norm(x[:, 0], lp["ln"], cfg.norm_eps)
    zxbcdt = jnp.einsum("bd,de->be", hn, lp["in_proj"])
    z, xbc, dtv = jnp.split(zxbcdt, [d_in, d_in + conv_ch], axis=-1)
    xbc, conv_state = causal_conv_step(xbc, lp["conv_w"], lp["conv_b"], state["conv"])
    xbc = jax.nn.silu(xbc)
    xs, bmat, cmat = jnp.split(xbc, [d_in, d_in + s.n_groups * s.d_state], axis=-1)
    xh = xs.reshape(b, nheads, s.head_dim)
    bmat = bmat.reshape(b, s.n_groups, s.d_state)
    cmat = cmat.reshape(b, s.n_groups, s.d_state)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + lp["dt_bias"])
    y, new_ssd = ssd_decode_step(xh, dtv, lp["a_log"], bmat, cmat, lp["d_skip"], state["ssd"])
    y = y.reshape(b, d_in)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                   lp["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, lp["out_proj"])
    return x + out[:, None], {"conv": conv_state.astype(jnp.float32), "ssd": new_ssd}


# ---------------------------------------------------------------- LM wiring

def init(cfg: ModelConfig, key: jax.Array) -> Params:
    k1, k2 = jax.random.split(key)
    vpad = L.pad_vocab(cfg.vocab_size)
    dt = jnp.dtype(cfg.dtype)
    return {
        "embed": (jax.random.normal(k1, (vpad, cfg.d_model), jnp.float32) * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "layers": init_block(cfg, k2, cfg.num_layers),
    }


def specs(cfg: ModelConfig, *, fsdp: bool = True) -> Params:
    return {
        "embed": P("model", None),
        "final_norm": P(None),
        "layers": block_specs(cfg, fsdp=fsdp),
    }


def init_state_shape(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    d_in, nheads, conv_ch = dims(cfg)
    nl = cfg.num_layers
    return {
        "conv": jax.ShapeDtypeStruct((nl, batch, s.conv_kernel - 1, conv_ch), jnp.float32),
        "ssd": jax.ShapeDtypeStruct((nl, batch, nheads, s.head_dim, s.d_state), jnp.float32),
        "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def state_specs(cfg: ModelConfig, *, batch_axes) -> Params:
    bt = batch_axes if batch_axes else None
    return {"conv": P(None, bt, None, None), "ssd": P(None, bt, None, None, None),
            "pos": P(bt)}


def init_state(cfg: ModelConfig, batch: int) -> Params:
    sh = init_state_shape(cfg, batch)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in sh.items()}


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            embeds=None, topo=None, impl="xla_flash", remat=True,
            return_cache=False):
    x = L.embed_lookup(params["embed"], tokens, topo=topo)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)

    def body(xc, lp):
        xo, st = block_apply(cfg, lp, xc, topo=topo)
        if topo is not None:
            xo = jax.lax.with_sharding_constraint(
                xo, topo.sharding(topo.batch_axes, None, None))
        return xo, st if return_cache else None

    f = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) if remat else body
    x, sts = jax.lax.scan(f, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed_logits(x, params["embed"].T, topo=topo)
    if return_cache:
        pos = jnp.full((tokens.shape[0],), x.shape[1], jnp.int32)
        return logits, {"conv": sts["conv"], "ssd": sts["ssd"], "pos": pos}
    return logits


def decode_step(cfg: ModelConfig, params: Params, state: Params,
                tokens: jax.Array, *, topo=None, seq_axes=()):
    x = L.embed_lookup(params["embed"], tokens[:, None], topo=topo)

    def body(xc, inp):
        lp, st = inp
        xo, st2 = block_decode(cfg, lp, xc, st)
        return xo, st2

    x, new_st = jax.lax.scan(
        body, x, (params["layers"], {"conv": state["conv"], "ssd": state["ssd"]}))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed_logits(x, params["embed"].T, topo=topo)
    return logits[:, 0], {"conv": new_st["conv"], "ssd": new_st["ssd"],
                          "pos": state["pos"] + 1}
