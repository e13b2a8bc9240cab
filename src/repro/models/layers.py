"""Shared neural layers: norms, RoPE, blocked (flash-style) attention, SwiGLU,
MoE, and topology-aware embedding / unembedding.

All functions are pure; parameters are plain dict pytrees. Attention has three
implementations selected by ``attn_impl``:

- ``"naive"``     materialized-scores oracle (tiny shapes, tests)
- ``"xla_flash"`` blocked online-softmax via ``lax.scan`` over KV blocks —
                  lowers on every backend with bounded memory; used by the
                  dry-run and by default on CPU
- ``"pallas"``    the TPU Pallas kernel in ``repro.kernels`` (chunked prefix
                  attention), validated in interpret mode on CPU
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.models.topology import Topology

DEFAULT_BLOCK_K = 1024


# ---------------------------------------------------------------- norms / rope

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions [...,S] -> cos,sin [...,S, head_dim//2] (float32)."""
    half = head_dim // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B,S,H,D]; cos/sin [B,S,half] or [S,half]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    ).astype(x.dtype)


# ----------------------------------------------------------------- attention

def _gqa_expand(q: jax.Array, num_kv: int) -> jax.Array:
    """[B,S,H,D] -> [B,S,K,G,D] grouped by kv head."""
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, d)


def naive_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal_offset: Optional[int] = 0, scale: Optional[float] = None,
) -> jax.Array:
    """Oracle. q [B,Sq,H,D], k/v [B,Skv,K,D]. ``causal_offset`` is the absolute
    position of q[0] minus the position of k[0] (prefix length). ``None``
    disables masking (bidirectional encoder)."""
    b, sq, h, d = q.shape
    kheads = k.shape[2]
    scale = scale or (1.0 / math.sqrt(d))
    qg = _gqa_expand(q, kheads)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32) * scale
    if causal_offset is not None:
        qpos = jnp.arange(sq)[:, None] + causal_offset
        kpos = jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def flash_attention_xla(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal_offset: Optional[int] = 0, scale: Optional[float] = None,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    """Blocked online-softmax attention (scan over KV blocks). Memory is
    O(Sq * block_k) instead of O(Sq * Skv)."""
    b, sq, h, d = q.shape
    skv, kheads = k.shape[1], k.shape[2]
    if skv <= block_k:
        return naive_attention(q, k, v, causal_offset=causal_offset, scale=scale)
    scale = scale or (1.0 / math.sqrt(d))
    nblk = -(-skv // block_k)
    pad = nblk * block_k - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, block_k, kheads, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, block_k, kheads, d).transpose(1, 0, 2, 3, 4)
    qg = _gqa_expand(q, kheads)  # [B,Sq,K,G,D]
    qpos = jnp.arange(sq)[:, None] + (0 if causal_offset is None else causal_offset)

    def body(carry, blk):
        m, l, acc, j = carry
        kj, vj = blk  # [B,blk,K,D]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj, preferred_element_type=jnp.float32) * scale
        kpos = j * block_k + jnp.arange(block_k)[None, :]
        valid = kpos < skv
        if causal_offset is not None:
            valid = jnp.logical_and(valid, kpos <= qpos)
        s = jnp.where(valid[None, None, None, :, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj, preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new, j + 1), None

    g = h // kheads
    m0 = jnp.full((b, kheads, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kheads, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kheads, g, sq, d), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, jnp.int32(0)), (kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def attention(q, k, v, *, causal_offset=0, scale=None, impl="xla_flash", block_k=DEFAULT_BLOCK_K):
    if impl == "naive":
        return naive_attention(q, k, v, causal_offset=causal_offset, scale=scale)
    if impl == "xla_flash":
        return flash_attention_xla(q, k, v, causal_offset=causal_offset, scale=scale, block_k=block_k)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.chunk_attention(q, k, v, causal_offset=causal_offset, scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")


# --------------------------------------------------- distributed decode attn

def decode_attention_local(q, k, v, kv_len, *, scale=None):
    """One-token decode against a cache. q [B,1,H,D]; k/v [B,Smax,K,D];
    kv_len [B] valid lengths. Returns ([B,1,H,D], lse [B,H], m [B,H])."""
    b, _, h, d = q.shape
    kheads = k.shape[2]
    scale = scale or (1.0 / math.sqrt(d))
    qg = _gqa_expand(q, kheads)[:, 0]  # [B,K,G,D]
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(k.shape[1])[None, :] < kv_len[:, None]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    l = p.sum(axis=-1)
    pv = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return pv.reshape(b, 1, h, d), l.reshape(b, h), m_safe.reshape(b, h)


def decode_attention_seqsharded(q, k, v, kv_len, *, axis_name, scale=None):
    """Flash-decoding across chips: the cache's SEQ dim is sharded over
    ``axis_name``; combine partial softmax stats with psums. Must run inside
    shard_map. k/v are the LOCAL seq shards; kv_len is the GLOBAL length."""
    b, _, h, d = q.shape
    s_loc = k.shape[1]
    idx = jax.lax.axis_index(axis_name)
    start = idx * s_loc
    local_len = jnp.clip(kv_len - start, 0, s_loc)
    pv, l, m = decode_attention_local(q, k, v, local_len, scale=scale)
    # combine: global max, rescale
    m_glob = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_glob)
    # fully-masked local shard -> l == 0, pv == 0; corr finite
    l_glob = jax.lax.psum(l * corr, axis_name)
    pv_glob = jax.lax.psum(pv * corr[:, None, :, None], axis_name)
    return (pv_glob / jnp.maximum(l_glob, 1e-30)[:, None, :, None]).astype(q.dtype)


# ------------------------------------------------------------------- mlp/moe

def swiglu(params, x):
    g = jnp.einsum("bsd,df->bsf", x, params["wg"])
    u = jnp.einsum("bsd,df->bsf", x, params["wu"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, params["wd"])


def moe_layer(params, x, *, num_experts: int, top_k: int, capacity_factor: float,
              topo: Optional[Topology] = None, num_real: int = 0,
              ep_axis=None, ep_axes=None):
    """Token-choice top-k MoE with per-example capacity-bounded sort dispatch.

    Dispatch is vmapped over the batch dim so token sorts never cross data
    shards. Three layouts:
      - default: expert FFNs TENSOR-parallel over the TP axis;
      - ``ep_axis``: EXPERT-parallel via GSPMD — the dispatched [B,E,cap,*]
        tensors are E-sharded so expert FFNs are chip-local and the only
        collective is the [B,S,d] psum at combine (experts zero-padded to
        the axis size, ``num_real`` masks their router logits — bit-exact);
      - ``ep_axes``: MANUAL expert parallelism (DESIGN.md §3.6) — the
        expert params arrive pre-sliced (``wg.shape[0]`` local experts per
        chip, kv-major over the named manual mesh axes); the full dispatch
        is computed replicated, MY expert rows are sliced out by axis index,
        and the returned [B,S,d] is the PARTIAL combine — the caller psums.
    x: [B,S,d]. params: router [d,E], wg/wu [E,d,f], wd [E,f,d].
    """
    b, s, d = x.shape
    e, k = num_experts, top_k
    n_real = num_real or e
    cap = max(int(math.ceil(s * k / n_real * capacity_factor)), k)
    logits = jnp.einsum("bsd,de->bse", x, params["router"], preferred_element_type=jnp.float32)
    if n_real < e:  # padded experts are never routable
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        logits = jnp.where(iota < n_real, logits, -1e30)
    weights, choices = jax.lax.top_k(logits, k)  # [B,S,k]
    weights = jax.nn.softmax(weights, axis=-1)

    def dispatch_one(xe, choice, w):
        # xe [S,d], choice [S,k], w [S,k]
        flat_e = choice.reshape(-1)  # [S*k]
        flat_tok = jnp.repeat(jnp.arange(s), k)
        flat_w = w.reshape(-1)
        # position of each (token,slot) within its expert, by token order
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        # rank within equal-expert runs
        same = jnp.concatenate([jnp.array([0], sorted_e.dtype), (sorted_e[1:] == sorted_e[:-1]).astype(sorted_e.dtype)])
        seg_start = jnp.where(same == 0, jnp.arange(s * k), 0)
        run_start = jax.lax.associative_scan(jnp.maximum, seg_start)
        rank = jnp.arange(s * k) - run_start
        # scatter token ids into [E, cap]
        keep = rank < cap
        e_idx = jnp.where(keep, sorted_e, e)  # drops -> row e (discarded)
        r_idx = jnp.where(keep, rank, 0)
        slots_tok = jnp.zeros((e + 1, cap), jnp.int32).at[e_idx, r_idx].set(
            flat_tok[order].astype(jnp.int32), mode="drop")
        slots_valid = jnp.zeros((e + 1, cap), jnp.bool_).at[e_idx, r_idx].set(True, mode="drop")
        slots_w = jnp.zeros((e + 1, cap), jnp.float32).at[e_idx, r_idx].set(flat_w[order], mode="drop")
        xd = xe[slots_tok[:e]] * slots_valid[:e, :, None].astype(xe.dtype)  # [E,cap,d]
        return xd, slots_tok[:e], slots_valid[:e], slots_w[:e]

    xd, tok, valid, wgt = jax.vmap(dispatch_one)(x, choices, weights)  # [B,E,cap,...]
    if ep_axes is not None:
        # manual EP: slice MY contiguous expert block (kv-major flat index
        # over the named axes, matching the P(..., axes, ...) layout)
        e_loc = params["wg"].shape[0]
        idx = jnp.int32(0)
        for a in ep_axes:
            idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        off = idx * e_loc
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, e_loc, axis=1)
        xd, tok, valid, wgt = sl(xd), sl(tok), sl(valid), sl(wgt)
    if ep_axis is not None:
        ep = P(None, ep_axis, None, None)
        xd = jax.lax.with_sharding_constraint(xd, ep)
    g = jnp.einsum("becd,edf->becf", xd, params["wg"])
    u = jnp.einsum("becd,edf->becf", xd, params["wu"])
    y = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, params["wd"])
    if ep_axis is not None:
        y = jax.lax.with_sharding_constraint(y, P(None, ep_axis, None, None))
    if topo is not None:
        y = jax.lax.with_sharding_constraint(
            y, topo.sharding(topo.batch_axes, None, None, None))
    y = y * (wgt * valid)[..., None].astype(y.dtype)

    def combine_one(ye, tok_e, valid_e):
        out = jnp.zeros((s, d), ye.dtype)
        return out.at[tok_e.reshape(-1)].add(
            ye.reshape(-1, d) * valid_e.reshape(-1, 1).astype(ye.dtype))

    return jax.vmap(combine_one)(y, tok, valid).astype(x.dtype)


def held_moe(params, x, *, top_k: int, held_first: int = 0,
             layer: Optional[int] = None):
    """Dropless token-choice top-k MoE over the experts this chip holds.

    The router scores every expert (``router`` [d, E]) and each token keeps
    its top ``top_k``, gated by a softmax over the k picked logits. The
    (token, pick) pairs whose expert lies in ``[held_first, held_first +
    Eh)`` (``Eh`` = the experts in ``e_wgu`` [Eh, d, 2f] / ``e_wd`` [Eh, f,
    d]) are sorted by expert and run as one grouped matmul for gate and up
    and one for down (``jax.lax.ragged_dot``): no capacity, nothing
    dropped, no padded slots. The other pairs belong to experts another
    chip holds and add nothing here, so the result is this chip's part of
    the layer (expert parallelism without its exchange).

    With ``layer`` (static) the expert weights are stacked over layers,
    [L, Eh, ...], and this layer's experts are read in place as groups
    ``[layer * Eh, (layer + 1) * Eh)`` of the stack: a grouped matmul takes
    whole operands, so a slice of the stack would be a copy.

    x [..., d]. Returns (out [..., d], held pairs as an int32 scalar)."""
    shape, d = x.shape, x.shape[-1]
    xt = x.reshape(-1, d)
    wgu, wd = params["e_wgu"], params["e_wd"]
    t, eh = xt.shape[0], wgu.shape[-3]
    first = 0
    if layer is not None:
        first = layer * eh
        wgu = wgu.reshape((-1,) + wgu.shape[-2:])
        wd = wd.reshape((-1,) + wd.shape[-2:])
    groups = wgu.shape[0]
    with jax.named_scope("layer.moe_route"):
        logits = jnp.einsum("td,de->te", xt, params["router"],
                            preferred_element_type=jnp.float32)
        top, picked = jax.lax.top_k(logits, top_k)               # [T, k]
        gates = jax.nn.softmax(top, axis=-1)
        local = picked - held_first
        held = (local >= 0) & (local < eh)
        # sort the pairs by held expert; pairs of absent experts go last,
        # past every group, where no matmul reads them (their rows of the
        # product are zeros)
        group = jnp.where(held, first + local, groups).reshape(-1)  # [T*k]
        order = jnp.argsort(group, stable=True)
        # rows per group, by a one-hot count
        sizes = jnp.sum(group[:, None] == jnp.arange(groups), axis=0,
                        dtype=jnp.int32)
        rows = jnp.take(xt, order // top_k, axis=0, mode="clip")  # [T*k, d]
    with jax.named_scope("layer.moe_experts"):
        gu = jax.lax.ragged_dot(rows, wgu, sizes)
        g, u = jnp.split(gu, 2, axis=-1)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, wd, sizes)
    with jax.named_scope("layer.moe_combine"):
        # each token's k rows, found by the inverse permutation (a sort),
        # gated and summed one pick at a time: no [T*k, d] copy in (token,
        # pick) order. The absent experts' rows are zeros and their gates
        # are dropped.
        at = jnp.argsort(order).reshape(t, top_k)
        w = jnp.where(held, gates, 0.0)
        out = jnp.zeros((t, d), jnp.float32)
        for j in range(top_k):
            out = out + w[:, j, None] * jnp.take(
                y, at[:, j], axis=0, mode="clip")
        out = out.astype(x.dtype)
    return out.reshape(shape), jnp.sum(held, dtype=jnp.int32)


# --------------------------------------------------------- embed / unembed

def pad_vocab(v: int, multiple: int = 128) -> int:
    return -(-v // multiple) * multiple


def embed_lookup(table: jax.Array, tokens: jax.Array, *, topo: Optional[Topology] = None):
    """table [Vpad, d] (vocab-sharded over TP), tokens [B,S] int32."""
    if topo is None or topo.tp_size == 1:
        return jnp.take(table, tokens, axis=0)
    vpad, dm = table.shape
    tp = topo.tp_size

    def local(tab, tok):
        vloc = tab.shape[0]
        off = jax.lax.axis_index(topo.tp_axis) * vloc
        li = tok - off
        ok = (li >= 0) & (li < vloc)
        vec = jnp.take(tab, jnp.clip(li, 0, vloc - 1), axis=0)
        vec = jnp.where(ok[..., None], vec, 0)
        return jax.lax.psum(vec, topo.tp_axis)

    return compat.shard_map(
        local, mesh=topo.mesh,
        in_specs=(P(topo.tp_axis, None), topo.batch_spec(None)),
        out_specs=topo.batch_spec(None, None),
    )(table, tokens)


def unembed_logits(x: jax.Array, w: jax.Array, *, topo: Optional[Topology] = None,
                   scale: float = 1.0):
    """x [B,S,d] @ w [d,Vpad] -> fp32 logits, vocab-sharded over TP."""
    logits = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=jnp.float32)
    if scale != 1.0:
        logits = logits / scale
    if topo is not None:
        logits = jax.lax.with_sharding_constraint(
            logits, topo.sharding(topo.batch_axes, None, topo.tp_axis))
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array, vocab_size: int):
    """Fused CE over (possibly padded + vocab-sharded) logits.
    logits [B,S,Vpad] fp32; labels [B,S]. Pads masked to -inf via iota compare.
    Returns mean loss."""
    vpad = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    logits = jnp.where(iota < vocab_size, logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    return jnp.mean(lse - true_logit)
