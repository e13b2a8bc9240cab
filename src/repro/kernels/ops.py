"""Jit'd public wrappers around the Pallas kernels.

Handles: head-dim padding to the 128-lane width, KV padding to block
multiples, and the token-major <-> head-major transposes at the kernel
boundary (callers pass ``[B, C, H, D]``; the kernels tile ``[B, H, C, D]``,
see ``chunk_attn``). On a TPU backend every kernel compiles through Mosaic;
off-TPU (CPU tests) they run in Pallas interpret mode.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import chunk_attn as _ca
from repro.kernels import decode_attn as _da
from repro.kernels import ssd as _ssd

LANE = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ------------------------------------------------------- launch counting
# Kernel launches are counted from the TRACED program, not at run time: each
# jitted wrapper below appears once per call site in the jaxpr, and a call
# site inside a ``lax.scan`` body launches once per iteration. Nothing is
# staged into the program, so counting costs no host callback (and none
# lands inside a manual shard_map region).

KERNEL_TAGS = ("chunk_attention", "pool_attention", "pool_attention_paged",
               "ssd", "decode_attention")


def _count_jaxpr(jaxpr, mult: int, out: dict) -> None:
    from jax.extend import core as jex_core
    for eqn in jaxpr.eqns:
        name = eqn.params.get("name")
        if eqn.primitive.name in ("jit", "pjit") and name in KERNEL_TAGS:
            out["count"] += mult
            out[name] = out.get(name, 0) + mult
            continue
        if eqn.primitive.name == "while":
            raise ValueError("count_launches: a while loop has no static "
                             "trip count")
        inner = mult * int(eqn.params.get("length", 1))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    _count_jaxpr(sub.jaxpr, inner, out)
                elif isinstance(sub, jex_core.Jaxpr):
                    _count_jaxpr(sub, inner, out)


def count_launches(fn, *args, **kwargs) -> dict:
    """Pallas kernel launches one call of ``fn(*args, **kwargs)`` issues:
    kernel call sites in the traced program, each multiplied by the trip
    counts of the scans around it (launches per tick x ticks for the
    pipeline). Returns ``{"count": total, <tag>: n, ...}`` with one key per
    kernel tag in ``KERNEL_TAGS`` that launches at least once. ``fn`` is
    traced, not run."""
    out = {"count": 0}
    _count_jaxpr(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr, 1, out)
    return out


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _heads_major(x: jax.Array) -> jax.Array:
    """[..., T, H, D] <-> [..., H, T, D]."""
    return jnp.swapaxes(x, -3, -2)


def _state_out(m, l, acc, d: int):
    """Kernel state rows [B, H, 1, C] + acc [B, H, C, Dp] -> the wrappers'
    (m, l) [B, H, C] + token-major acc [B, C, H, D]."""
    return m[:, :, 0], l[:, :, 0], _heads_major(acc[..., :d])


@partial(jax.jit, static_argnames=("causal_offset", "scale", "block_q",
                                   "block_k", "return_state"))
def chunk_attention(q, k, v, *, causal_offset: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = _ca.DEFAULT_BLOCK_Q,
                    block_k: int = _ca.DEFAULT_BLOCK_K,
                    return_state: bool = False,
                    k_scale=None, v_scale=None):
    """Chunked-prefill flash attention (MOCAP hot spot). See chunk_attn.py.

    q [B, C, H, D]; k, v [B, T, KVH, D]. ``return_state=True`` also returns
    the fp32 online-softmax residuals ``(m, l) [B, H, C]`` and the
    unnormalized fp32 accumulator ``acc [B, C, H, D]`` so partial results
    combine across KV sources at full precision — used by the pipeline's
    "pallas" attention backend (core.attention).

    ``k_scale``/``v_scale`` [B, T, KVH]: k/v are quantized KV-page payloads
    (``repro.kvstore``, one scale row per kv token) and the kernel
    dequantizes after the block load.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    t, c = k.shape[1], q.shape[1]
    bq = min(block_q, c)
    while c % bq:
        bq //= 2
    bk = min(block_k, t)
    qp = _heads_major(_pad_to(q, 3, LANE))
    kp = _heads_major(_pad_to(_pad_to(k, 3, LANE), 1, bk))
    vp = _heads_major(_pad_to(_pad_to(v, 3, LANE), 1, bk))
    if k_scale is not None:  # pad rows are masked via kv_len
        k_scale = jnp.swapaxes(_pad_to(k_scale, 1, bk), 1, 2)[..., None]
        v_scale = jnp.swapaxes(_pad_to(v_scale, 1, bk), 1, 2)[..., None]
    res = _ca.chunk_attention_pallas(
        qp, kp, vp, causal_offset=causal_offset, scale=scale, kv_len=t,
        block_q=bq, block_k=bk, interpret=not _on_tpu(),
        return_state=return_state, k_scale=k_scale, v_scale=v_scale)
    if return_state:
        out, m, l, acc = res
        return (_heads_major(out[..., :d]),) + _state_out(m, l, acc, d)
    return _heads_major(res[..., :d])


@partial(jax.jit, static_argnames=("scale", "block_q", "block_k"))
def pool_attention(q, k, v, valid, *, scale: Optional[float] = None,
                   block_q: int = _ca.DEFAULT_BLOCK_Q,
                   block_k: int = _ca.DEFAULT_BLOCK_K,
                   k_scale=None, v_scale=None):
    """Batched pool attention (MOCAP pool scan, single launch). See
    ``chunk_attn.pool_attention_pallas``.

    q [B, C, H, D]; k, v [S, B, T, KVH, D] — a stack of S stored chunks,
    each fully visible; ``valid`` [S] bool/int gates slots (False slot ==
    identity-state contribution, exactly). ``k_scale``/``v_scale``
    [S, B, T, KVH]: quantized page payloads, dequantized after the block
    load. Returns the fp32 online-softmax state ``(m, l) [B, H, C]`` +
    unnormalized ``acc [B, C, H, D]`` for the caller's combine chain —
    the launch count is O(1) in pool depth instead of O(slots)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    t, c = k.shape[2], q.shape[1]
    bq = min(block_q, c)
    while c % bq:
        bq //= 2
    bk = min(block_k, t)
    qp = _heads_major(_pad_to(q, 3, LANE))
    kp = _heads_major(_pad_to(_pad_to(k, 4, LANE), 2, bk))
    vp = _heads_major(_pad_to(_pad_to(v, 4, LANE), 2, bk))
    if k_scale is not None:  # pad rows are masked via kv_len
        k_scale = jnp.swapaxes(_pad_to(k_scale, 2, bk), 2, 3)[..., None]
        v_scale = jnp.swapaxes(_pad_to(v_scale, 2, bk), 2, 3)[..., None]
    m, l, acc = _ca.pool_attention_pallas(
        qp, kp, vp, valid, scale=scale, kv_len=t, block_q=bq, block_k=bk,
        interpret=not _on_tpu(), k_scale=k_scale, v_scale=v_scale)
    return _state_out(m, l, acc, d)


@partial(jax.jit, static_argnames=("ppc", "scale", "kv_len", "block_q"))
def pool_attention_paged(q, k_pages, v_pages, handles, valid, *, ppc: int,
                         scale: Optional[float] = None,
                         kv_len: Optional[int] = None,
                         block_q: int = _ca.DEFAULT_BLOCK_Q,
                         k_scale=None, v_scale=None):
    """Ragged paged pool attention (MOCAP pool scan, single launch, ZERO
    gather). See ``chunk_attn.pool_attention_paged_pallas``.

    q [B, C, H, D]; ``k_pages``/``v_pages`` [P, B, KVH, pt, D] — the
    head-major page store's layer slice in STORAGE dtype, read in place
    (``pl.ANY``); ``handles`` [S*ppc] int32 flattened page-handle rows;
    ``valid`` [S] bool/int per-slot occupancy (both scalar-prefetched into
    SMEM). ``k_scale``/``v_scale`` [P, B, KVH, 1, 1] fp32: the pool's
    per-page scales, dequantized on the VMEM landing buffer. ``kv_len`` <
    ppc*pt handles a partial last page. Returns the
    fp32 online-softmax state like ``pool_attention`` — one launch per
    (layer, tick), O(1) in pool depth, and HBM traffic O(resident pages),
    not O(padded pool)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    c = q.shape[1]
    bq = min(block_q, c)
    while c % bq:
        bq //= 2
    qp = _heads_major(_pad_to(q, 3, LANE))
    # lane-pad the PAGE STORE only when head_dim is off-lane (a one-off
    # [P, ...] copy — real configs keep hd a multiple of 128 and pass
    # through untouched; there is never an [S, B, C, KVH, D] gather)
    kp = _pad_to(k_pages, 4, LANE)
    vp = _pad_to(v_pages, 4, LANE)
    if k_scale is not None:
        k_scale = k_scale.reshape(k_scale.shape[0], -1)  # [P, B*KVH]
        v_scale = v_scale.reshape(v_scale.shape[0], -1)
        assert k_scale.shape[1] == q.shape[0] * k_pages.shape[2], \
            k_scale.shape
    m, l, acc = _ca.pool_attention_paged_pallas(
        qp, kp, vp, handles, valid, ppc=ppc, scale=scale, kv_len=kv_len,
        block_q=bq, interpret=not _on_tpu(), k_scale=k_scale,
        v_scale=v_scale)
    return _state_out(m, l, acc, d)


def full_attention(q, k, v, *, scale: Optional[float] = None,
                   block_q: int = _ca.DEFAULT_BLOCK_Q,
                   block_k: int = _ca.DEFAULT_BLOCK_K):
    """Non-causal (full-visibility) wrapper around ``chunk_attention``:
    every query attends over every key — the encdec CROSS-attention shape
    (decoder chunk vs the whole encoder output) and bidirectional encoders.
    Implemented as a causal offset past the last key, so padded kv rows are
    still masked by ``kv_len`` inside the kernel."""
    return chunk_attention(q, k, v, causal_offset=int(k.shape[1]),
                           scale=scale, block_q=block_q, block_k=block_k)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a_log, b, c, d_skip, *, chunk: int = 128, init_state=None,
        interpret: Optional[bool] = None):
    """Mamba2 chunked SSD scan. See ssd.py."""
    t = x.shape[1]
    ck = min(chunk, t)
    while t % ck:
        ck //= 2
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _ssd.ssd_pallas(x, dt, a_log, b, c, d_skip, chunk=ck,
                           init_state=init_state, interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "block_s"))
def decode_attention(q, k, v, kv_len, *, scale: Optional[float] = None,
                     block_s: int = _da.DEFAULT_BLOCK_S):
    """Flash-decode (one token vs KV cache). See decode_attn.py."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qp = _pad_to(q, 2, LANE)
    kp = _pad_to(k, 3, LANE)
    vp = _pad_to(v, 3, LANE)
    s_len = kp.shape[1]
    bs = min(block_s, s_len)
    while s_len % bs:
        bs //= 2
    out = _da.decode_attention_pallas(qp, kp, vp, kv_len, scale=scale,
                                      block_s=bs, interpret=not _on_tpu())
    return out[..., :d]
