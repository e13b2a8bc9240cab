"""Online-softmax attention state + the pluggable backend registry.

MOCAP's attention for one chunk is a COMBINE of partial flash-attention
states over several KV sources (own pool slots, remote fetch/qship partials,
the causal self block). This module owns the state algebra — ``attn_init /
attn_combine / attn_finish`` with state ``(m, l, acc)``: running max,
denominator, unnormalized accumulator, all fp32 — and a registry of
*backends* that compute one partial state:

- ``jnp``    — the pure-jnp streaming reference (``attn_update``): einsum
               scores, masked softmax, accumulate. Runs everywhere; the
               numerics oracle.
- ``pallas`` — the WaferLLM-style flash kernel ``kernels.ops.chunk_attention``
               with ``return_state=True``: the kernel returns (m, l) plus
               the UNNORMALIZED fp32 accumulator straight from VMEM scratch,
               so kernel results join the same combine chain at full
               precision even when the normalized output dtype is bf16
               (interpret mode off-TPU, compiled on TPU).

A backend supplies two block kinds (DESIGN.md §2.3):
- ``self_block``  — causal attention of the chunk over its own fresh KV.
- ``chunk_block`` — full-visibility attention over ONE stored chunk's KV,
  gated by a traced ``valid`` scalar (the chunk participates iff its index is
  below the consumer's phase). Gating must be exact: an invalid chunk
  contributes the identity state (m=-inf, l=0, acc=0).

Stored chunks arrive ENCODED from the KV page store (``repro.kvstore``):
``chunk_block_q`` takes the page payload plus per-head scales and owns the
dequant-on-read — the jnp reference multiplies the scales out before its
block update; the pallas backend hands payload + scales straight to the
kernel, which dequantizes in its epilogue (quantized bytes cross HBM).

Backends are selected per-plan via ``RunConfig.attn_backend`` ->
``PipelinePlan.attn_backend``, and may be MIXED per source:
``RunConfig.pool_backend`` routes the pool-sourced partials (own-pool scan,
fetch/qship) separately from the self block. A backend that advertises
``batched_pool`` additionally fuses the whole pool scan into one
``pool_block`` call — the pallas slot-grid kernel
(``kernels.ops.pool_attention``) makes that a SINGLE launch per (layer,
tick), O(1) in pool depth, vs one ``chunk_attention`` launch per occupied
slot in the per-slot reference order. A backend that advertises
``paged_pool`` (``paged``) goes further: ``pool_scan`` hands it the
page-handle rows themselves and ``pool_block_paged`` launches the ragged
paged kernel (``kernels.ops.pool_attention_paged``) straight off the page
store — no gather, no dense slot stack, HBM traffic O(resident pages).
Registration is open for follow-ons (TPU-native qship kernel — ROADMAP).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvstore import pages as kvpages
from repro.kvstore import quant as kvquant

NEG_INF = float(-1e30)  # finite -inf stand-in: keeps masked softmax NaN-free

State = Tuple[jax.Array, jax.Array, jax.Array]  # (m, l, acc)


# ======================================================= state algebra (fp32)

def group_queries(q: jax.Array, kvh: int) -> jax.Array:
    """[B,C,H,D] -> [B,C,K,G,D] (query heads grouped per kv head)."""
    b, c, h, d = q.shape
    return q.reshape(b, c, kvh, h // kvh, d)


def attn_init(b: int, c: int, kvh: int, g: int, d: int) -> State:
    return (jnp.full((b, kvh, g, c), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, c), jnp.float32),
            jnp.zeros((b, kvh, g, c, d), jnp.float32))


def attn_update(qg, k, v, mask, scale, st: State) -> State:
    """One online-softmax block update (the jnp reference path).
    qg [B,C,K,G,D]; k,v [B,Ck,K,D]; mask broadcastable to [B,K,G,C,Ck];
    st = (m, l, acc) with m,l [B,K,G,C], acc [B,K,G,C,D]."""
    m, l, acc = st
    s = jnp.einsum("bckgd,bskd->bkgcs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # fully-masked rows: exp against a safe max so p == 0 (not exp(0) == 1)
    m_safe = jnp.where(m_new < NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    corr = jnp.exp(m - m_safe)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bkgcs,bskd->bkgcd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def attn_combine(st1: State, st2: State) -> State:
    m1, l1, a1 = st1
    m2, l2, a2 = st2
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(m < NEG_INF / 2, 0.0, m)
    c1, c2 = jnp.exp(m1 - m_safe), jnp.exp(m2 - m_safe)
    return m, l1 * c1 + l2 * c2, a1 * c1[..., None] + a2 * c2[..., None]


def attn_finish(st: State, q_dtype) -> jax.Array:
    m, l, acc = st
    b, kvh, g, c, d = acc.shape
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, kvh * g, d).astype(q_dtype)


# =========================================================== backend registry

class AttentionBackend:
    """One way to compute a partial attention state. Subclasses implement
    ``self_block`` (causal, within-chunk) and ``chunk_block`` (one stored
    chunk, full visibility, gated by a traced ``valid`` scalar); the combine
    chain and finish are shared module-level functions.

    ``batched_pool`` advertises a fused multi-slot path: when True,
    ``pool_scan`` gathers every visited slot's pages in one shot and calls
    ``pool_block`` ONCE (the pallas backend turns that into a single kernel
    launch); when False the scan stays per-slot (the jnp reference order)."""

    name = "abstract"
    batched_pool = False

    def self_block(self, qg, k, v, scale, st: State) -> State:
        raise NotImplementedError

    def chunk_block(self, qg, k, v, valid, scale, st: State) -> State:
        raise NotImplementedError

    def pool_block(self, qg, kq, vq, ks, vs, valid, scale,
                   st: State) -> State:
        """Attention over a STACK of stored chunks: payloads ``kq``/``vq``
        [S, B, Ck, K, D], per-page scales ``ks``/``vs`` [S, ppc, B, 1, K, 1]
        (None for a passthrough codec), ``valid`` [S] bool (traced). The
        base implementation is the per-slot ``lax.scan`` through
        ``chunk_block_q`` — slot order preserved, numerically identical to
        the unbatched pool scan; backends with a fused multi-slot kernel
        (pallas) override it."""
        def body(carry, xs):
            if ks is None:
                kqi, vqi, vi = xs
                ksi = vsi = None
            else:
                kqi, vqi, ksi, vsi, vi = xs
            return self.chunk_block_q(qg, kqi, vqi, ksi, vsi, vi, scale,
                                      carry), None

        xs = (kq, vq, valid) if ks is None else (kq, vq, ks, vs, valid)
        st, _ = jax.lax.scan(body, st, xs)
        return st

    def chunk_block_q(self, qg, kq, vq, k_scale, v_scale, valid, scale,
                      st: State) -> State:
        """``chunk_block`` over an ENCODED stored chunk: KV-page payload
        [B, Ck, K, D] + per-PAGE scales [ppc, B, 1, K, 1] from
        ``repro.kvstore``. Default: dequantize on read, then the plain
        block. Backends that can consume the payload directly (pallas)
        override this."""
        if k_scale is not None:
            pt = kq.shape[1] // k_scale.shape[0]
            k_scale = kvquant.expand_page_scale(k_scale, pt)  # [B, Ck, K, 1]
            v_scale = kvquant.expand_page_scale(v_scale, pt)
        k = kvquant.decode(kq, k_scale, qg.dtype)
        v = kvquant.decode(vq, v_scale, qg.dtype)
        return self.chunk_block(qg, k, v, valid, scale, st)


class JnpBackend(AttentionBackend):
    """Pure-jnp streaming reference (runs on any jax backend)."""

    name = "jnp"

    def self_block(self, qg, k, v, scale, st: State) -> State:
        c = qg.shape[1]
        tri = jnp.tril(jnp.ones((c, c), bool))
        return attn_update(qg, k, v, tri[None, None, None], scale, st)

    def chunk_block(self, qg, k, v, valid, scale, st: State) -> State:
        mask = valid[None, None, None, None, None]  # whole chunk on/off
        return attn_update(qg, k, v, mask, scale, st)


class PallasBackend(AttentionBackend):
    """Flash kernel backend: ``kernels.ops.chunk_attention`` computes the
    block, ``return_state`` exposes (m, l) plus the fp32 accumulator from
    VMEM scratch (NOT reconstructed from the dtype-rounded normalized
    output) so the result joins the combine chain at full precision.
    Interpret mode off-TPU; real Mosaic lowering on TPU."""

    name = "pallas"
    batched_pool = True

    @staticmethod
    def _to_state(m, l, acc, kvh: int) -> State:
        b, c, h, d = acc.shape
        g = h // kvh
        acc = acc.reshape(b, c, kvh, g, d).transpose(0, 2, 3, 1, 4)
        return m.reshape(b, kvh, g, c), l.reshape(b, kvh, g, c), acc

    def _kernel_state(self, qg, k, v, scale, causal_offset: int,
                      k_scale=None, v_scale=None) -> State:
        from repro.kernels import ops
        b, c, kvh, g, d = qg.shape
        q = qg.reshape(b, c, kvh * g, d)
        _, m, l, acc = ops.chunk_attention(
            q, k, v, causal_offset=causal_offset, scale=float(scale),
            return_state=True, k_scale=k_scale, v_scale=v_scale)
        return self._to_state(m, l, acc, kvh)

    @staticmethod
    def _gate(s2: State, valid) -> State:
        return (jnp.where(valid, s2[0], NEG_INF),
                jnp.where(valid, s2[1], 0.0),
                jnp.where(valid, s2[2], 0.0))

    def self_block(self, qg, k, v, scale, st: State) -> State:
        return attn_combine(st, self._kernel_state(qg, k, v, scale, 0))

    def chunk_block(self, qg, k, v, valid, scale, st: State) -> State:
        # full visibility: every query sees all Ck keys (offset >= Ck)
        s2 = self._kernel_state(qg, k, v, scale, int(k.shape[1]))
        return attn_combine(st, self._gate(s2, valid))

    def chunk_block_q(self, qg, kq, vq, k_scale, v_scale, valid, scale,
                      st: State) -> State:
        """Quantized pages go straight into the kernel: the dequant epilogue
        (chunk_attn.py) multiplies the per-token scale rows after the block
        load, so only payload bytes cross HBM."""
        if k_scale is None:
            return self.chunk_block(qg, kq, vq, valid, scale, st)
        pt = kq.shape[1] // k_scale.shape[0]
        ksc = kvquant.expand_page_scale(k_scale, pt)[..., 0]  # [B, Ck, K]
        vsc = kvquant.expand_page_scale(v_scale, pt)[..., 0]
        s2 = self._kernel_state(qg, kq, vq, scale, int(kq.shape[1]),
                                ksc, vsc)
        return attn_combine(st, self._gate(s2, valid))

    def pool_block(self, qg, kq, vq, ks, vs, valid, scale,
                   st: State) -> State:
        """Fused slot-grid kernel: ONE ``kernels.ops.pool_attention`` launch
        covers every stored chunk (grid = B x H x q-blocks x slots x
        kv-blocks), with per-slot validity gating and the quantized-page
        dequant epilogue inside the kernel — launch count per pool scan is
        O(1) in pool depth instead of O(slots)."""
        if not self.batched_pool:  # flag is authoritative: per-slot order
            return super().pool_block(qg, kq, vq, ks, vs, valid, scale, st)
        from repro.kernels import ops
        b, c, kvh, g, d = qg.shape
        q = qg.reshape(b, c, kvh * g, d)
        ksc = vsc = None
        if ks is not None:
            # per-page scales [S, ppc, B, 1, K, 1] -> per-token rows with a
            # leading slot axis [S, B, Ck, K] (pages axis leading for
            # expand_page_scale, slot axis rides in the batch dims)
            pt = kq.shape[2] // ks.shape[1]
            ksc = kvquant.expand_page_scale(jnp.moveaxis(ks, 1, 0), pt)[..., 0]
            vsc = kvquant.expand_page_scale(jnp.moveaxis(vs, 1, 0), pt)[..., 0]
        m, l, acc = ops.pool_attention(q, kq, vq, valid, scale=float(scale),
                                       k_scale=ksc, v_scale=vsc)
        return attn_combine(st, self._to_state(m, l, acc, kvh))


class PagedPallasBackend(PallasBackend):
    """Ragged paged pool backend (DESIGN.md §3.7): pool-sourced partials go
    through ``kernels.ops.pool_attention_paged`` — the kernel reads KV pages
    in place from the page store via scalar-prefetched handle rows, with
    double-buffered async copies and dequant on the VMEM landing buffer. No
    ``gather_chunks`` call, no dense ``[S, B, C, KVH, D]`` stack in HBM:
    pool HBM traffic is O(resident pages). Self/chunk blocks inherit the
    pallas flash kernel."""

    name = "paged"
    paged_pool = True  # pool_scan feeds page tables, not gathered stacks

    def pool_block_paged(self, qg, pool_l, page_rows, valid, scale,
                         st: State) -> State:
        """ONE paged launch straight off the layer's page store slice.
        ``page_rows`` [S, ppc] page-handle rows of the visited slots (static
        numpy or traced); ``valid`` [S] traced occupancy."""
        from repro.kernels import ops
        k_l, v_l, ks_l, vs_l = pool_l
        b, c, kvh, g, d = qg.shape
        q = qg.reshape(b, c, kvh * g, d)
        ppc = page_rows.shape[1]
        handles = jnp.asarray(page_rows, jnp.int32).reshape(-1)
        m, l, acc = ops.pool_attention_paged(
            q, k_l, v_l, handles, valid, ppc=ppc, scale=float(scale),
            k_scale=ks_l, v_scale=vs_l)
        return attn_combine(st, self._to_state(m, l, acc, kvh))

    def pool_block(self, qg, kq, vq, ks, vs, valid, scale,
                   st: State) -> State:
        """Stacked-interface entry (the batched-fetch landing path): view
        the landed chunk stack [S, B, C, K, D] as a head-major page store
        with identity handles — [S*ppc, B, K, pt, D] pages — and reuse the
        paged kernel. That costs one small staging-buffer transpose (the
        staging buffer is n_remote chunks, not the pool)."""
        from repro.kernels import ops
        s, b_, ck, kvh_, d_ = kq.shape
        ppc = 1 if ks is None else ks.shape[1]
        pt = ck // ppc

        def pageize(x):
            x = x.reshape(s, b_, ppc, pt, kvh_, d_)
            return x.transpose(0, 2, 1, 4, 3, 5).reshape(
                s * ppc, b_, kvh_, pt, d_)

        ksc = vsc = None
        if ks is not None:  # [S, ppc, B, 1, K, 1] -> [S*ppc, B, K, 1, 1]
            ksc = jnp.swapaxes(ks, -3, -2).reshape(s * ppc, b_, kvh_, 1, 1)
            vsc = jnp.swapaxes(vs, -3, -2).reshape(s * ppc, b_, kvh_, 1, 1)
        handles = jnp.arange(s * ppc, dtype=jnp.int32)
        b, c, kvh, g, d = qg.shape
        q = qg.reshape(b, c, kvh * g, d)
        m, l, acc = ops.pool_attention_paged(
            q, pageize(kq), pageize(vq), handles, valid, ppc=ppc,
            scale=float(scale), k_scale=ksc, v_scale=vsc)
        return attn_combine(st, self._to_state(m, l, acc, kvh))


_BACKENDS: Dict[str, Callable[[], AttentionBackend]] = {}


def register_backend(name: str, factory: Callable[[], AttentionBackend]) -> None:
    _BACKENDS[name] = factory


def get_backend(name: str) -> AttentionBackend:
    if name not in _BACKENDS:
        raise KeyError(f"unknown attention backend {name!r}; "
                       f"registered: {sorted(_BACKENDS)}")
    return _BACKENDS[name]()


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


register_backend("jnp", JnpBackend)
register_backend("pallas", PallasBackend)
register_backend("paged", PagedPallasBackend)


# ============================================================ pool traversal

def pool_scan(backend: AttentionBackend, qg, pool_l, slot_pages, slot_chunk,
              limit, scale, st: State, slots: Optional[Any] = None) -> State:
    """Accumulate attention over pool slots whose stored chunk < ``limit``.

    ``pool_l`` = (k_l, v_l, ks_l, vs_l): THIS layer's slices of the paged
    pool — head-major payloads [P, B, K, page_tokens, D] plus per-head
    scales [P, B, K, 1, 1] (None for a passthrough codec). ``slot_pages`` [slots+1, ppc] is the page table;
    each visited slot's pages are gathered, and the ENCODED chunk goes to
    ``chunk_block_q`` (dequant-on-read is the backend's business).
    ``slots``: optional static subset of slot indices to visit (the creditor
    scan touches only the few host slots, not the whole pool).

    Three traversal orders, numerically reconciled by tests: a backend with
    ``paged_pool`` gets the page-handle rows DIRECTLY (``handle_rows`` ->
    ``pool_block_paged``) and the kernel reads pages in place — zero gather;
    a backend with ``batched_pool`` gets every visited slot's pages in ONE
    gather and ONE ``pool_block`` call (the pallas slot-grid kernel — a
    single launch over a dense HBM stack); otherwise the per-slot
    ``lax.scan`` below is the reference order (one chunk-layer resident at a
    time, one ``chunk_block_q`` per slot)."""
    k_l, v_l, ks_l, vs_l = pool_l
    if slots is not None:
        if len(slots) == 0:
            return st
        idx = jnp.asarray(np.asarray(slots, np.int32))
        chunk_ids = jnp.asarray(slot_chunk)[idx]
        page_rows = kvpages.handle_rows(slot_pages, slots)
    else:
        nslots = slot_pages.shape[0] - 1
        if nslots <= 0:
            return st
        chunk_ids = jnp.asarray(slot_chunk[:nslots])
        page_rows = kvpages.handle_rows(slot_pages)

    valid = (chunk_ids >= 0) & (chunk_ids < limit)
    if getattr(backend, "paged_pool", False):
        # handle rows go straight into the kernel's scalar-prefetch args —
        # both the full-pool and the creditor ``slots=`` subset paths
        return backend.pool_block_paged(qg, pool_l, page_rows, valid, scale,
                                        st)

    if backend.batched_pool:
        # ORACLE FEED, not a perf path: gather_chunks materializes the dense
        # [S, B, C, KVH, D] stack the paged kernel exists to avoid — kept as
        # the reference input for the slot-grid kernel
        kq, vq, ks, vs = kvpages.gather_chunks(k_l, v_l, ks_l, vs_l,
                                               page_rows)
        return backend.pool_block(qg, kq, vq, ks, vs, valid, scale, st)

    def body(carry, xs):
        pages, cid = xs
        kq, vq, ks, vs = kvpages.gather_chunk(k_l, v_l, ks_l, vs_l, pages)
        valid = (cid >= 0) & (cid < limit)
        return backend.chunk_block_q(qg, kq, vq, ks, vs, valid, scale,
                                     carry), None

    st, _ = jax.lax.scan(body, st, (page_rows, chunk_ids))
    return st
