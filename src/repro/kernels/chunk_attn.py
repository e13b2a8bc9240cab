"""Pallas TPU kernel: chunked-prefill flash attention with a prefix offset.

This is MOCAP's compute hot spot: one chunk of C query tokens attends over
(prefix + chunk) KV — the prefix rows are fully visible, the final C rows are
causal with offset ``prefix_len``. GQA is handled by mapping query head h to
kv head h // group in the K/V BlockSpec index maps (no KV replication in VMEM).

Layout: every kernel here is HEAD-MAJOR — q ``[B, H, C, D]``, k/v
``[B, KVH, T, D]`` — so each block's last two dims are (tokens, head_dim),
which the TPU compiler tiles as (8k, 128) rows. A token-major ``[B, C, H, D]``
block with one head in the second-minor position is refused by Mosaic; the
``kernels.ops`` wrappers transpose at the boundary. The online-softmax row
statistics live as ``(block_q, 1)`` columns in VMEM and leave the kernel as
lane-dense ``[B, H, 1, C]`` rows (one in-kernel transpose at the last step).

Tiling: grid = (B, H, nq, nk) with the KV block loop innermost (sequential on
TPU); online-softmax accumulators live in fp32 VMEM scratch. Block shapes are
(block_q, head_dim) / (block_k, head_dim) with head_dim padded to the 128-lane
width by the wrapper (`ops.chunk_attention`). Blocks strictly above the causal
diagonal are skipped via ``pl.when`` (no MXU work issued). Matmuls run in the
input dtype with fp32 accumulation; probabilities are cast to the value dtype
before the PV matmul, like the jnp reference (``core.attention``).

``pool_attention_pallas`` is the batched sibling for MOCAP's POOL scan: the
same online softmax with a slot axis in the grid — (B, H, nq, slots, nk) —
so one launch covers every stored chunk a consumer attends over, instead of
one launch (and one traced-level combine round-trip) per occupied slot.

``pool_attention_paged_pallas`` is the ragged-paged successor (DESIGN.md
§3.7): page-handle rows + per-slot occupancy arrive as SCALAR-PREFETCH
arguments (``pltpu.PrefetchScalarGridSpec``) and the kernel reads KV pages
straight from the head-major page store ``[P, B, KVH, pt, hd]`` — no
``gather_chunks`` copy, no dense slot stack in HBM — double-buffering each
page HBM→VMEM with ``pltpu.make_async_copy`` while the MXU runs the previous
page, and dequantizing int8/fp8 payloads on the landing buffer. Invalid
slots issue zero copies and zero MXU work.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = float(-1e30)
LANES = 128


def _block_update(q, k, v, mask, scale, m_ref, l_ref, acc_ref):
    """One online-softmax block update against the VMEM scratch state —
    shared by the per-chunk and the pool kernels (k/v already dequantized
    to q's dtype; only the mask differs between callers). ``m_ref`` /
    ``l_ref`` are ``(block_q, 1)`` fp32 columns."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    m_safe = jnp.where(m_new < NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(m_prev - m_safe)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _row(col):
    """(block_q, 1) column -> (1, block_q) lane-dense row."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1]


def _store_state(m_ref, l_ref, acc_ref, mo_ref, lo_ref, ao_ref):
    mo_ref[0, 0] = _row(m_ref[...])
    lo_ref[0, 0] = _row(l_ref[...])
    ao_ref[0, 0] = acc_ref[...]


def _load_kv(x, sc, dtype):
    """Stored kv block -> the query's dtype, the jnp reference's decode:
    a quantized payload is multiplied by its scale ``sc`` (broadcast per
    row) first; None = passthrough."""
    if sc is not None:
        x = x.astype(jnp.float32) * sc
    return x.astype(dtype)


def _state_scratch(block_q, d):
    return [
        pltpu.VMEM((block_q, 1), jnp.float32),    # running max
        pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
        pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
    ]


def _state_shapes(b, h, c, d):
    """(m, l) lane-dense rows [B, H, 1, C] + acc [B, H, C, D], all fp32."""
    return ([jax.ShapeDtypeStruct((b, h, 1, c), jnp.float32)] * 2
            + [jax.ShapeDtypeStruct((b, h, c, d), jnp.float32)])


def _attn_kernel(q_ref, k_ref, v_ref, *refs,
                 scale: float, causal_offset: int, kv_len: int,
                 block_q: int, block_k: int, return_state: bool = False,
                 quantized: bool = False):
    if quantized:  # extra inputs: per-token fp32 dequant scale columns
        ksc_ref, vsc_ref, *refs = refs
    o_ref, *refs = refs
    if return_state:  # extra outputs: max / denom rows, fp32 accumulator
        mo_ref, lo_ref, ao_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    # absolute positions of this block's queries / keys
    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # skip blocks entirely above the causal diagonal
    last_q = qb * block_q + causal_offset + block_q - 1  # last query's abs pos
    first_k = kb * block_k

    @pl.when(first_k <= last_q)
    def _compute():
        q = q_ref[0, 0]
        # dequant-on-read: quantized pages store (payload, per-page
        # per-head scales expanded to a per-token column by the caller)
        k = _load_kv(k_ref[0, 0], ksc_ref[0, 0] if quantized else None,
                     q.dtype)
        v = _load_kv(v_ref[0, 0], vsc_ref[0, 0] if quantized else None,
                     q.dtype)
        mask = (k_pos <= q_pos + causal_offset) & (k_pos < kv_len)
        _block_update(q, k, v, mask, scale, m_ref, l_ref, acc_ref)

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if return_state:
            _store_state(m_ref, l_ref, acc_ref, mo_ref, lo_ref, ao_ref)


def _pool_kernel(valid_ref, q_ref, k_ref, v_ref, *refs,
                 scale: float, kv_len: int, block_q: int, block_k: int,
                 quantized: bool = False):
    """Slot-grid pool attention: ONE launch over a stack of stored chunks.

    Grid = (B, H, nq, S, nk) with (slot, kv-block) innermost and sequential,
    so the online-softmax scratch accumulates across every slot's KV blocks
    — the fused form of the per-slot ``chunk_attention`` + combine chain.
    Every stored chunk is fully visible (no causal diagonal); a slot whose
    ``valid`` flag is 0 issues no MXU work and contributes the identity
    state, exactly like the gated per-slot path."""
    if quantized:  # extra inputs: per-(slot, token, kv-head) dequant scales
        ksc_ref, vsc_ref, *refs = refs
    mo_ref, lo_ref, ao_ref, m_ref, l_ref, acc_ref = refs
    si = pl.program_id(3)
    kb = pl.program_id(4)
    ns = pl.num_programs(3)
    nk = pl.num_programs(4)

    @pl.when((si == 0) & (kb == 0))
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    @pl.when(valid_ref[si] != 0)
    def _compute():
        q = q_ref[0, 0]
        k = _load_kv(k_ref[0, 0, 0], ksc_ref[0, 0, 0] if quantized else None,
                     q.dtype)
        v = _load_kv(v_ref[0, 0, 0], vsc_ref[0, 0, 0] if quantized else None,
                     q.dtype)
        # stored chunks are fully visible: only page padding masks
        _block_update(q, k, v, k_pos < kv_len, scale, m_ref, l_ref, acc_ref)

    @pl.when((si == ns - 1) & (kb == nk - 1))
    def _finish():
        _store_state(m_ref, l_ref, acc_ref, mo_ref, lo_ref, ao_ref)


def pool_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, valid: jax.Array, *,
    scale: Optional[float] = None, kv_len: Optional[int] = None,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
):
    """Batched pool attention: q [B, H, C, D] vs a STACK of stored chunks
    k, v [S, B, KVH, T, D] (T padded to a multiple of block_k), in one
    kernel launch. ``valid`` [S] int32 gates each slot (0 = identity
    contribution, scalar-prefetched into SMEM). Returns ONLY the
    online-softmax state — ``(m, l) [B, H, 1, C]`` fp32 rows and the
    unnormalized accumulator ``acc [B, H, C, D]`` fp32 — because the caller
    always combines the pool state with the self block / remote partials
    before normalizing.

    ``kv_len``: VALID tokens per chunk (uniform chunks; pad rows masked).
    ``k_scale``/``v_scale`` ``[S, B, KVH, T, 1]`` fp32: when given, k/v are
    quantized page payloads and the per-token scale columns (the page
    store's per-page scales expanded per token) are multiplied out after
    the block load."""
    b, h, c, d = q.shape
    ns, kvh, t = k.shape[0], k.shape[2], k.shape[3]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = kv_len if kv_len is not None else t
    block_q = min(block_q, c)
    block_k = min(block_k, t)
    assert c % block_q == 0 and t % block_k == 0, (c, t, block_q, block_k)
    nq, nk = c // block_q, t // block_k
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)

    kernel = functools.partial(
        _pool_kernel, scale=scale, kv_len=kv_len,
        block_q=block_q, block_k=block_k, quantized=quantized)
    # index maps take the grid indices PLUS the scalar-prefetch ref
    ml_spec = pl.BlockSpec((1, 1, 1, block_q),
                           lambda bi, hi, qi, si, ki, vr: (bi, hi, 0, qi))
    acc_spec = pl.BlockSpec((1, 1, block_q, d),
                            lambda bi, hi, qi, si, ki, vr: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, 1, block_k, d),
                           lambda bi, hi, qi, si, ki, vr: (si, bi, hi // g, ki, 0))
    in_specs = [acc_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if quantized:
        sc_spec = pl.BlockSpec(
            (1, 1, 1, block_k, 1),
            lambda bi, hi, qi, si, ki, vr: (si, bi, hi // g, ki, 0))
        in_specs += [sc_spec, sc_spec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, h, nq, ns, nk), in_specs=in_specs,
        out_specs=[ml_spec, ml_spec, acc_spec],
        scratch_shapes=_state_scratch(block_q, d))
    m, l, acc = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_state_shapes(b, h, c, d),
        interpret=interpret,
    )(valid.astype(jnp.int32).reshape(-1), *args)
    return m, l, acc


def _paged_kernel(handles_ref, valid_ref, q_ref, k_src, v_src, *refs,
                  scale: float, kv_len: int, block_q: int, pt: int,
                  ppc: int, np_eff: int, group: int, kvh: int,
                  quantized: bool):
    """Ragged paged pool attention: ONE launch straight off the page store.

    Grid = (B, H, nq, S, np_eff) with (slot, page) innermost and sequential.
    ``handles_ref`` [S*ppc] and ``valid_ref`` [S] are scalar-prefetch SMEM
    refs — available BEFORE the grid runs, so they can steer data movement.
    ``k_src``/``v_src`` are the UNBLOCKED page stores (``pl.ANY`` memory
    space). Each grid step issues a ``make_async_copy`` of the NEXT valid
    page's ``[pt, hd]`` slice (one kv head of one page: whole (8, 128)
    tiles of the head-major store) into the other half of a double buffer
    while the MXU consumes the current half — the handle indirection
    happens in the DMA source index, so no gathered stack ever exists in
    HBM.

    A slot with ``valid == 0`` contributes the exact identity state: its
    steps issue no copies (the prefetch for step t+1 is validity-gated) and
    no MXU work. Quantized payloads are dequantized ON THE LANDING BUFFER:
    the per-page scale rides in SMEM (indexed by the same handle) and the
    multiply fuses into the upcast."""
    if quantized:  # extra inputs: per-page per-(batch, kv-head) fp32 scales
        ksc_ref, vsc_ref, *refs = refs
    mo_ref, lo_ref, ao_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref = refs

    bi, hi = pl.program_id(0), pl.program_id(1)
    si, pi = pl.program_id(3), pl.program_id(4)
    ns = pl.num_programs(3)
    hk = hi // group
    step = si * np_eff + pi          # page step within this (bi, hi, qi)
    nsteps = ns * np_eff
    cur_valid = valid_ref[si] != 0

    def page_copies(buf_i, s2, p2):
        h = handles_ref[s2 * ppc + p2]
        ck = pltpu.make_async_copy(k_src.at[h, bi, hk],
                                   kbuf.at[buf_i], sem.at[buf_i, 0])
        cv = pltpu.make_async_copy(v_src.at[h, bi, hk],
                                   vbuf.at[buf_i], sem.at[buf_i, 1])
        return ck, cv

    # warm-up: the first page of each (bi, hi, qi) program has no
    # predecessor to prefetch it — one stall per q-block program
    @pl.when((step == 0) & cur_valid)
    def _warm():
        for c in page_copies(0, 0, 0):
            c.start()

    # land the NEXT page in the other buffer half while this page's block
    # update runs; invalid targets issue no copy at all
    nxt = step + 1
    n_si = jnp.minimum(nxt // np_eff, ns - 1)  # clamp: last step only
    n_pi = jax.lax.rem(nxt, np_eff)

    @pl.when((nxt < nsteps) & (valid_ref[n_si] != 0))
    def _prefetch():
        for c in page_copies(jax.lax.rem(nxt, 2), n_si, n_pi):
            c.start()

    @pl.when(step == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    k_pos = pi * pt + jax.lax.broadcasted_iota(jnp.int32, (block_q, pt), 1)

    @pl.when(cur_valid)
    def _compute():
        buf_i = jax.lax.rem(step, 2)
        for c in page_copies(buf_i, si, pi):
            c.wait()
        q = q_ref[0, 0]
        ksc = vsc = None
        if quantized:  # dequant on the landing buffer
            sidx = (handles_ref[si * ppc + pi] * pl.num_programs(0) + bi) \
                * kvh + hk
            ksc, vsc = ksc_ref[sidx], vsc_ref[sidx]
        k = _load_kv(kbuf[buf_i], ksc, q.dtype)
        v = _load_kv(vbuf[buf_i], vsc, q.dtype)
        # stored chunks are fully visible: only the partial last page masks
        _block_update(q, k, v, k_pos < kv_len, scale, m_ref, l_ref, acc_ref)

    @pl.when(step == nsteps - 1)
    def _finish():
        _store_state(m_ref, l_ref, acc_ref, mo_ref, lo_ref, ao_ref)


def pool_attention_paged_pallas(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    handles: jax.Array, valid: jax.Array, *, ppc: int,
    scale: Optional[float] = None, kv_len: Optional[int] = None,
    block_q: int = DEFAULT_BLOCK_Q, interpret: bool = False,
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
):
    """Ragged paged pool attention: q [B, H, C, D] vs the head-major PAGE
    STORE ``k_pages``/``v_pages`` [P, B, KVH, pt, D] (one layer's slice,
    storage dtype), addressed through ``handles`` [S*ppc] int32 (the
    flattened page-handle rows of the visited slots) with per-slot
    occupancy ``valid`` [S] int32 — both delivered as scalar-prefetch
    arguments. Returns the online-softmax state ``(m, l) [B, H, 1, C]`` fp32
    rows + unnormalized ``acc [B, H, C, D]`` fp32, exactly like
    ``pool_attention_pallas``, but with NO gathered intermediate: pages
    stream HBM→VMEM per grid step (double-buffered ``make_async_copy``).

    ``kv_len``: valid tokens per chunk (< ppc*pt for a partial last page —
    trailing fully-empty pages are excluded from the grid, the straddling
    page is masked). ``k_scale``/``v_scale`` [P, B*KVH] fp32: per-page
    dequant scales, SMEM-indexed by the same handles."""
    b, h, c, d = q.shape
    kvh, pt = k_pages.shape[2], k_pages.shape[3]
    assert k_pages.shape[-1] == d, (k_pages.shape, d)
    ns = valid.shape[0]
    assert ns >= 1 and handles.shape == (ns * ppc,), (handles.shape, ns, ppc)
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = kv_len if kv_len is not None else ppc * pt
    np_eff = max(1, min(ppc, -(-kv_len // pt)))  # drop fully-empty pages
    block_q = min(block_q, c)
    assert c % block_q == 0, (c, block_q)
    nq = c // block_q
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)

    kernel = functools.partial(
        _paged_kernel, scale=scale, kv_len=kv_len, block_q=block_q, pt=pt,
        ppc=ppc, np_eff=np_eff, group=g, kvh=kvh, quantized=quantized)
    # index maps take the grid indices PLUS the scalar-prefetch refs
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, si, pi, hr, vr: (bi, hi, qi, 0))
    # unblocked page stores: the kernel DMAs page slices itself
    kv_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k_pages, v_pages]
    if quantized:
        # whole (small) scale tables in SMEM, indexed by the same handles
        sc_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
        in_specs += [sc_spec, sc_spec]
        args += [k_scale.astype(jnp.float32).reshape(-1),
                 v_scale.astype(jnp.float32).reshape(-1)]
    ml_spec = pl.BlockSpec((1, 1, 1, block_q),
                           lambda bi, hi, qi, si, pi, hr, vr: (bi, hi, 0, qi))
    scratch = [
        pltpu.VMEM((2, pt, d), k_pages.dtype),   # k landing buffers
        pltpu.VMEM((2, pt, d), v_pages.dtype),   # v landing buffers
        pltpu.SemaphoreType.DMA((2, 2)),         # [buffer, k|v]
    ] + _state_scratch(block_q, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, h, nq, ns, np_eff),
        in_specs=in_specs, out_specs=[ml_spec, ml_spec, q_spec],
        scratch_shapes=scratch)
    m, l, acc = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_state_shapes(b, h, c, d),
        interpret=interpret,
    )(handles.astype(jnp.int32), valid.astype(jnp.int32), *args)
    return m, l, acc


def chunk_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal_offset: int = 0, scale: Optional[float] = None,
    kv_len: Optional[int] = None,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False, return_state: bool = False,
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
):
    """q [B, H, C, D]; k, v [B, KVH, T, D] (T = prefix + C, padded to a
    multiple of block_k). Returns [B, H, C, D].

    ``causal_offset``: absolute position of q[0] minus the position of k[0]
    (= prefix length for chunked prefill). ``kv_len``: number of VALID kv
    positions (defaults to T; use when T includes padding).

    ``return_state``: also return the online-softmax residuals — ``(m, l)
    [B, H, 1, C]`` (fp32 running max / denominator rows) and the
    UNNORMALIZED fp32 accumulator ``acc [B, H, C, D]`` straight from VMEM
    scratch — so the caller can COMBINE this kernel's result with other
    partial-attention states at full precision even when the normalized
    output is bf16. This is the seam the pipeline's pluggable attention
    backend plugs into.

    ``k_scale``/``v_scale`` [B, KVH, T, 1] fp32: when given, k/v are
    QUANTIZED page payloads (int8 / fp8 from ``kvstore.quant``) and the
    kernel dequantizes each block after the load — the KV bytes that cross
    HBM and land in VMEM stay compressed. One scale per kv token (the page
    store's per-page per-head scales, expanded by the caller), so scales
    may vary across the pages inside one kv block.
    """
    b, h, c, d = q.shape
    kvh, t = k.shape[1], k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = kv_len if kv_len is not None else t
    block_q = min(block_q, c)
    block_k = min(block_k, t)
    assert c % block_q == 0 and t % block_k == 0, (c, t, block_q, block_k)
    nq, nk = c // block_q, t // block_k
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal_offset=causal_offset, kv_len=kv_len,
        block_q=block_q, block_k=block_k, return_state=return_state,
        quantized=quantized)
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda bi, hi, qi, ki: (bi, hi // g, ki, 0))
    out_shapes = [jax.ShapeDtypeStruct((b, h, c, d), q.dtype)]
    out_specs = [q_spec]
    if return_state:
        ml_spec = pl.BlockSpec((1, 1, 1, block_q),
                               lambda bi, hi, qi, ki: (bi, hi, 0, qi))
        out_shapes += _state_shapes(b, h, c, d)
        out_specs += [ml_spec, ml_spec, q_spec]
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if quantized:
        sc_spec = pl.BlockSpec((1, 1, block_k, 1),
                               lambda bi, hi, qi, ki: (bi, hi // g, ki, 0))
        in_specs += [sc_spec, sc_spec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    res = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs if return_state else q_spec,
        out_shape=out_shapes if return_state else out_shapes[0],
        scratch_shapes=_state_scratch(block_q, d),
        interpret=interpret,
    )(*args)
    return tuple(res) if return_state else res
