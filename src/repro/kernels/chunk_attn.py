"""Pallas TPU kernel: chunked-prefill flash attention with a prefix offset.

This is MOCAP's compute hot spot: one chunk of C query tokens attends over
(prefix + chunk) KV — the prefix rows are fully visible, the final C rows are
causal with offset ``prefix_len``. GQA is handled by tiling the query heads
that share a kv head together (no KV replication in VMEM).

Layout: every kernel here is HEAD-MAJOR — q ``[B, H, C, D]``, k/v
``[B, KVH, T, D]`` — so each block's last two dims are (tokens, head_dim),
which the TPU compiler tiles as (8k, 128) rows. A token-major ``[B, C, H, D]``
block with one head in the second-minor position is refused by Mosaic; the
``kernels.ops`` wrappers transpose at the boundary. The online-softmax row
statistics live as ``(rows, 1)`` columns in VMEM and leave the kernel as
lane-dense ``[B, H, 1, C]`` rows (one in-kernel transpose at the end).

Tiling of the self block (``chunk_attention_pallas``): grid = (B, KVH *
blocks, nq). A program owns a query tile: ``heads`` of the g query heads
of one kv head, ``block_q`` rows each, viewed as ``heads * block_q`` score
rows, so each key slice meets every row of the tile in one online-softmax
update. K/V of the kv head reach VMEM whole, once per head block (the
BlockSpec's index ignores the query tile; a kv head too large for VMEM
comes in spans along a fourth grid axis instead). The program loops over
the key slices its tile can see and no others: slices below every row's
diagonal update with no mask, the widest first; only slices that straddle
the diagonal or ``kv_len`` build a mask; slices past the tile's last
visible key are never computed. ``self_tiles`` picks the tile from the
shapes; the diagonal square has the query tile's side, so a chunk of C
queries computes ``1 + block_q / C`` times its causal pairs
(``self_block_pairs``). Accumulators live in fp32 VMEM scratch, head_dim is
padded to the 128-lane width by the wrapper (`ops.chunk_attention`).
Matmuls run in the input dtype with fp32 accumulation; probabilities are
cast to the value dtype before the PV matmul, like the jnp reference
(``core.attention``).

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
slots cost no step, no copy and no MXU work. Its grid runs over blocks of
the query heads that share a kv head — (B, KVH * blocks, nq) — and each
program loops over the valid pages only, so a page crosses HBM→VMEM once
per (head block, query tile) and meets all of its rows at once; each
landed page is consumed whole, in one online-softmax update where its
score tile fits (``paged_tiles`` picks the tile from the shapes). At
qwen3-8b widths (g 4, 2048-token pages) a tile is the 4 heads x 128 rows,
and a page is read 16 times per call where a (query head, 128-row block)
grid read it 64 times.
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

# Fixed kernel names: the HLO custom call of each kernel (and so the op a
# profiler trace shows, ``chunk_attention.<n>``) takes this name, whatever
# the Python functions around it are called.
SELF_KERNEL = "chunk_attention"
POOL_KERNEL = "pool_attention"
PAGED_POOL_KERNEL = "pool_attention_paged"
NEG_INF = float(-1e30)
LANES = 128

# Paged pool kernel tile (``paged_tiles``): the most query rows per tile;
# the fewest rows a landed page should meet (a page read feeds R rows at R
# FLOP per byte, and v5e does 240 per byte of HBM); the fp32 score tile's
# bytes; and the VMEM estimate up to which the kernel keeps the default
# scoped limit (v5e: 16 MiB of its 128 MiB). Within the default, the program
# around the kernel compiles as it would without it.
PAGED_TILE_ROWS = 2048
PAGED_MIN_ROWS = 256
PAGED_SCORE_BYTES = 4 << 20
PAGED_VMEM_BYTES = 14 << 20

# Self-block kernel tile (``self_tiles``): the most query rows per head of
# a tile, which is also the side of the diagonal square a tile masks; and
# the most score rows (heads x rows per head) of one update.
SELF_DIAG_ROWS = 256
SELF_TILE_ROWS = 1024


def _block_update(q, k, v, mask, scale, m_ref, l_ref, acc_ref):
    """One online-softmax block update against the VMEM scratch state —
    shared by the per-chunk and the pool kernels (k/v already dequantized
    to q's dtype; only the mask differs between callers, None = every key
    visible). ``m_ref`` / ``l_ref`` are ``(rows, 1)`` fp32 columns."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask is not None:
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
                 block_q: int, block_k: int, span: int,
                 return_state: bool = False, quantized: bool = False):
    """Causal self block: grid = (B, KVH * blocks, nq, spans). A program
    (bi, hb, qi) owns one query tile, ``heads`` query heads of kv head
    ``hb // blocks``, ``block_q`` rows each, viewed as ``R = heads *
    block_q`` score rows (row r is query ``qi*block_q + r % block_q``).
    k/v arrive in spans of ``span`` keys (the whole kv head at every served
    shape, so ``spans`` is 1); each program loops over the key slices of
    the span that its tile can see, and no further:

    - slices at or below every row's diagonal update with no mask, the
      widest (``block_k``) first, the remainder in halving widths down to
      ``narrow = math.gcd(block_q, block_k)``;
    - the ``narrow`` slices that straddle a row's diagonal or ``kv_len``
      mask, the rest of the span is never computed.

    The state scratch carries across spans; the last span writes o (and
    the state rows with ``return_state``)."""
    if quantized:  # extra inputs: per-token fp32 dequant scale columns
        ksc_ref, vsc_ref, *refs = refs
    o_ref, *refs = refs
    if return_state:  # extra outputs: max / denom rows, fp32 accumulator
        mo_ref, lo_ref, ao_ref, m_ref, l_ref, acc_ref = refs
    else:
        m_ref, l_ref, acc_ref = refs
    qi, si = pl.program_id(2), pl.program_id(3)
    heads = q_ref.shape[1]
    rows = heads * block_q
    narrow = math.gcd(block_q, block_k)
    first = qi * block_q + causal_offset   # the tile's first query, as a key
    lo = si * span                         # the span's first key
    full, seen = _visible(first, lo, kv_len, block_q, span, narrow)

    @pl.when(si == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    q = q_ref[0].reshape(rows, q_ref.shape[-1])

    def update(start, width, masked):
        """One online-softmax update against keys [start, start + width)
        of the span."""
        sl = pl.ds(pl.multiple_of(start, narrow), width)
        k = _load_kv(k_ref[0, 0, sl], ksc_ref[0, 0, sl] if quantized else None,
                     q.dtype)
        v = _load_kv(v_ref[0, 0, sl], vsc_ref[0, 0, sl] if quantized else None,
                     q.dtype)
        mask = None
        if masked:
            iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                                     (rows, width))
            k_pos = lo + start + iota(1)
            q_pos = first + jax.lax.rem(iota(0), block_q)
            mask = (k_pos <= q_pos) & (k_pos < kv_len)
        _block_update(q, k, v, mask, scale, m_ref, l_ref, acc_ref)

    # unmasked: ``full`` narrow slices, as wide slices and then the rest
    per = block_k // narrow
    wide = full // per

    def wide_step(i, carry):
        update(i * block_k, block_k, False)
        return carry

    jax.lax.fori_loop(0, wide, wide_step, 0)
    start, rest = wide * block_k, full - wide * per
    for j in reversed(range((per - 1).bit_length())):
        bit = jax.lax.shift_right_logical(rest, j) & 1

        @pl.when(bit == 1)
        def _part():
            update(start, narrow << j, False)
        start = start + bit * (narrow << j)

    # masked: the narrow slices from there to the last key some row sees
    def masked_step(i, carry):
        update(start + i * narrow, narrow, True)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(seen - start, narrow), masked_step, 0)

    @pl.when(si == pl.num_programs(3) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        for j in range(heads):
            sl = slice(j * block_q, (j + 1) * block_q)
            o_ref[0, j] = out[sl].astype(o_ref.dtype)
            if return_state:
                mo_ref[0, j] = _row(m_ref[sl])
                lo_ref[0, j] = _row(l_ref[sl])
                ao_ref[0, j] = acc_ref[sl]


def _visible(first, lo, kv_len, block_q, span, narrow):
    """Keys of the span [lo, lo + span) that a query tile whose first query
    sits at key position ``first`` sees: ``full``, the number of narrow
    slices from ``lo`` that every row of the tile sees, and ``seen``, the
    keys from ``lo`` that some row sees. Python ints or traced scalars."""
    def clip(x):
        return jnp.minimum(jnp.maximum(x, 0), span)
    full = clip(jnp.minimum(first + 1, kv_len) - lo) // narrow
    seen = clip(jnp.minimum(first + block_q, kv_len) - lo)
    return full, seen


def _largest(n: int, most: int):
    """``n`` if it is at most ``most``, else the largest multiple of 128
    that divides ``n`` and is at most ``most``; None if none is."""
    if n <= most:
        return n
    fits = [m for m in range(LANES, most + 1, LANES) if n % m == 0]
    return fits[-1] if fits else None


def self_tiles(group: int, c: int, t: int, d: int, causal_offset: int = 0):
    """Tile of the causal self-block kernel, from shapes alone: ``(heads,
    block_q, block_k)``.

    ``block_q`` is the query rows per head of a tile and the side of the
    diagonal square: a divisor of ``c`` that is a multiple of 128 (or
    ``c`` itself) and at most ``SELF_DIAG_ROWS``, so a tile computes at
    most half a square past its rows' diagonals and a chunk of C queries
    computes ``1 + block_q / C`` times its causal pairs. ``heads`` is the
    most of the ``group`` query heads of one kv head whose ``R = heads *
    block_q`` rows stay within ``SELF_TILE_ROWS``. ``block_k``, the widest
    unmasked key slice, is ``block_q`` doubled while it divides the keys
    (``t`` rounded up to ``block_q``) and its fp32 score tile ``[R,
    block_k]`` fits ``PAGED_SCORE_BYTES``. ``d`` and ``causal_offset`` do
    not move the tile at any shape served; ``self_span`` picks from ``d``
    how many keys reach VMEM at once."""
    del d, causal_offset
    block_q = _largest(c, SELF_DIAG_ROWS) or c
    heads = max(h for h in range(1, group + 1) if group % h == 0
                and (h == 1 or h * block_q <= SELF_TILE_ROWS))
    rows, keys = heads * block_q, -(-t // block_q) * block_q
    block_k = block_q
    while (keys % (2 * block_k) == 0
           and 4 * rows * 2 * block_k <= PAGED_SCORE_BYTES):
        block_k *= 2
    return heads, block_q, block_k


def self_vmem_bytes(rows: int, block_k: int, span: int, d: int,
                    kv_bytes: int, q_bytes: int = 2,
                    quantized: bool = False) -> int:
    """VMEM of the self-block kernel at a tile of ``rows`` query rows and
    ``span`` resident keys, with the state outputs: the pipelined q, o
    and accumulator blocks and state rows, the two k|v span buffers (and
    their lane-padded scale columns), the state scratch (lane-padded (R, 1)
    columns) and ~5 bytes per score element of temporaries, as
    ``paged_vmem_bytes`` counts them."""
    return (2 * rows * d * q_bytes * 2          # q and o blocks
            + 2 * rows * d * 4                  # acc out block
            + 2 * 2 * 8 * rows * 4              # m, l out rows (8-padded)
            + 2 * 2 * span * d * kv_bytes       # k|v spans, double-buffered
            + (2 * 2 * span * LANES * 4 if quantized else 0)
            + rows * (2 * LANES + d) * 4        # m, l columns + acc scratch
            + 5 * rows * block_k)               # score temporaries


def self_span(t: int, rows: int, block_k: int, d: int, kv_bytes: int,
              q_bytes: int = 2, quantized: bool = False) -> int:
    """Keys of one kv head that reach VMEM at once: all ``t`` where the
    tile's ``self_vmem_bytes`` stays within ``PAGED_VMEM_BYTES`` (so the
    default scoped limit holds), else the largest multiple of ``block_k``
    dividing ``t`` that does."""
    fits = [s for s in range(block_k, t + 1, block_k) if t % s == 0
            and self_vmem_bytes(rows, block_k, s, d, kv_bytes, q_bytes,
                                quantized) <= PAGED_VMEM_BYTES]
    return fits[-1] if fits else block_k


def self_block_pairs(c: int, t: int, causal_offset: int, tiles):
    """(computed, useful) (query, key) pairs per head of one self-block
    call at ``tiles = (heads, block_q, block_k)``: the key slices the
    kernel updates against, and the pairs a causal mask keeps (query i
    sees keys ``<= causal_offset + i`` below ``t``)."""
    _, block_q, block_k = tiles
    narrow = math.gcd(block_q, block_k)
    keys = -(-t // block_k) * block_k
    computed = 0
    for qi in range(c // block_q):
        _, seen = _visible(qi * block_q + causal_offset, 0, t, block_q,
                           keys, narrow)
        computed += block_q * narrow * -(-int(seen) // narrow)
    useful = sum(min(i + causal_offset + 1, t) for i in range(c))
    return computed, useful


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
        interpret=interpret, name=POOL_KERNEL,
    )(valid.astype(jnp.int32).reshape(-1), *args)
    return m, l, acc


def paged_tiles(group: int, c: int, pt: int):
    """Tile of the paged pool kernel, from shapes alone: ``(heads,
    block_q, block_k)``.

    ``block_k`` is the key slice of a landed page per online-softmax update:
    the whole page, unless a score tile of ``PAGED_MIN_ROWS`` rows ``[256,
    pt]`` would pass ``PAGED_SCORE_BYTES`` (then the largest multiple of
    128 dividing ``pt`` that fits). Whole pages matter most: every update
    also rescales the accumulator and updates the row statistics, a cost
    per row that a 512-token slice pays four times per 2048-token page.
    The query tile is ``heads`` of the ``group`` query heads of one kv
    head, ``block_q`` rows each (a divisor of ``c``: a multiple of 128, or
    ``c`` itself), the most rows ``R = heads * block_q`` whose fp32 score
    tile ``[R, block_k]`` fits ``PAGED_SCORE_BYTES`` and at most
    ``PAGED_TILE_ROWS``, the most heads among equal ``R``. A page then
    crosses HBM→VMEM ``c * group / R`` times per call."""
    least = min(group * c, PAGED_MIN_ROWS)
    block_k = _largest(pt, PAGED_SCORE_BYTES // (4 * least)) or (
        LANES if pt % LANES == 0 else pt)
    most = min(PAGED_TILE_ROWS, PAGED_SCORE_BYTES // (4 * block_k))
    tiles = [(h * bq, h, bq) for h in range(group, 0, -1) if group % h == 0
             for bq in [_largest(c, most // h)] if bq]
    _, heads, block_q = max(tiles) if tiles else (
        0, 1, LANES if c % LANES == 0 else c)
    return heads, block_q, block_k


def paged_vmem_bytes(rows: int, block_k: int, pt: int, d: int,
                     kv_bytes: int, q_bytes: int = 2) -> int:
    """VMEM of the paged pool kernel at a tile of ``rows`` query rows:
    the pipelined q and accumulator blocks, the two page landing buffers,
    the state scratch (lane-padded (R, 1) columns) and ~5 bytes per score
    element of temporaries (fitted to Mosaic's v5e allocations, slightly
    above them)."""
    return (2 * rows * d * q_bytes          # q block, double-buffered
            + 2 * rows * d * 4              # acc out block, double-buffered
            + 2 * 2 * pt * d * kv_bytes     # k|v landing, two halves
            + rows * (2 * LANES + d) * 4    # m, l columns + acc scratch
            + 5 * rows * block_k)           # score temporaries


def _paged_kernel(handles_ref, valid_ref, q_ref, k_src, v_src, *refs,
                  scale: float, kv_len: int, block_q: int, block_k: int,
                  pt: int, ppc: int, np_eff: int, kvh: int, blocks: int,
                  quantized: bool):
    """Ragged paged pool attention: ONE launch straight off the page store.

    Grid = (B, KVH * blocks, nq); each program loops over the valid pages
    itself. A program (bi, hb, qi) owns one query tile: ``heads`` query
    heads of kv head ``hk = hb // blocks`` (a kv head's ``g`` query heads
    make ``blocks = g // heads`` head blocks), ``block_q`` rows each. The q
    block ``[heads, block_q, D]`` is viewed as ``R = heads*block_q`` rows,
    so each landed page feeds all of them before the next page is read.
    ``handles_ref`` [S*ppc] and ``valid_ref`` [S] are scalar-prefetch
    SMEM refs; ``k_src``/``v_src`` are the UNBLOCKED page stores (``pl.ANY``
    memory space).

    The program first lists the valid slots in SMEM (``order``), then runs
    one loop step per valid page: it issues a ``make_async_copy`` of the
    NEXT page's ``[pt, hd]`` slice (one kv head of one page: whole (8, 128)
    tiles of the head-major store) into the other half of a double buffer,
    waits for its own page and runs the MXU on it — the handle indirection
    happens in the DMA source index, so no gathered stack ever exists in
    HBM. The last step prefetches the first page of the next program
    (every program shares the slot list and handles), so only the call's
    first program waits for a cold page. A slot with ``valid == 0`` costs
    no loop step, no copy and no MXU work, and contributes the exact
    identity state; with none valid the program writes the identity.

    A landed page is consumed in ``block_k``-token slices, each one online-
    softmax update of the ``[R, block_k]`` score tile (one slice, the whole
    page, at the served shapes). Pages wholly inside ``kv_len`` build no
    mask; only the slice that straddles ``kv_len`` on the last page masks,
    and slices past it are skipped. Quantized payloads are dequantized ON
    THE LANDING BUFFER: the per-page scale of (handle, batch, kv head) rides
    in SMEM and the multiply fuses into the upcast."""
    if quantized:  # extra inputs: per-page per-(batch, kv-head) fp32 scales
        ksc_ref, vsc_ref, *refs = refs
    (mo_ref, lo_ref, ao_ref, kbuf, vbuf, sem, order,
     m_ref, l_ref, acc_ref) = refs

    bi, hb, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nhb, nq = pl.num_programs(1), pl.num_programs(2)
    hk = hb // blocks                # head block -> its kv head
    heads = q_ref.shape[1]
    rows = heads * block_q
    ns = valid_ref.shape[0]
    prog = (bi * nhb + hb) * nq + qi
    nprog = pl.num_programs(0) * nhb * nq

    def collect(s, n):               # the valid slots, in order
        order[n] = s
        return n + (valid_ref[s] != 0).astype(jnp.int32)

    nsteps = jax.lax.fori_loop(0, ns, collect, jnp.int32(0)) * np_eff
    gbase = prog * nsteps            # buffer parity runs across programs

    def page_copies(buf_i, b2, h2, t):
        """Copies of loop step ``t``'s page of (batch b2, kv head h2)."""
        s2 = order[t // np_eff]
        h = handles_ref[s2 * ppc + jax.lax.rem(t, np_eff)]
        ck = pltpu.make_async_copy(k_src.at[h, b2, h2],
                                   kbuf.at[buf_i], sem.at[buf_i, 0])
        cv = pltpu.make_async_copy(v_src.at[h, b2, h2],
                                   vbuf.at[buf_i], sem.at[buf_i, 1])
        return ck, cv

    # warm-up: the call's first page has no predecessor to prefetch it
    @pl.when((prog == 0) & (nsteps > 0))
    def _warm():
        for c in page_copies(0, bi, hk, 0):
            c.start()

    _init_state(m_ref, l_ref, acc_ref)

    def update(q, k, v, ksc, vsc, t, n_valid):
        """One online-softmax update against key slice ``t`` of the page;
        ``n_valid`` < block_k masks the slice's tail."""
        sl = pl.ds(t * block_k, block_k)
        k = _load_kv(k[sl], ksc, q.dtype)
        v = _load_kv(v[sl], vsc, q.dtype)
        mask = None
        if n_valid < block_k:
            mask = jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 1) < n_valid
        _block_update(q, k, v, mask, scale, m_ref, l_ref, acc_ref)

    def page(q, k, v, ksc, vsc, n_tok):
        """The page's first ``n_tok`` tokens, slice by slice."""
        for t in range(-(-n_tok // block_k)):
            update(q, k, v, ksc, vsc, t, min(block_k, n_tok - t * block_k))

    last_tok = kv_len - (np_eff - 1) * pt   # valid tokens of the last page

    def step(t, carry):
        buf_i = jax.lax.rem(gbase + t, 2)
        nbuf = 1 - buf_i

        # land the NEXT page in the other buffer half while this one runs
        @pl.when(t + 1 < nsteps)
        def _prefetch():
            for c in page_copies(nbuf, bi, hk, t + 1):
                c.start()

        # ... and on the last step, the next program's first page
        @pl.when((t + 1 == nsteps) & (prog + 1 < nprog))
        def _prefetch_next_program():
            b2 = (prog + 1) // (nhb * nq)
            h2 = jax.lax.rem((prog + 1) // nq, nhb) // blocks
            for c in page_copies(nbuf, b2, h2, 0):
                c.start()

        for c in page_copies(buf_i, bi, hk, t):
            c.wait()
        pi = jax.lax.rem(t, np_eff)
        q = q_ref[0].reshape(rows, q_ref.shape[-1])
        ksc = vsc = None
        if quantized:  # dequant on the landing buffer
            hnd = handles_ref[order[t // np_eff] * ppc + pi]
            sidx = (hnd * pl.num_programs(0) + bi) * kvh + hk
            ksc, vsc = ksc_ref[sidx], vsc_ref[sidx]
        k, v = kbuf.at[buf_i], vbuf.at[buf_i]
        if last_tok == pt:               # whole pages: no mask anywhere
            page(q, k, v, ksc, vsc, pt)
            return carry
        if np_eff > 1:
            @pl.when(pi < np_eff - 1)
            def _whole():
                page(q, k, v, ksc, vsc, pt)

        @pl.when(pi == np_eff - 1)
        def _partial():
            page(q, k, v, ksc, vsc, last_tok)
        return carry

    jax.lax.fori_loop(0, nsteps, step, 0)
    for j in range(heads):
        sl = slice(j * block_q, (j + 1) * block_q)
        mo_ref[0, j] = _row(m_ref[sl])
        lo_ref[0, j] = _row(l_ref[sl])
        ao_ref[0, j] = acc_ref[sl]


def pool_attention_paged_pallas(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    handles: jax.Array, valid: jax.Array, *, ppc: int,
    scale: Optional[float] = None, kv_len: Optional[int] = None,
    heads: Optional[int] = None, block_q: Optional[int] = None,
    block_k: Optional[int] = None, interpret: bool = False,
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
    stream HBM→VMEM per loop step (double-buffered ``make_async_copy``).

    The grid runs over head blocks of one kv head, not single query heads,
    and each program loops over the valid pages only: q's ``[B, H, C, D]``
    is blocked ``[1, heads, block_q, D]`` (``heads`` of the g query heads of
    one kv head; head ``h = hk*g + j``), so a page is read once per (head
    block, query tile) and meets ``heads*block_q`` query rows per read.
    ``heads``, ``block_q`` and the key slice ``block_k`` default to
    ``paged_tiles``' choice from the shapes; a tile past
    ``PAGED_VMEM_BYTES`` raises the kernel's VMEM limit.

    ``kv_len``: valid tokens per chunk (< ppc*pt for a partial last page —
    trailing fully-empty pages are never visited, the straddling page is
    masked). ``k_scale``/``v_scale`` [P, B*KVH] fp32: per-page dequant
    scales, SMEM-indexed by the same handles."""
    b, h, c, d = q.shape
    kvh, pt = k_pages.shape[2], k_pages.shape[3]
    assert k_pages.shape[-1] == d, (k_pages.shape, d)
    ns = valid.shape[0]
    assert ns >= 1 and handles.shape == (ns * ppc,), (handles.shape, ns, ppc)
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = kv_len if kv_len is not None else ppc * pt
    np_eff = max(1, min(ppc, -(-kv_len // pt)))  # drop fully-empty pages
    auto = paged_tiles(g, c, pt)
    heads, block_q, block_k = (x or a for x, a in zip(
        (heads, block_q, block_k), auto))
    assert g % heads == 0 and c % block_q == 0 and pt % block_k == 0, (
        g, heads, c, block_q, pt, block_k)
    nq = c // block_q
    vmem = paged_vmem_bytes(heads * block_q, block_k, pt, d,
                            k_pages.dtype.itemsize, q.dtype.itemsize)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)

    kernel = functools.partial(
        _paged_kernel, scale=scale, kv_len=kv_len, block_q=block_q,
        block_k=block_k, pt=pt, ppc=ppc, np_eff=np_eff, kvh=kvh,
        blocks=g // heads, quantized=quantized)
    # index maps take the grid indices PLUS the scalar-prefetch refs; head
    # block hb of ``heads`` heads is heads hb*heads .. hb*heads+heads-1, all
    # of kv head hb // (g // heads)
    q_spec = pl.BlockSpec((1, heads, block_q, d),
                          lambda bi, hb, qi, hr, vr: (bi, hb, qi, 0))
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
    ml_spec = pl.BlockSpec((1, heads, 1, block_q),
                           lambda bi, hb, qi, hr, vr: (bi, hb, 0, qi))
    scratch = [
        pltpu.VMEM((2, pt, d), k_pages.dtype),   # k landing buffers
        pltpu.VMEM((2, pt, d), v_pages.dtype),   # v landing buffers
        pltpu.SemaphoreType.DMA((2, 2)),         # [buffer, k|v]
        pltpu.SMEM((ns,), jnp.int32),            # the valid slots, in order
    ] + _state_scratch(heads * block_q, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, h // heads, nq),
        in_specs=in_specs, out_specs=[ml_spec, ml_spec, q_spec],
        scratch_shapes=scratch)
    m, l, acc = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_state_shapes(b, h, c, d),
        # a tile past the default scoped VMEM asks for twice its estimate
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=(
            None if vmem <= PAGED_VMEM_BYTES else 2 * vmem)),
        interpret=interpret, name=PAGED_POOL_KERNEL,
    )(handles.astype(jnp.int32), valid.astype(jnp.int32), *args)
    return m, l, acc


def chunk_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal_offset: int = 0, scale: Optional[float] = None,
    kv_len: Optional[int] = None, heads: Optional[int] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: bool = False, return_state: bool = False,
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
):
    """q [B, H, C, D]; k, v [B, KVH, T, D] (T = prefix + C, padded to a
    multiple of block_k). Returns [B, H, C, D].

    ``causal_offset``: absolute position of q[0] minus the position of k[0]
    (= prefix length for chunked prefill). ``kv_len``: number of VALID kv
    positions (defaults to T; use when T includes padding).

    ``heads``, ``block_q`` and ``block_k`` (query heads and rows per head
    of a tile, widest key slice) default to ``self_tiles``' choice from the
    shapes; k/v come whole per kv head where ``self_span`` finds VMEM for
    it, else in the largest spans that fit.

    ``return_state``: also return the online-softmax residuals — ``(m, l)
    [B, H, 1, C]`` (fp32 running max / denominator rows) and the
    UNNORMALIZED fp32 accumulator ``acc [B, H, C, D]`` straight from VMEM
    scratch — so the caller can COMBINE this kernel's result with other
    partial-attention states at full precision even when the normalized
    output is bf16. This is the seam the pipeline's pluggable attention
    backend plugs into.

    ``k_scale``/``v_scale`` [B, KVH, T, 1] fp32: when given, k/v are
    QUANTIZED page payloads (int8 / fp8 from ``kvstore.quant``) and the
    kernel dequantizes each key slice after the load — the KV bytes that
    cross HBM and land in VMEM stay compressed. One scale per kv token (the
    page store's per-page per-head scales, expanded by the caller), so
    scales may vary across the pages inside one key slice.
    """
    b, h, c, d = q.shape
    kvh, t = k.shape[1], k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = kv_len if kv_len is not None else t
    auto = self_tiles(g, c, kv_len, d, causal_offset)
    heads, block_q, block_k = (x or a for x, a in zip(
        (heads, block_q, block_k), auto))
    assert g % heads == 0 and c % block_q == 0 and t % block_k == 0, (
        g, heads, c, block_q, t, block_k)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)
    span = self_span(t, heads * block_q, block_k, d, k.dtype.itemsize,
                     q.dtype.itemsize, quantized)
    nq, blocks = c // block_q, g // heads

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal_offset=causal_offset, kv_len=kv_len,
        block_q=block_q, block_k=block_k, span=span,
        return_state=return_state, quantized=quantized)

    def kv_index(bi, hb, qi, si):
        """Span si of the tile's kv head; past the tile's last visible span
        the index stays, so the pipeline copies nothing."""
        last = (jnp.minimum(qi * block_q + causal_offset + block_q, kv_len)
                - 1) // span
        return bi, hb // blocks, jnp.minimum(si, last), 0

    # head block hb of ``heads`` heads is heads hb*heads .. hb*heads+heads-1,
    # all of kv head hb // blocks
    q_spec = pl.BlockSpec((1, heads, block_q, d),
                          lambda bi, hb, qi, si: (bi, hb, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, span, d), kv_index)
    out_shapes = [jax.ShapeDtypeStruct((b, h, c, d), q.dtype)]
    out_specs = [q_spec]
    if return_state:
        ml_spec = pl.BlockSpec((1, heads, 1, block_q),
                               lambda bi, hb, qi, si: (bi, hb, 0, qi))
        out_shapes += _state_shapes(b, h, c, d)
        out_specs += [ml_spec, ml_spec, q_spec]
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if quantized:
        sc_spec = pl.BlockSpec((1, 1, span, 1), kv_index)
        in_specs += [sc_spec, sc_spec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    res = pl.pallas_call(
        kernel,
        grid=(b, h // heads, nq, t // span),
        in_specs=in_specs,
        out_specs=out_specs if return_state else q_spec,
        out_shape=out_shapes if return_state else out_shapes[0],
        scratch_shapes=_state_scratch(heads * block_q, d),
        interpret=interpret, name=SELF_KERNEL,
    )(*args)
    return tuple(res) if return_state else res
