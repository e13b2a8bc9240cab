"""Page-table KV store: the byte layer under the MBKR slot plan.

The pre-kvstore pool stored whole-chunk KV arrays indexed directly by slot
id. Here the unit of storage is a fixed-size PAGE of ``page_tokens`` tokens;
a chunk occupies ``pages_per_chunk`` pages, and a device-resident page table
(``slot_pages [slots+1, ppc]``, a static numpy array that lowers to an HLO
constant) maps each MBKR slot to its physical page handles. Slot semantics —
which chunk lives in which slot at which phase — stay entirely in
``core.mbkr``; this module only owns where the bytes of a slot live, so
creditor/debtor reallocation is page-handle movement: the spill wire carries
encoded pages + scales and the creditor scatters them under ITS page table.

Pages are stored encoded (``kvstore.quant``): payload arrays in the codec's
storage dtype plus per-(page, layer, batch, kv-head) fp32 scales when
quantized — block-wise quantization at page granularity, so smaller pages
mean tighter amax windows and lower error.

Layouts (P = total physical pages incl. the scratch slot's):
    k_pages / v_pages   [P, lps, B, kvh, page_tokens, hd]   storage dtype
    k_scale / v_scale   [P, lps, B, kvh, 1, 1]              fp32 (quantized)

Pages are HEAD-MAJOR so one kv head of one page is a run of whole
(tokens, hd) tiles — the slice the paged kernel DMAs. Chunks outside the
store keep the token-major ``[.., C, kvh, hd]`` layout with per-page scales
``[ppc, .., 1, kvh, 1]`` (``kvstore.quant``); scatter and gather transpose.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvstore import quant as Q


@dataclass(frozen=True)
class PageGeometry:
    """Static page layout of one stage's pool."""
    chunk_len: int
    page_tokens: int
    pages_per_chunk: int
    num_slots: int            # excl. scratch
    num_pages: int            # (num_slots + 1) * pages_per_chunk

    @property
    def scratch_slot(self) -> int:
        return self.num_slots


def page_geometry(chunk_len: int, num_slots: int,
                  kv_page_tokens: int = 0) -> PageGeometry:
    """``kv_page_tokens`` 0 (or >= chunk) means one page per chunk; otherwise
    it is rounded down to the largest divisor of ``chunk_len`` so chunks stay
    page-aligned (uniform chunks; LBCP buckets pad to the bucket)."""
    pt = kv_page_tokens if 0 < kv_page_tokens < chunk_len else chunk_len
    while chunk_len % pt:
        pt -= 1
    ppc = chunk_len // pt
    return PageGeometry(chunk_len, pt, ppc, num_slots,
                        (num_slots + 1) * ppc)


def build_slot_pages(geom: PageGeometry) -> np.ndarray:
    """slot -> physical page handles, [slots+1, ppc] int32.

    Pages of one slot are STRIDED across the physical array (handle =
    j * (slots+1) + slot) rather than contiguous, so nothing downstream can
    silently rely on slot-major contiguity — every read/write goes through
    the table, which is what makes reallocation pure handle movement."""
    s1 = geom.num_slots + 1
    tbl = np.empty((s1, geom.pages_per_chunk), np.int32)
    for s in range(s1):
        for j in range(geom.pages_per_chunk):
            tbl[s, j] = j * s1 + s
    return tbl


def handle_rows(slot_pages, slots=None):
    """Export the page-handle rows the PAGED pool kernel consumes as its
    scalar-prefetch argument (``kernels.ops.pool_attention_paged``): the
    [S, ppc] rows of the visited slots — all non-scratch slots, or the
    ``slots`` subset (creditor scan). Static numpy in, static numpy out (the
    handles lower to an HLO constant and land in SMEM before the grid
    runs); traced tables pass through as jnp."""
    if isinstance(slot_pages, np.ndarray):
        rows = (slot_pages[:-1] if slots is None
                else slot_pages[np.asarray(slots, np.int32)])
        return rows.astype(np.int32)
    tbl = jnp.asarray(slot_pages, jnp.int32)
    return tbl[:-1] if slots is None else tbl[jnp.asarray(slots)]


def verify_page_plan(slot_pages: np.ndarray, geom: PageGeometry) -> None:
    """Page handles must be a bijection onto [0, num_pages): distinct slots
    own disjoint page sets, so slot-level collision-freedom (``mbkr.
    verify_plan``) implies page-level collision-freedom. Raises on violation."""
    flat = slot_pages.ravel()
    assert flat.size == geom.num_pages, (flat.size, geom.num_pages)
    assert flat.min() >= 0 and flat.max() < geom.num_pages
    assert np.unique(flat).size == flat.size, "page handle collision"


# --------------------------------------------------------------------- pool

@dataclass
class PagedPool:
    """Device-resident paged KV pool (a jax pytree; scales None when the
    codec is passthrough)."""
    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None


def _pool_flatten(p: PagedPool):
    return (p.k, p.v, p.k_scale, p.v_scale), None


def _pool_unflatten(_, children):
    return PagedPool(*children)


jax.tree_util.register_pytree_node(PagedPool, _pool_flatten, _pool_unflatten)


def alloc_pool(geom: PageGeometry, codec: Q.KVCodec, lps: int, b: int,
               kvh: int, hd: int) -> PagedPool:
    shape = (geom.num_pages, lps, b, kvh, geom.page_tokens, hd)
    dt = jnp.dtype(codec.storage_dtype)
    k = jnp.zeros(shape, dt)
    v = jnp.zeros(shape, dt)
    if not codec.quantized:
        return PagedPool(k, v)
    sshape = (geom.num_pages, lps, b, kvh, 1, 1)
    one = jnp.ones(sshape, jnp.float32)  # benign scale for never-written pages
    return PagedPool(k, v, one, one)


def pool_bytes(geom: PageGeometry, codec: Q.KVCodec, lps: int, b: int,
               kvh: int, hd: int) -> float:
    """Total device bytes of one stage's paged pool (k + v + scales)."""
    payload = 2.0 * geom.num_pages * lps * b * geom.page_tokens * kvh * hd \
        * codec.bytes_per_el
    scales = 2.0 * geom.num_pages * codec.scale_bytes_per_page(lps, b, kvh)
    return payload + scales


# ----------------------------------------------------------- write (scatter)

def _paginate(x: jax.Array, ppc: int) -> jax.Array:
    """[lps, B, C, kvh, hd] -> [ppc, lps, B, kvh, page_tokens, hd]."""
    lps, b, c, kvh, hd = x.shape
    x = x.reshape(lps, b, ppc, c // ppc, kvh, hd)
    return x.transpose(2, 0, 1, 4, 3, 5)


def scatter_chunk(pool: PagedPool, pages: jax.Array, k: jax.Array,
                  v: jax.Array, codec: Q.KVCodec) -> PagedPool:
    """Encode one chunk's fresh KV ([lps, B, C, kvh, hd]) block-wise (one
    scale per page) and scatter its pages to the handles ``pages`` [ppc]
    (traced)."""
    ppc = pages.shape[0]
    kq, ks = Q.encode(codec, k, pages=ppc)
    vq, vs = Q.encode(codec, v, pages=ppc)
    return scatter_chunk_raw(pool, pages, kq, vq, ks, vs)


def scatter_chunk_raw(pool: PagedPool, pages: jax.Array, kq: jax.Array,
                      vq: jax.Array, ks: Optional[jax.Array],
                      vs: Optional[jax.Array]) -> PagedPool:
    """Scatter already-encoded chunk KV (the creditor side of a spill: the
    wire delivered payload + per-page scales [ppc, lps, B, 1, kvh, 1]; only
    handles move locally). One batched scatter per tensor — page handles of
    one slot are disjoint by the table bijection (``verify_page_plan``)."""
    ppc = pages.shape[0]
    kp = _paginate(kq, ppc).astype(pool.k.dtype)
    vp = _paginate(vq, ppc).astype(pool.v.dtype)
    k_pool = pool.k.at[pages].set(kp)
    v_pool = pool.v.at[pages].set(vp)
    k_sc, v_sc = pool.k_scale, pool.v_scale
    if k_sc is not None:
        k_sc = k_sc.at[pages].set(jnp.swapaxes(ks, -3, -2))
        v_sc = v_sc.at[pages].set(jnp.swapaxes(vs, -3, -2))
    return PagedPool(k_pool, v_pool, k_sc, v_sc)


# ------------------------------------------------------------ read (gather)

def gather_chunk(k_l: jax.Array, v_l: jax.Array,
                 ks_l: Optional[jax.Array], vs_l: Optional[jax.Array],
                 pages: jax.Array
                 ) -> Tuple[jax.Array, jax.Array,
                            Optional[jax.Array], Optional[jax.Array]]:
    """Gather one slot's chunk from LAYER-SLICED pool arrays — the
    jnp-REFERENCE feed (per-slot scan order and the streamed fetch wire),
    not a perf path: the paged kernel (``ops.pool_attention_paged``) reads
    pages in place and never materializes this copy.

    k_l/v_l [P, B, kvh, page_tokens, hd]; ks_l/vs_l [P, B, kvh, 1, 1];
    pages [ppc] (traced). Returns the ENCODED chunk ([B, C, kvh, hd] payload
    + per-PAGE scales [ppc, B, 1, kvh, 1]) — decode is the reader's
    business (the jnp backend multiplies out; the Pallas kernel dequantizes
    in its epilogue)."""
    kq = jnp.take(k_l, pages, axis=0)          # [ppc, B, kvh, pt, hd]
    vq = jnp.take(v_l, pages, axis=0)
    ppc, b, kvh, pt, hd = kq.shape
    kq = kq.transpose(1, 0, 3, 2, 4).reshape(b, ppc * pt, kvh, hd)
    vq = vq.transpose(1, 0, 3, 2, 4).reshape(b, ppc * pt, kvh, hd)
    ks = vs = None
    if ks_l is not None:                       # -> [ppc, B, 1, kvh, 1]
        ks = jnp.swapaxes(jnp.take(ks_l, pages, axis=0), -3, -2)
        vs = jnp.swapaxes(jnp.take(vs_l, pages, axis=0), -3, -2)
    return kq, vq, ks, vs


def gather_chunks(k_l: jax.Array, v_l: jax.Array,
                  ks_l: Optional[jax.Array], vs_l: Optional[jax.Array],
                  page_rows: jax.Array
                  ) -> Tuple[jax.Array, jax.Array,
                             Optional[jax.Array], Optional[jax.Array]]:
    """``gather_chunk`` over a STACK of slots in one shot: ``page_rows``
    [S, ppc] (traced) -> payloads [S, B, C, kvh, hd] + per-page scales
    [S, ppc, B, 1, kvh, 1]. One batched take per tensor.

    ORACLE FEED ONLY: this materializes the dense slot stack in HBM — the
    input of the gathered slot-grid kernel (``kernels.ops.pool_attention``),
    kept as the reference the paged path is reconciled against. The perf
    path (``pool_backend="paged"``) skips it entirely: the paged kernel
    takes ``handle_rows`` and reads pages in place."""
    s, ppc = page_rows.shape
    flat = page_rows.reshape(-1)
    kq = jnp.take(k_l, flat, axis=0)           # [S*ppc, B, kvh, pt, hd]
    vq = jnp.take(v_l, flat, axis=0)
    _, b, kvh, pt, hd = kq.shape
    kq = kq.reshape(s, ppc, b, kvh, pt, hd).transpose(0, 2, 1, 4, 3, 5) \
           .reshape(s, b, ppc * pt, kvh, hd)
    vq = vq.reshape(s, ppc, b, kvh, pt, hd).transpose(0, 2, 1, 4, 3, 5) \
           .reshape(s, b, ppc * pt, kvh, hd)
    ks = vs = None
    if ks_l is not None:                       # -> [S, ppc, B, 1, kvh, 1]
        ks = jnp.swapaxes(jnp.take(ks_l, flat, axis=0), -3, -2).reshape(
            s, ppc, b, 1, kvh, 1)
        vs = jnp.swapaxes(jnp.take(vs_l, flat, axis=0), -3, -2).reshape(
            s, ppc, b, 1, kvh, 1)
    return kq, vq, ks, vs
