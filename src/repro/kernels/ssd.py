"""Pallas TPU kernel: Mamba2 chunked SSD (state-space duality) scan.

One grid step computes one (batch, head, chunk) cell: the intra-chunk
"diagonal" attention-like term, the chunk's contribution to the running SSM
state, and the inter-chunk "off-diagonal" term read from the state carried in
fp32 VMEM scratch. The chunk axis is the innermost grid dimension, which TPU
executes SEQUENTIALLY — the scratch state [P, N] persists across chunk steps
and is reset at chunk 0 (this is how the recurrence crosses chunk boundaries
without leaving VMEM).

Layout: the wrapper hands the kernel head-major operands, so every block is
a whole [Q, P] / [Q, N] / [P, N] tile of one head (Mosaic tiles the last two
dims by 8 x 128 or takes them whole). dt arrives as a [1, Q] row; its
column and the running sums of dt·A in both orientations are float32
matmuls with an identity or a triangular mask (no in-kernel transpose or
cumsum).
The per-head A_log and D live in SMEM. The big products take the input's
dtype on the MXU (bf16 on the served path) with fp32 accumulation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _exact(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _mm(a, b, dims, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _ssd_kernel(a_ref, dskip_ref, x_ref, dt_ref, b_ref, c_ref,
                init_ref, y_ref, st_out_ref, state_ref, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _reset():
        state_ref[...] = init_ref[0, 0].astype(jnp.float32)

    dtype = x_ref.dtype
    x = x_ref[0, 0].astype(jnp.float32)              # [Q, P]
    a = -jnp.exp(a_ref[hi])                          # scalar A of this head
    dt_row = dt_ref[0, 0].astype(jnp.float32)        # [1, Q]
    da_row = dt_row * a
    bm = b_ref[0, 0]                                 # [Q, N]
    cm = c_ref[0, 0]                                 # [Q, N]

    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_j <= iota_i
    tril = causal.astype(jnp.float32)
    eye = (iota_i == iota_j).astype(jnp.float32)
    # dt as a column, and the running sums of dt·A, cs[i] = sum_{k <= i}
    # da[k], as a row and a column
    dt_col = _exact(eye, dt_row, ((1,), (1,)))                         # [Q, 1]
    cs_row = _exact(da_row, tril, ((1,), (1,)))                        # [1, Q]
    cs_col = _exact(tril, da_row, ((1,), (1,)))                        # [Q, 1]
    total = jnp.sum(da_row, axis=1, keepdims=True)                     # [1, 1]
    xdt = x * dt_col                                                   # [Q, P]

    # intra-chunk: L[i,j] = exp(cs[i] - cs[j]) for j <= i
    lmat = jnp.where(causal, jnp.exp(cs_col - cs_row), 0.0)
    cb = _mm(cm, bm, ((1,), (1,)), dtype)                              # [Q, Q]
    y_diag = _mm(cb * lmat, xdt, ((1,), (0,)), dtype)                  # [Q, P]

    # off-diagonal: read the carried state
    state = state_ref[...]                                             # [P, N]
    y_off = _mm(cm, state, ((1,), (1,)), dtype) * jnp.exp(cs_col)      # [Q, P]

    y = y_diag + y_off + x * dskip_ref[hi]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state' = state * exp(sum da) + sum_q exp(cs[-1] - cs[q]) (x dt)[q] B[q]
    upd = _mm(xdt * jnp.exp(total - cs_col), bm, ((0,), (0,)), dtype)  # [P, N]
    state_ref[...] = state * jnp.exp(total) + upd

    @pl.when(ci == nc - 1)
    def _emit_state():
        st_out_ref[0, 0] = state_ref[...]


def ssd_pallas(x: jax.Array, dt: jax.Array, a_log: jax.Array,
               b: jax.Array, c: jax.Array, d_skip: jax.Array, *,
               chunk: int = 128, init_state: Optional[jax.Array] = None,
               n_groups: int = 1, interpret: bool = False):
    """Chunked SSD. x [B,T,H,P]; dt [B,T,H] (post-softplus); a_log [H];
    b, c [B,T,G,N]; d_skip [H]. T must be a multiple of ``chunk``.
    Returns (y [B,T,H,P] fp32-accurate in x.dtype, final_state [B,H,P,N] fp32).

    Groups (G < H) are mapped per-head in the B/C BlockSpec index maps.
    """
    bs, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg = h // g
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    if init_state is None:
        init_state = jnp.zeros((bs, h, p, n), jnp.float32)
    head_major = lambda a: jnp.moveaxis(a, 2, 1)          # [B,T,H,..] -> [B,H,T,..]
    dth = head_major(dt.astype(jnp.float32))[:, :, None]  # [B,H,1,T]

    grid = (bs, h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem, smem,
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi // hg, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi // hg, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, h, t, p), x.dtype),
            jax.ShapeDtypeStruct((bs, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(a_log.astype(jnp.float32), d_skip.astype(jnp.float32), head_major(x),
      dth, head_major(b), head_major(c),
      init_state)
    return head_major(y), st
