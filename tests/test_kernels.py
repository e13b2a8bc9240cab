"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("b,c,h,kvh,d,p", [
    (2, 64, 4, 2, 32, 128),
    (1, 128, 8, 8, 64, 0),
    (2, 32, 4, 1, 16, 96),
    (1, 256, 2, 2, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_attention(b, c, h, kvh, d, p, dtype):
    t = p + c
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, c, h, d), dtype)
    k = _rand(ks[1], (b, t, kvh, d), dtype)
    v = _rand(ks[2], (b, t, kvh, d), dtype)
    out = ops.chunk_attention(q, k, v, causal_offset=p)
    want = ref.chunk_attention_ref(q, k, v, causal_offset=p)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_chunk_attention_blocks():
    """Block-shape invariance: different tilings, same result."""
    b, c, h, kvh, d, p = 1, 128, 4, 2, 64, 64
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, c, h, d), jnp.float32)
    k = _rand(ks[1], (b, p + c, kvh, d), jnp.float32)
    v = _rand(ks[2], (b, p + c, kvh, d), jnp.float32)
    o1 = ops.chunk_attention(q, k, v, causal_offset=p, block_q=32, block_k=32)
    o2 = ops.chunk_attention(q, k, v, causal_offset=p, block_q=128, block_k=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


@pytest.mark.parametrize("b,t,h,p_,g,n,ck", [
    (2, 64, 4, 8, 1, 16, 16),
    (1, 128, 2, 16, 2, 8, 32),
    (1, 96, 4, 8, 4, 8, 32),  # uneven chunk fallback (96 % 32 == 0)
])
def test_ssd(b, t, h, p_, g, n, ck):
    ks = jax.random.split(KEY, 4)
    x = _rand(ks[0], (b, t, h, p_), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, t, h), jnp.float32))
    a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
    bb = _rand(ks[2], (b, t, g, n), jnp.float32)
    cc = _rand(ks[3], (b, t, g, n), jnp.float32)
    dsk = jnp.ones((h,))
    y, st = ops.ssd(x, dt, a_log, bb, cc, dsk, chunk=ck)
    yw, stw = ref.ssd_ref(x, dt, a_log, bb, cc, dsk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw), atol=3e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(stw), atol=3e-4)


def test_ssd_state_carry():
    """Sequential kernel calls with carried state == one long call."""
    b, t, h, p_, g, n = 1, 64, 2, 8, 1, 8
    ks = jax.random.split(KEY, 4)
    x = _rand(ks[0], (b, t, h, p_), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, t, h), jnp.float32))
    a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
    bb = _rand(ks[2], (b, t, g, n), jnp.float32)
    cc = _rand(ks[3], (b, t, g, n), jnp.float32)
    dsk = jnp.ones((h,))
    y_full, st_full = ops.ssd(x, dt, a_log, bb, cc, dsk, chunk=16)
    y1, st1 = ops.ssd(x[:, :32], dt[:, :32], a_log, bb[:, :32], cc[:, :32],
                      dsk, chunk=16)
    y2, st2 = ops.ssd(x[:, 32:], dt[:, 32:], a_log, bb[:, 32:], cc[:, 32:],
                      dsk, chunk=16, init_state=st1)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:]), np.asarray(y2),
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(st_full), np.asarray(st2), atol=3e-4)


@pytest.mark.parametrize("b,h,kvh,d,s", [
    (2, 8, 2, 64, 256),
    (3, 4, 4, 32, 100),
    (1, 16, 2, 128, 1024),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, kvh, d, s, dtype):
    ks = jax.random.split(KEY, 4)
    q = _rand(ks[0], (b, h, d), dtype)
    k = _rand(ks[1], (b, s, kvh, d), dtype)
    v = _rand(ks[2], (b, s, kvh, d), dtype)
    kvl = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = ops.decode_attention(q, k, v, kvl)
    want = ref.decode_attention_ref(q, k, v, kvl)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_decode_attention_ragged_lengths():
    """kv_len masking: garbage beyond the valid length must not leak."""
    b, h, kvh, d, s = 2, 4, 2, 32, 128
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, h, d), jnp.float32)
    k = _rand(ks[1], (b, s, kvh, d), jnp.float32)
    v = _rand(ks[2], (b, s, kvh, d), jnp.float32)
    kvl = jnp.array([17, 64], jnp.int32)
    out1 = ops.decode_attention(q, k, v, kvl)
    # poison the invalid region
    k2 = k.at[0, 17:].set(1e4)
    v2 = v.at[0, 17:].set(-1e4)
    out2 = ops.decode_attention(q, k2, v2, kvl)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# ------------------------------------------ ssm backend knob (stage program)

def test_ssd_backend_registry_and_block_parity():
    """``models.ssm.block_apply`` routes the SSD inner loop through the
    ``SSD_IMPLS`` registry (RunConfig.ssm_backend): pallas (interpret) must
    match the jnp reference through a full Mamba2 block, with and without
    carried state."""
    from repro.configs.base import get_smoke_config, replace as cfg_replace
    from repro.models import ssm as S
    assert set(S.SSD_IMPLS) >= {"jnp", "pallas"}
    cfg = cfg_replace(get_smoke_config("mamba2-130m"), dtype="float32")
    params = S.init(cfg, jax.random.key(0))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = _rand(jax.random.key(1), (2, 64, cfg.d_model), jnp.float32)
    y_j, st_j = S.block_apply(cfg, lp, x, ssd_impl="jnp")
    y_p, st_p = S.block_apply(cfg, lp, x, ssd_impl="pallas")
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_p), atol=3e-4)
    np.testing.assert_allclose(np.asarray(st_j["ssd"]), np.asarray(st_p["ssd"]),
                               atol=3e-4)
    # carried state (the tick-to-tick path the ssm stage program uses)
    st = {"conv": st_j["conv"], "ssd": st_j["ssd"]}
    y_j2, _ = S.block_apply(cfg, lp, x, state=st, ssd_impl="jnp")
    y_p2, _ = S.block_apply(cfg, lp, x, state=st, ssd_impl="pallas")
    np.testing.assert_allclose(np.asarray(y_j2), np.asarray(y_p2), atol=3e-4)
    with pytest.raises(KeyError, match="unknown ssm backend"):
        S.block_apply(cfg, lp, x, ssd_impl="nope")


# ----------------------------------------- non-causal (full-visibility) attn

@pytest.mark.parametrize("b,c,h,kvh,d,t", [
    (2, 32, 4, 2, 40, 96),     # non-lane head dim, prefix-free kv
    (1, 16, 6, 3, 64, 150),    # kv not a block multiple (pad + kv_len mask)
])
def test_full_attention_matches_bidirectional_oracle(b, c, h, kvh, d, t):
    """``ops.full_attention`` (the encdec cross-attention wrapper): every
    query sees every key — must match the naive oracle with masking off."""
    from repro.models import layers as L
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, c, h, d), jnp.float32)
    k = _rand(ks[1], (b, t, kvh, d), jnp.float32)
    v = _rand(ks[2], (b, t, kvh, d), jnp.float32)
    out = ops.full_attention(q, k, v)
    want = L.naive_attention(q, k, v, causal_offset=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------- the kv-head tile of the self-block kernel
# Each case: group size g, kv heads, chunk C, prefix P (keys T = P + C;
# "full": every key visible, causal_offset = T), kv payload, return_state,
# and the tile (heads, block_q, block_k; None = ``chunk_attn.self_tiles``'
# choice from the shapes). "-spans": no VMEM for a whole kv head, so k/v
# come in spans of block_k keys along the grid.
SELF_RETILED_CASES = {
    "g1-causal": (1, 2, 64, 0, "float32", True, (None, None, None)),
    "g4-heads-x2-rows-x4-keys-x2": (4, 2, 64, 0, "float32", True, (2, 16, 32)),
    "g12-heads-x3-prefix": (12, 1, 64, 40, "float32", True, (4, 32, 64)),
    "g4-full-visibility": (4, 2, 32, "full", "float32", True, (2, 16, 64)),
    "g4-kv-tail": (4, 1, 48, 37, "float32", True, (4, 16, 32)),
    "g4-full-kv-tail": (4, 1, 16, "full", "float32", True, (1, 16, 64)),
    "g4-int8-full": (4, 2, 32, "full", "int8", True, (2, 16, 32)),
    "g12-fp8-prefix": (12, 1, 32, 24, "fp8", True, (6, 16, 32)),
    "g4-bf16-no-state": (4, 2, 64, 16, "bfloat16", False, (None, None, None)),
    "g4-keys-below-rows": (4, 1, 64, 32, "float32", False, (1, 64, 16)),
    "g12-c256": (12, 1, 256, 0, "float32", True, (None, None, None)),
    "g4-prefix-spans": (4, 1, 32, 40, "float32", True, (2, 16, 16)),
}


def _quantized(x, kv_dtype):
    """(payload, per-token scales [B, T, KVH], dequantized float32)."""
    top = {"int8": 127.0, "fp8": 448.0}[kv_dtype]
    sc = jnp.max(jnp.abs(x), axis=-1) / top
    y = x / sc[..., None]
    payload = (jnp.round(y).astype(jnp.int8) if kv_dtype == "int8"
               else y.astype(jnp.float8_e4m3fn))
    return payload, sc, payload.astype(jnp.float32) * sc[..., None]


@pytest.mark.parametrize("case", list(SELF_RETILED_CASES))
def test_chunk_attention_retiled_parity(case, monkeypatch):
    """The self-block kernel's kv-head tile — one or several head blocks of
    the g query heads of a kv head, one or several query tiles, unmasked
    key slices of one or several widths and the masked diagonal and
    ``kv_len`` slices — against the materialised oracle, and its fp32
    state against the jnp backend's one-update state: g 1, 4 and 12, a
    causal chunk, a prefix, full visibility, keys that are no multiple of
    the key slice, int8 and fp8 payloads, tile overrides, k/v in spans."""
    from repro.core import attention as A
    from repro.kernels import chunk_attn as ca
    if case.endswith("-spans"):
        monkeypatch.setattr(ca, "PAGED_VMEM_BYTES", 0)
    g, kvh, c, p, kv_dtype, state, (heads, bq, bk) = SELF_RETILED_CASES[case]
    b, d, h = 1, 16, g * kvh
    full = p == "full"
    t = c + (0 if full else p)
    offset = t if full else p
    dt = jnp.bfloat16 if kv_dtype == "bfloat16" else jnp.float32
    ks = jax.random.split(jax.random.key(13), 3)
    q = _rand(ks[0], (b, c, h, d), dt)
    k = _rand(ks[1], (b, t, kvh, d), dt)
    v = _rand(ks[2], (b, t, kvh, d), dt)
    quant, k_ref, v_ref = {}, k, v
    if kv_dtype in ("int8", "fp8"):
        k, k_sc, k_ref = _quantized(k, kv_dtype)
        v, v_sc, v_ref = _quantized(v, kv_dtype)
        quant = {"k_scale": k_sc, "v_scale": v_sc}
    res = ops.chunk_attention(q, k, v, causal_offset=offset, heads=heads,
                              block_q=bq, block_k=bk, return_state=state,
                              **quant)
    out = res[0] if state else res
    want = ref.chunk_attention_ref(q, k_ref, v_ref, causal_offset=offset)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    if not state:
        return
    qg = A.group_queries(q, kvh)
    mask = (jnp.arange(t)[None, :] <= jnp.arange(c)[:, None] + offset)
    st = A.attn_update(qg, k_ref, v_ref, mask[None, None, None],
                       1.0 / np.sqrt(d), A.attn_init(b, c, kvh, g, d))
    got = A.PallasBackend._to_state(*res[1:], kvh)
    for x, y in zip(got, st):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=0,
            atol=1e-6 * max(1.0, float(np.max(np.abs(np.asarray(y))))))


# every served self-block shape: C = T for the 2048-token chunks and the
# mixed-open buckets' chunks, g 4 (qwen3-8b, granite) and 12 (mistral)
SERVED_SELF_SHAPES = [(g, c) for g in (4, 12) for c in (256, 512, 1024, 2048)]


@pytest.mark.parametrize("g,c", SERVED_SELF_SHAPES,
                         ids=[f"g{g}-c{c}" for g, c in SERVED_SELF_SHAPES])
def test_self_tiles_served_shapes(g, c):
    """``self_tiles`` divides each served shape, keeps the fp32 score tile
    within its budget and the diagonal square with the query tile: no key
    slice lies wholly above the diagonal (a tile computes at most half a
    square past its rows' diagonals), and a 2048-token chunk computes at
    most 1.15 times its causal pairs."""
    from repro.kernels import chunk_attn as ca
    heads, block_q, block_k = tiles = ca.self_tiles(g, c, c, 128, 0)
    assert g % heads == 0 and c % block_q == 0 and c % block_k == 0
    assert block_q % 128 == 0 and block_k % block_q == 0
    assert 4 * heads * block_q * block_k <= ca.PAGED_SCORE_BYTES
    computed, useful = ca.self_block_pairs(c, c, 0, tiles)
    assert useful == c * (c + 1) // 2
    assert useful <= computed <= useful + (c // block_q) * block_q ** 2 // 2
    if c == 2048:
        assert computed / useful <= 1.15
