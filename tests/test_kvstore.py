"""KV page store tests (repro.kvstore): codec round-trip error bounds per
config family, page-table collision-freedom over a full MBKR steady-state
cycle, quantized-byte lease accounting under mixed-bucket admission, the
attention-output error bound for int8 pages (both backends, p99 <= the
deep-int8 tolerance), tier planning / cold staging, and the end-to-end
pipeline parity run with quantized pages."""
import math

import numpy as np
import pytest

from tests.helpers.subproc import run_pipeline_check

DEEP_INT8_P99_TOL = 0.05   # the historical deep-int8 spill tolerance


# ------------------------------------------------------- codec round trips

# (family, kv tensor shape [lps, B, C, kvh, hd]) — per config family so
# head-count/head-dim geometry differences are exercised
FAMILY_SHAPES = [
    ("dense-qwen3-8b", (2, 2, 32, 4, 64)),
    ("moe-qwen2", (2, 1, 16, 2, 32)),
    ("hybrid-zamba2", (1, 2, 16, 8, 40)),      # non-lane head dim
    ("encdec-whisper", (2, 1, 64, 6, 48)),
]

@pytest.mark.parametrize("dtype", ["int8", "fp8"])
@pytest.mark.parametrize("family,shape", FAMILY_SHAPES)
def test_codec_round_trip_bounds(dtype, family, shape):
    """Round-trip error against the CODEC's own bound, elementwise:

    - int8: round-to-nearest on a per-page per-head grid of step ``scale``
      => |err| <= scale / 2 everywhere (exact by construction);
    - fp8-e4m3: payloads live in [0, 448] with ulp <= 32 at the top bin
      => |err| <= 32 * scale = amax / 14.

    Plus a signal-level check (what attention accuracy depends on): int8's
    RMS error stays under 1% of the per-head amax (its half-step bound
    makes it at most amax / 254); fp8 rounds each element to nearest with
    3 mantissa bits, so its error is at most |x| / 16, or half the
    smallest subnormal step (2^-10 x scale) near zero, and the RMS error
    at most 1/16 of the signal's. The data is seeded by the family's name
    (crc32: the same in every process).
    """
    import zlib

    import jax
    import jax.numpy as jnp
    from repro.kvstore import quant as Q
    codec = Q.get_codec(dtype)
    kv = jax.random.normal(jax.random.key(zlib.crc32(family.encode())),
                           shape, jnp.float32)
    pages = 4 if shape[2] % 4 == 0 else 1
    payload, scale = Q.encode(codec, kv, pages=pages)
    assert str(payload.dtype) == codec.storage_dtype
    scale_tok = np.asarray(Q.expand_page_scale(scale, shape[2] // pages))
    back = np.asarray(Q.decode(payload, Q.expand_page_scale(
        scale, shape[2] // pages)))
    err = np.abs(back - np.asarray(kv))
    step = scale_tok * (0.5 if dtype == "int8" else 32.0)
    assert (err <= step * (1 + 1e-5)).all(), f"{family}/{dtype}"
    if dtype == "int8":
        rms = np.sqrt(np.mean((err / (scale_tok * 127.0)) ** 2))
        assert rms < 0.01, f"{family}/{dtype}: rms/amax {rms}"
    else:
        x = np.abs(np.asarray(kv))
        assert (err <= (x / 16 + scale_tok * 2.0**-10) * (1 + 1e-5)).all()
        rms, signal = np.sqrt(np.mean(err ** 2)), np.sqrt(np.mean(x ** 2))
        assert rms <= signal / 16, f"{family}/{dtype}: rms {rms}"


def test_codec_auto_is_identity():
    import jax
    import jax.numpy as jnp
    from repro.kvstore import quant as Q
    codec = Q.get_codec("auto", "bfloat16")
    assert codec.name == "bfloat16" and not codec.quantized
    kv = jax.random.normal(jax.random.key(0), (2, 4, 2, 8), jnp.bfloat16)
    payload, scale = Q.encode(codec, kv)
    assert scale is None
    assert (np.asarray(payload, np.float32)
            == np.asarray(kv, np.float32)).all()


def test_pages_scatter_gather_round_trip():
    import jax
    import jax.numpy as jnp
    from repro.kvstore import pages as PG
    from repro.kvstore import quant as Q
    geom = PG.page_geometry(16, 5, kv_page_tokens=4)
    tbl = PG.build_slot_pages(geom)
    codec = Q.get_codec("int8")
    pool = PG.alloc_pool(geom, codec, lps=2, b=1, kvh=3, hd=8)
    k = jax.random.normal(jax.random.key(1), (2, 1, 16, 3, 8))
    v = jax.random.normal(jax.random.key(2), (2, 1, 16, 3, 8))
    pool = PG.scatter_chunk(pool, jnp.asarray(tbl[2]), k, v, codec)
    for li in range(2):
        sl = lambda a: a[:, li]
        kq, vq, ks, vs = PG.gather_chunk(sl(pool.k), sl(pool.v),
                                         sl(pool.k_scale), sl(pool.v_scale),
                                         jnp.asarray(tbl[2]))
        scale_tok = np.asarray(Q.expand_page_scale(ks, geom.page_tokens))
        kd = np.asarray(Q.decode(kq, Q.expand_page_scale(ks, geom.page_tokens)))
        err = np.abs(kd - np.asarray(k[li]))
        assert (err <= scale_tok * 0.5 * (1 + 1e-5)).all()


# ------------------------------------ page-table collision freedom (MBKR)

@pytest.mark.parametrize("m,n", [(16, 16), (16, 8), (8, 8), (24, 16), (12, 4)])
@pytest.mark.parametrize("ppc_tokens", [0, 4])
def test_page_table_collision_free_steady_state(m, n, ppc_tokens):
    """Replay the MBKR back-to-back steady state at PAGE granularity on a
    (stage, pair) couple: no live page is ever overwritten, and every
    pool-scan read finds all of its chunk's pages. This is the page-level
    analogue of ``mbkr.verify_plan`` — the slot plan's collision-freedom
    must survive the slot->page indirection."""
    from repro.core import mbkr
    from repro.kvstore import pages as PG
    pl = mbkr.plan(m, n)
    mbkr.verify_plan(pl)                      # slot level (precondition)
    chunk_len = 16
    geom = PG.page_geometry(chunk_len, pl.num_slots, ppc_tokens)
    tbl = PG.build_slot_pages(geom)
    PG.verify_page_plan(tbl, geom)            # handles are a bijection
    if pl.p2 >= m:
        return                                # no spilling: trivial buffer

    n2 = n // 2
    # page pools of me (stage 0) and my pair (stage n2):
    # page id -> (owner, req, chunk, death_tick)
    pools = {0: {}, 1: {}}
    stage_of = {0: 0, 1: n2}
    host_table = {0: pl.host_slot_a, 1: pl.host_slot_b}

    def phase(me, t):
        tt = t - stage_of[me]
        return tt % m, tt // m

    def write(pool, pages, entry, t):
        for pid in pages:
            prev = pool.get(int(pid))
            assert prev is None or prev[3] < t, \
                ("live page overwritten", t, pid, prev, entry)
            pool[int(pid)] = entry

    for t in range(n2, 4 * m + n2):
        for me in (0, 1):
            phi, req = phase(me, t)
            if req < 0:
                continue
            other = 1 - me
            death = t + (m - 1 - phi)
            if phi < pl.p2:
                write(pools[me], tbl[int(pl.own_slot[phi])],
                      (me, req, phi, death), t)
            else:
                write(pools[other], tbl[int(host_table[other][phi])],
                      (me, req, phi, death), t)
        for me in (0, 1):
            phi, req = phase(me, t)
            if req < 1:
                continue
            other = 1 - me
            for j in range(phi + 1):
                if j < pl.p2:
                    pages = tbl[int(pl.own_slot[j])]
                    pool = pools[me]
                else:
                    pages = tbl[int(host_table[other][j])]
                    pool = pools[other]
                for pid in pages:
                    e = pool.get(int(pid))
                    assert e and e[:3] == (me, req, j), \
                        ("page miss", t, me, j, pid, e)


# --------------------------------------- quantized-byte lease accounting

def _continuous(kv_dtype, buckets=(16384, 65536), inflight=2):
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.runtime.engine import ContinuousEngine, EngineConfig, SimExecutor
    cfg = get_config("llama3-70b")
    ec = EngineConfig(model=cfg, hw=cm.WSC_PAPER, num_stages=16, tp=1,
                      num_chunks=16, max_batch=8, buckets=buckets,
                      partition="uniform", kv_dtype=kv_dtype,
                      inflight=inflight)
    return ContinuousEngine(ec, SimExecutor(cfg, ec.hw))


def test_lease_hwm_within_budget_quantized_mixed_buckets():
    """hwm <= budget must hold with int8 byte accounting under mixed-bucket
    admission, and the quantized high-water mark must sit near the codec's
    compression factor of the bf16 one (leases count STORED bytes)."""
    from repro.runtime.engine import Request
    hwms = {}
    for kv_dtype in ("auto", "int8"):
        eng = _continuous(kv_dtype)
        for i in range(12):
            eng.submit(Request(rid=i, arrival=0.0,
                               seq_len=16384 if i % 3 else 65536))
        eng.run_until_drained()
        assert eng.metrics()["completed"] == 12
        assert (eng.lease.hwm <= eng.lease.budget * (1 + 1e-9)).all(), kv_dtype
        hwms[kv_dtype] = eng.lease.hwm.max()
    # int8 stored bytes ~ 0.5x bf16 (+ per-page scale overhead)
    ratio = hwms["int8"] / hwms["auto"]
    assert 0.45 < ratio < 0.60, ratio


def test_quantized_leases_admit_what_bf16_cannot():
    """Admission capacity grows with the codec: at a budget of ONE request's
    worth of MBKR slots (inflight=1, 12 slots vs a 16-chunk peak residency),
    bf16 requests cannot be admitted at all, while int8 accounting (~0.52x
    stored bytes) fits every one of them under the SAME physical budget."""
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.runtime.engine import (ContinuousEngine, EngineConfig,
                                      Request, SimExecutor)
    cfg = get_config("llama3-70b")
    done, refusals = {}, {}
    for kv_dtype in ("auto", "int8"):
        ec = EngineConfig(model=cfg, hw=cm.WSC_PAPER, num_stages=16, tp=1,
                          num_chunks=16, max_batch=8, buckets=(131072,),
                          partition="uniform", kv_dtype=kv_dtype, inflight=1)
        eng = ContinuousEngine(ec, SimExecutor(cfg, ec.hw))
        for i in range(10):
            eng.submit(Request(rid=i, arrival=0.0, seq_len=131072))
        eng.run_until_drained()
        assert (eng.lease.hwm <= eng.lease.budget * (1 + 1e-9)).all()
        done[kv_dtype] = eng.metrics()["completed"]
        refusals[kv_dtype] = eng.lease.refusals
    assert done["auto"] == 0 and refusals["auto"] == 10, (done, refusals)
    assert done["int8"] == 10 and refusals["int8"] == 0, (done, refusals)


# --------------------------- attention-output error bound (both backends)

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_int8_attention_output_p99_within_deep_tolerance(backend):
    """The acceptance bound: int8-paged attention OUTPUT error (one full
    pool-scan + self block composite, either backend) stays at p99 <= the
    deep-int8 tolerance, against the fp32 unquantized reference."""
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    from repro.kvstore import pages as PG
    from repro.kvstore import quant as Q
    b, c, kvh, g, d, nchunks = 2, 32, 2, 3, 64, 5
    geom = PG.page_geometry(c, nchunks, kv_page_tokens=8)
    tbl = PG.build_slot_pages(geom)
    codec = Q.get_codec("int8")
    ks = jax.random.split(jax.random.key(7), 2 * nchunks + 3)
    qg = jax.random.normal(ks[0], (b, c, kvh, g, d), jnp.float32)
    k_self = jax.random.normal(ks[1], (b, c, kvh, d), jnp.float32)
    v_self = jax.random.normal(ks[2], (b, c, kvh, d), jnp.float32)
    scale = 1.0 / math.sqrt(d)

    pool = PG.alloc_pool(geom, codec, lps=1, b=b, kvh=kvh, hd=d)
    chunks = []
    for j in range(nchunks):
        kj = jax.random.normal(ks[3 + 2 * j], (1, b, c, kvh, d), jnp.float32)
        vj = jax.random.normal(ks[4 + 2 * j], (1, b, c, kvh, d), jnp.float32)
        chunks.append((kj[0], vj[0]))
        pool = PG.scatter_chunk(pool, jnp.asarray(tbl[j]), kj, vj, codec)

    be = A.get_backend(backend)
    sl = lambda a: a[:, 0]
    pool_l = (sl(pool.k), sl(pool.v), sl(pool.k_scale), sl(pool.v_scale))
    slot_chunk = np.concatenate([np.arange(nchunks), [-1]]).astype(np.int32)
    st = A.attn_init(b, c, kvh, g, d)
    st = A.pool_scan(be, qg, pool_l, tbl, slot_chunk, jnp.int32(nchunks),
                     scale, st)
    st = be.self_block(qg, k_self, v_self, scale, st)
    out = np.asarray(A.attn_finish(st, jnp.float32))

    ref_be = A.get_backend("jnp")
    st_r = A.attn_init(b, c, kvh, g, d)
    for j, (kj, vj) in enumerate(chunks):
        st_r = ref_be.chunk_block(qg, kj, vj, jnp.bool_(True), scale, st_r)
    st_r = ref_be.self_block(qg, k_self, v_self, scale, st_r)
    ref = np.asarray(A.attn_finish(st_r, jnp.float32))

    # normalize by the output's signal level: attention outputs of random
    # KV center on zero, so elementwise relative error is ill-posed there
    err_p99 = float(np.percentile(np.abs(out - ref), 99))
    rms = float(np.sqrt(np.mean(ref ** 2)))
    assert err_p99 / rms <= DEEP_INT8_P99_TOL, \
        f"{backend}: p99/rms {err_p99 / rms}"
    assert np.isfinite(out).all()


# --------------------------------------------------- tiers / cold staging

def test_tier_plan_prefetch_feasibility():
    from repro.core import mbkr
    from repro.kvstore import pages as PG
    from repro.kvstore import quant as Q
    from repro.kvstore import tiers as TR
    m, n = 16, 16
    pl = mbkr.plan(m, n)
    geom = PG.page_geometry(128, pl.num_slots, 32)
    tbl = PG.build_slot_pages(geom)
    codec = Q.get_codec("int8")
    dims = dict(lps=4, b=1, kvh=8, hd=128)
    cb = TR.chunk_page_bytes(geom, codec, **dims)
    # hot budget for half the own chunks -> the rest go cold
    spec = TR.TierSpec(hot_bytes=cb * pl.p2 / 2, cold_bw=1e12)
    plan = TR.plan_tiers(geom, codec, tbl, pl.own_slot, pl.p2, m, spec,
                         **dims, tick_s=np.full(m, 1e-3))
    assert plan.feasible
    assert plan.cold_bytes > 0 and plan.hot_bytes <= spec.hot_bytes * (1 + 1e-9)
    # every cold page must be prefetched BEFORE its due tick
    assert all(op.issue_tick < op.due_tick for op in plan.prefetch)
    # starving the staging link must flip feasibility
    slow = TR.plan_tiers(geom, codec, tbl, pl.own_slot, pl.p2, m,
                         TR.TierSpec(hot_bytes=spec.hot_bytes, cold_bw=1.0),
                         **dims, tick_s=np.full(m, 1e-3))
    assert not slow.feasible


def test_max_seq_len_int8_vs_bf16_ratio():
    """Equal per-stage byte budget: int8 pages must admit >= 1.5x the bf16
    max feasible sequence length (the benchmark's acceptance floor)."""
    from repro.kvstore import quant as Q
    from repro.kvstore import tiers as TR
    kw = dict(kv_token_bytes=4096.0, num_chunks=16, num_stages=16,
              page_tokens=64, head_dim=128)
    s_bf16 = TR.max_seq_len_for_budget(1e9, codec=Q.get_codec("bfloat16"), **kw)
    s_int8 = TR.max_seq_len_for_budget(1e9, codec=Q.get_codec("int8"), **kw)
    assert s_int8 >= 1.5 * s_bf16, (s_int8, s_bf16)


def test_host_offload_stager_round_trip():
    import jax
    import jax.numpy as jnp
    from repro.kvstore.tiers import HostOffloadStager
    pages = jax.random.normal(jax.random.key(0), (8, 2, 4, 2, 8))
    ref = np.asarray(pages)
    st = HostOffloadStager()
    parked = st.offload("k", pages, [1, 5, 6])
    assert st.host_bytes() > 0
    assert (np.asarray(parked)[[1, 5, 6]] == 0).all()       # cleared on device
    assert (np.asarray(parked)[[0, 2, 3, 4, 7]] == ref[[0, 2, 3, 4, 7]]).all()
    back = st.restore("k", parked)
    np.testing.assert_array_equal(np.asarray(back), ref)
    assert st.host_bytes() == 0


# ------------------------------------------------ end-to-end (subprocess)

def test_pipeline_int8_pages_backend_parity():
    """Deep pipeline, int8 KV pages, 4-token pages, both backends: jnp and
    pallas read the SAME quantized pages and must agree; end-to-end logits
    stay within the documented int8 tail bounds and the argmax matches."""
    run_pipeline_check("qwen3-8b", "mocap", "qship", deep=True,
                       backend="both", kv_dtype="int8", page_tokens=4,
                       expect="PASS backend-parity")


def test_pipeline_fp8_pages():
    run_pipeline_check("qwen3-8b", "mocap", "fetch", deep=True,
                       backend="jnp", kv_dtype="fp8", page_tokens=8)
