"""Batched slot-grid pool attention (`kernels.ops.pool_attention`): the
single-launch pool scan must reproduce the per-slot scan's state — the
combine algebra is associative, but batched (online across slots inside the
kernel) and scanned (per-slot state + traced-level `attn_combine`) evaluate
in different floating-point orders, so the reconciliation is asserted
explicitly here: within 1e-6 (fp32 combine) on float pages, < 2e-3 headroom
on int8 pages (both paths read IDENTICAL quantized pages, so the observed
divergence stays at fp32-rounding level).

Also: the launch count (`ops.count_launches`) pins the O(1)-in-pool-
depth property, and a hypothesis property test sweeps ragged occupancy
(random slot subsets, mixed chunk ids vs. limit, empty pool, single slot).
"""
import math

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # CI installs hypothesis; bare containers may not
    given = None


def _require_jax():
    import jax  # noqa: F401
    return jax


def _build_pool(nslots, kv_dtype, b, c, kvh, d, page_tokens, seed=7):
    """Paged pool with ``nslots`` random chunks scattered under the table."""
    import jax
    import jax.numpy as jnp
    from repro.kvstore import pages as PG
    from repro.kvstore import quant as Q
    geom = PG.page_geometry(c, nslots, page_tokens)
    tbl = PG.build_slot_pages(geom)
    codec = Q.get_codec(kv_dtype, "float32")
    pool = PG.alloc_pool(geom, codec, 1, b, kvh, d)
    keys = jax.random.split(jax.random.key(seed), max(2 * nslots, 1))
    for s in range(nslots):
        k = jax.random.normal(keys[2 * s], (1, b, c, kvh, d), jnp.float32)
        v = jax.random.normal(keys[2 * s + 1], (1, b, c, kvh, d), jnp.float32)
        pool = PG.scatter_chunk(pool, jnp.asarray(tbl[s]), k, v, codec)
    sl = lambda a: None if a is None else a[:, 0]
    pool_l = (sl(pool.k), sl(pool.v), sl(pool.k_scale), sl(pool.v_scale))
    return geom, tbl, pool_l


def _scan_states(pool_l, tbl, slot_chunk, limit, qg, slots=None):
    """(jnp per-slot, pallas per-slot, pallas batched) finished outputs +
    raw states for one occupancy pattern."""
    import jax.numpy as jnp
    from repro.core import attention as A
    b, c, kvh, g, d = qg.shape
    scale = 1.0 / math.sqrt(d)
    sc = np.asarray(slot_chunk, np.int32)
    outs, states = {}, {}
    per_slot_pallas = A.PallasBackend()
    per_slot_pallas.batched_pool = False  # force the reference order
    for name, be in (("jnp", A.get_backend("jnp")),
                     ("pallas_scan", per_slot_pallas),
                     ("pallas_batched", A.get_backend("pallas")),
                     ("paged", A.get_backend("paged"))):
        stt = A.pool_scan(be, qg, pool_l, tbl, sc, jnp.int32(limit), scale,
                          A.attn_init(b, c, kvh, g, d), slots=slots)
        states[name] = tuple(np.asarray(x) for x in stt)
        outs[name] = np.asarray(A.attn_finish(stt, jnp.float32))
    return outs, states


def _assert_parity(outs, states, tol):
    ref = outs["pallas_scan"]
    for name in ("pallas_batched", "paged", "jnp"):
        np.testing.assert_allclose(outs[name], ref, atol=tol, rtol=tol)
    # state-level reconciliation (m exact-ish, l/acc to fp32 rounding —
    # the paged kernel sums per PAGE, the gathered kernel per block_k, so
    # both get the same rounding-order headroom vs the per-slot scan)
    for name in ("pallas_batched", "paged"):
        for i in range(3):
            np.testing.assert_allclose(states[name][i],
                                       states["pallas_scan"][i],
                                       atol=tol, rtol=max(tol, 1e-5))


@pytest.mark.parametrize("kv_dtype,tol", [
    ("float32", 1e-6), ("bfloat16", 1e-6), ("int8", 2e-3), ("fp8", 2e-3),
])
def test_batched_pool_matches_per_slot_scan(kv_dtype, tol):
    """Full-pool traversal: batched kernel state == per-slot scan state.
    bfloat16/float32 pages sit at the 1e-6 fp32-combine floor; int8 pages
    get the quantized headroom (both paths read identical pages, so the
    observed error is still rounding-level)."""
    import jax
    import jax.numpy as jnp
    jax  # imported for device init
    b, c, kvh, g, d = 1, 32, 2, 2, 24
    _, tbl, pool_l = _build_pool(4, kv_dtype, b, c, kvh, d, page_tokens=8)
    qg = jax.random.normal(jax.random.key(3), (b, c, kvh, g, d), jnp.float32)
    outs, states = _scan_states(pool_l, tbl, [0, 1, 2, 3, -1], limit=3, qg=qg)
    _assert_parity(outs, states, tol)


@pytest.mark.parametrize("kv_dtype,tol", [("bfloat16", 1e-6), ("int8", 2e-3)])
def test_batched_pool_creditor_subset(kv_dtype, tol):
    """The creditor-side ``slots=`` subset path (qship) through the batched
    kernel: only the listed slots are visited, in listed order."""
    import jax
    import jax.numpy as jnp
    b, c, kvh, g, d = 1, 16, 1, 2, 16
    _, tbl, pool_l = _build_pool(5, kv_dtype, b, c, kvh, d, page_tokens=0)
    qg = jax.random.normal(jax.random.key(5), (b, c, kvh, g, d), jnp.float32)
    outs, states = _scan_states(pool_l, tbl, [4, 2, 0, 1, 3, -1], limit=4,
                                qg=qg, slots=np.asarray([1, 3, 4]))
    _assert_parity(outs, states, tol)


@pytest.mark.parametrize("backend", ["pallas", "paged"])
def test_batched_pool_all_invalid_is_identity(backend):
    """limit=0 invalidates every slot: the batched/paged kernels must
    contribute the EXACT identity state (m=-inf, l=0, acc=0), like the
    gated scan — the paged kernel additionally issues ZERO page copies."""
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    b, c, kvh, g, d = 1, 16, 1, 2, 16
    _, tbl, pool_l = _build_pool(3, "float32", b, c, kvh, d, page_tokens=0)
    qg = jax.random.normal(jax.random.key(1), (b, c, kvh, g, d), jnp.float32)
    st0 = A.attn_init(b, c, kvh, g, d)
    stt = A.pool_scan(A.get_backend(backend), qg, pool_l, tbl,
                      np.asarray([0, 1, 2, -1], np.int32), jnp.int32(0),
                      0.25, st0)
    for a, b_ in zip(st0, stt):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_launch_count_is_o1_in_pool_depth():
    """The acceptance hook: kernel launches per pool scan must be 1 under
    the batched path regardless of pool depth, vs one per slot in the
    per-slot order (ops.count_launches multiplies each kernel call site by
    the trip counts of the scans around it, so scan iterations are counted,
    not trace sites)."""
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    from repro.kernels import ops
    b, c, kvh, g, d = 1, 16, 1, 2, 16

    def run(be, nslots):
        _, tbl, pool_l = _build_pool(nslots, "float32", b, c, kvh, d, 0)
        qg = jax.random.normal(jax.random.key(0), (b, c, kvh, g, d))
        sc = np.concatenate([np.arange(nslots), [-1]]).astype(np.int32)
        fn = jax.jit(lambda q: A.attn_finish(A.pool_scan(
            be, q, pool_l, tbl, sc, jnp.int32(nslots), 0.25,
            A.attn_init(b, c, kvh, g, d)), jnp.float32))
        return ops.count_launches(fn, qg)

    batched = A.get_backend("pallas")
    paged = A.get_backend("paged")
    per_slot = A.PallasBackend()
    per_slot.batched_pool = False
    assert run(batched, 3)["count"] == 1
    assert run(batched, 6)["count"] == 1  # O(1): depth-independent
    assert run(per_slot, 3)["count"] == 3
    assert run(per_slot, 6)["count"] == 6  # O(slots): the launch tax
    assert run(A.get_backend("jnp"), 6)["count"] == 0
    # paged: O(1) too, and every launch carries the paged tag — the
    # gathered pool kernel never runs under this backend
    for nslots in (3, 6):
        lc = run(paged, nslots)
        assert lc["count"] == 1, lc
        assert lc["pool_attention_paged"] == 1, lc
        assert "pool_attention" not in lc, lc


def test_pool_backend_plan_resolution():
    """RunConfig.pool_backend: "auto" follows attn_backend; an explicit
    value mixes per source and reaches the plan unchanged."""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.core.plan import build_plan
    cfg = ModelConfig(arch="t", family="dense", num_layers=4, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      head_dim=8, dtype="float32")
    run = RunConfig(num_chunks=8, num_stages=4, attn_backend="pallas")
    assert build_plan(cfg, 4, 128, run).pool_backend == "pallas"
    run = RunConfig(num_chunks=8, num_stages=4, attn_backend="pallas",
                    pool_backend="jnp")
    assert build_plan(cfg, 4, 128, run).pool_backend == "jnp"
    gp = build_plan(cfg, 4, 128, run, mode="gpipe")
    assert gp.pool_backend == "jnp"
    run = RunConfig(num_chunks=8, num_stages=4, attn_backend="pallas",
                    pool_backend="paged")
    assert build_plan(cfg, 4, 128, run).pool_backend == "paged"


# --------------------------------------------------- ragged-occupancy sweep

def _check_occupancy(nslots, chunk_ids, limit, subset_mask, kv_dtype):
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    b, c, kvh, g, d = 1, 16, 1, 2, 16
    _, tbl, pool_l = _build_pool(nslots, kv_dtype, b, c, kvh, d,
                                 page_tokens=8)
    qg = jax.random.normal(jax.random.key(2), (b, c, kvh, g, d), jnp.float32)
    if nslots == 0:  # empty pool: pool_scan must be a no-op on every path
        st0 = A.attn_init(b, c, kvh, g, d)
        for name in ("jnp", "pallas", "paged"):
            stt = A.pool_scan(A.get_backend(name), qg, pool_l, tbl,
                              np.asarray([-1], np.int32), jnp.int32(limit),
                              0.25, st0)
            assert stt is st0
        return
    slots = np.nonzero(subset_mask[:nslots])[0].astype(np.int32)
    sc = list(chunk_ids[:nslots]) + [-1]
    tol = 2e-3 if kv_dtype == "int8" else 1e-6
    outs, states = _scan_states(pool_l, tbl, sc, limit, qg)
    _assert_parity(outs, states, tol)
    if len(slots):
        outs, states = _scan_states(pool_l, tbl, sc, limit, qg, slots=slots)
        _assert_parity(outs, states, tol)


if given is not None:
    @settings(max_examples=12, deadline=None)
    @given(
        nslots=st.integers(min_value=0, max_value=5),
        chunk_ids=st.lists(st.integers(min_value=-1, max_value=7),
                           min_size=5, max_size=5),
        limit=st.integers(min_value=0, max_value=8),
        subset_mask=st.lists(st.booleans(), min_size=5, max_size=5),
        kv_dtype=st.sampled_from(["bfloat16", "int8"]),
    )
    def test_ragged_occupancy_property(nslots, chunk_ids, limit, subset_mask,
                                       kv_dtype):
        """Random slot subsets x mixed chunk ids vs. limit x empty/single-
        slot edges: batched-kernel state == per-slot-scan state on both
        page codecs and both backends."""
        _check_occupancy(nslots, np.asarray(chunk_ids), limit,
                         np.asarray(subset_mask), kv_dtype)
else:
    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_ragged_occupancy_property():
        pass


# ------------------------------------------- deterministic ragged coverage

RAGGED_CASES = [
    # (nslots, chunk_ids, limit, subset_mask, kv_dtype) — hand-picked rows
    # of the hypothesis space above, run unconditionally (no hypothesis
    # needed): empty pool, single slot, limit-0, mixed ids, full house
    (0, [-1, -1, -1, -1, -1], 3, [False] * 5, "bfloat16"),
    (1, [0, -1, -1, -1, -1], 1, [True] * 5, "int8"),
    (3, [0, 1, 2, -1, -1], 0, [True] * 5, "bfloat16"),
    (5, [0, 1, -1, 3, 7], 4, [True, False, True, True, False], "bfloat16"),
    (4, [2, 0, 5, 1, -1], 2, [False, False, True, True, False], "int8"),
    (5, [6, 7, 5, 4, 3], 8, [True] * 5, "int8"),
]


@pytest.mark.parametrize("nslots,chunk_ids,limit,subset_mask,kv_dtype",
                         RAGGED_CASES)
def test_ragged_occupancy_cases(nslots, chunk_ids, limit, subset_mask,
                                kv_dtype):
    """Deterministic ragged-occupancy sweep (all four traversal orders,
    incl. the paged kernel): random slot subsets, mixed chunk ids vs.
    limit, empty pool, single slot, all-invalid."""
    _check_occupancy(nslots, np.asarray(chunk_ids), limit,
                     np.asarray(subset_mask), kv_dtype)


@pytest.mark.parametrize("kv_len", [20, 24])
def test_paged_partial_last_page(kv_len):
    """``kv_len`` < C: the paged kernel masks the partial page's tail (20)
    or ends on a page boundary (24), AND statically drops trailing all-dead
    pages (np_eff) — parity vs the gathered kernel on token-truncated
    stacks."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kvstore import pages as PG
    b, c, kvh, g, d = 1, 32, 2, 2, 32
    nslots = 3  # pt=8: pages 0-1 full, page 2 partial or full, page 3 dead
    _, tbl, pool_l = _build_pool(nslots, "float32", b, c, kvh, d,
                                 page_tokens=8)
    k_l, v_l, ks_l, vs_l = pool_l
    rows = PG.handle_rows(tbl)
    assert rows.shape == (nslots, 4)
    handles = jnp.asarray(rows, jnp.int32).reshape(-1)
    valid = jnp.ones((nslots,), jnp.int32)
    q = jax.random.normal(jax.random.key(9), (b, c, kvh * g, d), jnp.float32)
    m, l, acc = ops.pool_attention_paged(q, k_l, v_l, handles, valid,
                                         ppc=rows.shape[1], kv_len=kv_len)
    kq, vq, _, _ = PG.gather_chunks(k_l, v_l, ks_l, vs_l, jnp.asarray(rows))
    mr, lr, accr = ops.pool_attention(q, kq[:, :, :kv_len], vq[:, :, :kv_len],
                                      valid)
    for got, ref in ((m, mr), (l, lr), (acc, accr)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6, rtol=1e-5)


# --------------------------------------- the kv-head tile of the paged kernel
# Each case: group size g (kv heads kvh), the query heads and rows per head
# of a tile and the key slice of a page (None = ``chunk_attn.paged_tiles``'
# choice, which at these sizes is the whole group, the whole chunk and the
# whole page), page tokens, kv dtype, occupancy, and kv_len (None = whole
# pages).
RETILED_CASES = {
    "g1-whole-chunk": (1, 2, None, None, None, 8, "bfloat16", "full", None),
    "g4-rows-x4-keys-x2": (4, 1, None, 8, 4, 8, "bfloat16", "ragged", None),
    "g12-rows-x2": (12, 1, None, 16, None, 8, "float32", "full", None),
    "g12-heads-x3-rows-x2": (12, 2, 4, 16, None, 16, "bfloat16", "ragged",
                             None),
    "g4-int8-keys-x2": (4, 2, None, None, 4, 8, "int8", "ragged", None),
    "g4-fp8-heads-x2-ppc2": (4, 1, 2, 16, 8, 16, "fp8", "full", None),
    "g4-odd-steps": (4, 2, None, None, None, 32, "bfloat16", "odd", None),
    "g4-all-invalid": (4, 1, 2, 8, 4, 8, "bfloat16", "none", None),
    "g4-creditor-subset-int8": (4, 1, 2, 8, 4, 8, "int8", "subset", None),
    "g4-partial-page-ppc2": (4, 1, 2, 8, 4, 16, "float32", "full", 22),
    "g12-partial-page-ppc4": (12, 1, None, None, 4, 8, "bfloat16", "full",
                              20),
}


@pytest.mark.parametrize("case", list(RETILED_CASES))
def test_paged_retiled_parity(case, monkeypatch):
    """The paged kernel's head-block grid — all or some of the g query heads
    of one kv head in one tile, in one or several row blocks, each landed
    page consumed whole or in key slices — against the gathered slot-grid
    kernel (``ops.pool_attention``) and the per-slot scan: group sizes 1,
    4 and 12, int8 and fp8 payloads, ragged and empty occupancy, an odd
    number of pages per program (the double buffer's parity runs on across
    programs), the creditor ``slots=`` subset, and a partial last page of
    ppc > 1 pages."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    from repro.kernels import ops
    from repro.kvstore import pages as PG
    g, kvh, heads, bq, bk, pt, kv_dtype, occupancy, kv_len = \
        RETILED_CASES[case]
    b, c, d, nslots = 1, 32, 16, 4
    _, tbl, pool_l = _build_pool(nslots, kv_dtype, b, c, kvh, d,
                                 page_tokens=pt)
    tiled = functools.partial(ops.pool_attention_paged, heads=heads,
                              block_q=bq, block_k=bk)
    if kv_len is not None:  # direct call vs token-truncated stacks
        k_l, v_l, ks_l, vs_l = pool_l
        rows = PG.handle_rows(tbl)
        handles = jnp.asarray(rows, jnp.int32).reshape(-1)
        valid = jnp.ones((nslots,), jnp.int32)
        q = jax.random.normal(jax.random.key(11), (b, c, kvh * g, d),
                              jnp.float32)
        got = tiled(q, k_l, v_l, handles, valid, ppc=rows.shape[1],
                    kv_len=kv_len)
        kq, vq, _, _ = PG.gather_chunks(k_l, v_l, ks_l, vs_l,
                                        jnp.asarray(rows))
        ref = ops.pool_attention(q, kq[:, :, :kv_len], vq[:, :, :kv_len],
                                 valid)
        for x, y in zip(got, ref):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6, rtol=1e-5)
        return
    monkeypatch.setattr(ops, "pool_attention_paged", tiled)
    qg = jax.random.normal(jax.random.key(6), (b, c, kvh, g, d), jnp.float32)
    tol = 2e-3 if kv_dtype in ("int8", "fp8") else 1e-6
    sc, limit, slots = [0, 1, 2, 3, -1], 4, None
    if occupancy == "ragged":
        sc, limit = [2, 0, 3, 1, -1], 2
    elif occupancy == "odd":  # 3 one-page steps per program, 2 programs
        limit = 3
    elif occupancy == "none":
        limit = 0
    elif occupancy == "subset":
        sc, slots = [3, 1, 0, 2, -1], np.asarray([0, 2, 3])
    outs, states = _scan_states(pool_l, tbl, sc, limit, qg, slots=slots)
    if occupancy == "none":  # the exact identity state, as the scan's
        st0 = A.attn_init(b, c, kvh, g, d)
        for x, y in zip(st0, states["paged"]):
            np.testing.assert_array_equal(np.asarray(x), y)
        return
    # the per-slot scan, the gathered kernel and the paged kernel sum in
    # different orders: bound each state and output by tol times its
    # largest magnitude (the unnormalised sums reach ~10 here)
    for name in ("pallas_batched", "paged", "jnp"):
        pairs = [(outs[name], outs["pallas_scan"])]
        if name != "jnp":
            pairs += list(zip(states[name], states["pallas_scan"]))
        for got, want in pairs:
            np.testing.assert_allclose(
                got, want, rtol=0,
                atol=tol * max(1.0, float(np.max(np.abs(want)))))


def test_paged_pool_scan_has_no_gather_intermediate():
    """Acceptance: the lowered paged pool scan contains NO dense
    [S, B, C, KVH, *] slot-stack intermediate and no [S*ppc, B, pt, KVH, *]
    page-take — the HBM copies the paged kernel exists to delete — while
    the gathered batched trace DOES carry the slot stack."""
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    b, c, kvh, g, d = 1, 32, 2, 2, 32
    nslots, pt, ppc = 4, 8, 4
    _, tbl, pool_l = _build_pool(nslots, "float32", b, c, kvh, d,
                                 page_tokens=pt)
    qg = jax.random.normal(jax.random.key(4), (b, c, kvh, g, d), jnp.float32)
    sc = np.asarray([0, 1, 2, 3, -1], np.int32)

    def all_shapes(backend):
        fn = lambda q: A.attn_finish(A.pool_scan(
            A.get_backend(backend), q, pool_l, tbl, sc, jnp.int32(4), 0.25,
            A.attn_init(b, c, kvh, g, d)), jnp.float32)
        jaxpr = jax.make_jaxpr(fn)(qg)
        shapes = set()

        def walk(jx):
            for eqn in jx.eqns:
                for var in list(eqn.invars) + list(eqn.outvars):
                    aval = getattr(var, "aval", None)
                    shp = getattr(aval, "shape", None)
                    if shp is not None:
                        shapes.add(tuple(shp))
                for val in eqn.params.values():
                    sub(val)

        def sub(val):
            if hasattr(val, "jaxpr"):       # ClosedJaxpr
                sub(val.jaxpr)
            elif hasattr(val, "eqns"):      # Jaxpr
                walk(val)
            elif isinstance(val, (list, tuple)):
                for item in val:
                    sub(item)

        walk(jaxpr.jaxpr)
        return shapes

    def gathers(shapes):
        slot_stack = [s for s in shapes
                      if len(s) == 5 and s[:4] == (nslots, b, c, kvh)]
        page_take = [s for s in shapes
                     if len(s) == 5 and s[:4] == (nslots * ppc, b, pt, kvh)]
        return slot_stack + page_take

    assert gathers(all_shapes("pallas")), "oracle lost its gather?"
    leaked = gathers(all_shapes("paged"))
    assert not leaked, f"paged trace materializes a gather: {leaked}"
