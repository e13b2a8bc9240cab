"""KV page store benchmark: max feasible sequence length vs kv_dtype.

Two levers multiply (DESIGN.md §6): MBKR slot orchestration shrinks the pool
from M chunk-slots to ``plan(M, N).num_slots``, and the page codec shrinks
every stored byte. At an EQUAL per-stage byte budget, the table reports the
max feasible sequence length per codec (per-page scale overhead included),
the combined gain over the Terapipe/bf16 baseline, and the cold-tier
headroom when --offload staging is allowed.

Acceptance floor: kv_dtype=int8 >= 1.5x the bf16 max seq len at the M=N=16
dryrun config. A device-validated leg round-trips one quantized pool chunk
through scatter/gather + both attention backends to pin the codec error the
capacity numbers rely on.

Writes artifacts/bench/kvstore.json. Usage:
  PYTHONPATH=src python -m benchmarks.kvstore [--quick]
"""
from __future__ import annotations

import argparse
import json
import math
import os

import numpy as np

from benchmarks.common import OUT_DIR, table
from repro.configs.base import get_config
from repro.core import costmodel as cm
from repro.core import mbkr
from repro.kvstore import pages as PG
from repro.kvstore import quant as Q
from repro.kvstore import tiers as TR

M = N = 16                       # the dryrun config of the acceptance floor
DTYPES = ("bfloat16", "int8", "fp8")
PAGE_TOKENS = 64
H2D_BW = 16e9


def capacity_table(arch: str = "llama3-70b", hw=cm.WSC_PAPER):
    """Max feasible seq len per codec at the per-stage KV byte budget left
    after weights (the same capacity math the lease manager provisions)."""
    cfg = get_config(arch)
    sm = cm.StageModel.build(cfg, N, 1)
    kv_tok = cm.kv_chunk_bytes(sm, 1)          # one stage's bytes/token, bf16
    weights = cfg.param_count() * 2 / N
    budget = max(hw.hbm_cap - weights, hw.hbm_cap * 0.2)
    base_tp = TR.max_seq_len_for_budget(
        budget, kv_token_bytes=kv_tok, num_chunks=M, num_stages=N,
        codec=Q.get_codec("bfloat16"), page_tokens=PAGE_TOKENS,
        head_dim=cfg.resolved_head_dim, mbkr=False)   # Terapipe/bf16 floor
    rows = []
    for dt in DTYPES:
        codec = Q.get_codec(dt)
        s = TR.max_seq_len_for_budget(
            budget, kv_token_bytes=kv_tok, num_chunks=M, num_stages=N,
            codec=codec, page_tokens=PAGE_TOKENS,
            head_dim=cfg.resolved_head_dim)
        bf16 = rows[0]["max_seq_len"] if rows else s
        rows.append({
            "arch": arch, "kv_dtype": dt,
            "budget_GB": round(budget / 1e9, 1),
            "max_seq_len": s,
            "vs_bf16": round(s / bf16, 3) if bf16 else "",
            "vs_terapipe_bf16": round(s / base_tp, 3) if base_tp else "",
        })
    return rows


def tier_headroom(arch: str = "llama3-70b", hw=cm.WSC_PAPER):
    """Cold-tier study: fraction of own pages that can live host-side with
    the analytic prefetch still landing every page before its pool-scan
    tick, per codec (quantized pages stream back faster)."""
    cfg = get_config(arch)
    sm = cm.StageModel.build(cfg, N, 1)
    mplan = mbkr.plan(M, N)
    c = 131072 // M
    dur, _, _, _, _ = cm.chunk_cost_arrays(sm, [c] * M, hw, mbkr_plan=mplan)
    host_slots = (np.unique(np.concatenate(
        [mplan.host_slot_a[mplan.p2:], mplan.host_slot_b[mplan.p2:]]))
        if mplan.p2 < M else None)
    rows = []
    for dt in DTYPES:
        codec = Q.get_codec(dt)
        geom = PG.page_geometry(c, mplan.num_slots, PAGE_TOKENS)
        tbl = PG.build_slot_pages(geom)
        dims = dict(lps=sm.attn_layers, b=1, kvh=cfg.num_kv_heads,
                    hd=cfg.resolved_head_dim)
        cb = TR.chunk_page_bytes(geom, codec, **dims)
        # shrink the hot budget until the plan goes infeasible
        best = 0
        for cold_chunks in range(0, mplan.p2):
            hot = cb * (mplan.p2 - cold_chunks)
            plan = TR.plan_tiers(geom, codec, tbl, mplan.own_slot, mplan.p2,
                                 M, TR.TierSpec(hot_bytes=hot, cold_bw=H2D_BW),
                                 **dims, tick_s=dur, host_slots=host_slots)
            if plan.feasible:
                # count the cold chunks actually placed (host-shared slots
                # are ineligible, so this can be < the requested overflow)
                best = max(best, len({op.chunk for op in plan.prefetch}))
        rows.append({
            "arch": arch, "kv_dtype": dt, "seq_len": c * M,
            "chunk_MB": round(cb / 1e6, 1),
            "cold_chunks_feasible": best,
            "cold_frac": round(best / max(mplan.p2, 1), 3),
        })
    return rows


def device_validation():
    """Round-trip one chunk through the paged pool + both backends on the
    actual device (interpret-mode kernels off-TPU): the codec error the
    capacity table's dtypes rely on, measured not assumed."""
    import jax
    import jax.numpy as jnp
    from repro.core import attention as A
    b, c, kvh, g, d = 1, 64, 4, 2, 64
    geom = PG.page_geometry(c, 3, PAGE_TOKENS)
    tbl = PG.build_slot_pages(geom)
    ks = jax.random.split(jax.random.key(0), 3)
    qg = jax.random.normal(ks[0], (b, c, kvh, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, b, c, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, b, c, kvh, d), jnp.float32)
    scale = 1.0 / math.sqrt(d)
    out = {}
    for dt in DTYPES:
        codec = Q.get_codec(dt)
        pool = PG.alloc_pool(geom, codec, 1, b, kvh, d)
        pool = PG.scatter_chunk(pool, jnp.asarray(tbl[0]), k, v, codec)
        sl = lambda a: None if a is None else a[:, 0]
        pool_l = (sl(pool.k), sl(pool.v), sl(pool.k_scale), sl(pool.v_scale))
        res = {}
        for name in ("jnp", "pallas"):
            be = A.get_backend(name)
            st = A.pool_scan(be, qg, pool_l, tbl,
                             np.asarray([0, -1, -1, -1], np.int32),
                             jnp.int32(1), scale,
                             A.attn_init(b, c, kvh, g, d))
            res[name] = np.asarray(A.attn_finish(st, jnp.float32))
        ref_st = A.get_backend("jnp").chunk_block(
            qg, k[0], v[0], jnp.bool_(True), scale,
            A.attn_init(b, c, kvh, g, d))
        ref = np.asarray(A.attn_finish(ref_st, jnp.float32))
        rms = float(np.sqrt(np.mean(ref ** 2)))
        out[dt] = {
            "attn_err_p99_over_rms": round(
                float(np.percentile(np.abs(res["jnp"] - ref), 99)) / rms, 5),
            "backend_parity_abs": float(np.abs(res["jnp"] - res["pallas"]).max()),
        }
        assert out[dt]["backend_parity_abs"] < 1e-4, (dt, out[dt])
    return out


def pipeline_leg(quick: bool = False) -> dict:
    """Real-pipeline leg: jit the chunked pipeline with a TP-SHARDED paged
    pool (GSPMD kv-head sharding) and measure the pool's actual device bytes + prefill wall time per
    kv_dtype. Appends to artifacts/bench/kvstore.json."""
    import time

    from repro import compat
    compat.ensure_host_devices(8)
    import jax
    from repro.configs.base import RunConfig, get_smoke_config, replace
    from repro.core import pipeline as pp
    from repro.launch.mesh import make_test_topology
    from repro.models.api import build_model

    cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
    stages, tp = 4, 2
    topo = make_test_topology(stages, tp)
    seq, m = 256, 8
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, seq), 0,
                                cfg.vocab_size)
    rows = []
    for dt in ("auto",) + (() if quick else ("int8",)):
        run_cfg = RunConfig(num_chunks=m, num_stages=stages, kv_dtype=dt,
                            kv_page_tokens=8)
        plan = pp.build_plan(cfg, stages, seq, run_cfg)
        staged = pp.stage_params(cfg, params, plan)
        pool = pp.alloc_kv_pool(cfg, plan, 2)
        nbytes = sum(int(a.nbytes) for a in
                     (pool.k, pool.v, pool.k_scale, pool.v_scale)
                     if a is not None)
        with compat.set_mesh(topo.mesh):
            fn = jax.jit(lambda st, tk: pp.prefill_pipeline(
                cfg, st, tk, plan, topo))
            out = fn(staged, tokens)
            out.block_until_ready()
            t0 = time.perf_counter()
            fn(staged, tokens).block_until_ready()
            wall = time.perf_counter() - t0
        rows.append({"kv_dtype": plan.kv_dtype, "tp": tp,
                     "pool_bytes": nbytes, "wall_s": round(wall, 3)})
    print(table(rows, ["kv_dtype", "tp", "pool_bytes", "wall_s"]))
    path = os.path.join(OUT_DIR, "kvstore.json")
    if os.path.exists(path):
        blob = json.load(open(path))
        blob["pipeline_leg"] = rows
        with open(path, "w") as f:
            json.dump(blob, f, indent=1)
    return {"rows": rows}


def run(quick: bool = False) -> dict:
    archs = ("llama3-70b",) if quick else ("llama3-70b", "qwen3-235b")
    cap_rows, tier_rows = [], []
    for a in archs:
        cap_rows += capacity_table(a)
        tier_rows += tier_headroom(a)
    print(table(cap_rows, ["arch", "kv_dtype", "budget_GB", "max_seq_len",
                           "vs_bf16", "vs_terapipe_bf16"]))
    print(table(tier_rows, ["arch", "kv_dtype", "seq_len", "chunk_MB",
                            "cold_chunks_feasible", "cold_frac"]))
    val = device_validation()
    int8_gain = min(r["vs_bf16"] for r in cap_rows
                    if r["kv_dtype"] == "int8")
    print(f"int8 max-seq gain over bf16 at equal budget: {int8_gain:.2f}x "
          f"(acceptance floor 1.5x); codec attention error p99/rms: "
          + ", ".join(f"{k}={v['attn_err_p99_over_rms']}"
                      for k, v in val.items()))
    assert int8_gain >= 1.5, int8_gain
    result = {"config": {"M": M, "N": N, "page_tokens": PAGE_TOKENS},
              "quick": quick,
              "capacity": cap_rows, "tiers": tier_rows, "validation": val}
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "kvstore.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"-> {path}")
    return result


def main(quick: bool = False):
    return run(quick=quick)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    run(quick=a.quick)
