"""Attention-backend comparison: jnp reference vs pallas (interpret off-TPU).

Times the ``core.attention`` backends on the composite the pipeline hot loop
actually runs per (layer, chunk): the stored-prefix pool scan + the causal
self block + finish — with the pool scan in all three traversal orders:

- ``jnp``            per-slot jnp reference scan,
- ``pallas_scan``    per-slot kernel launches (one ``chunk_attention`` +
                     traced combine per occupied slot — the pre-batching
                     pallas path),
- ``pool_batched``   the fused slot-grid kernel (``ops.pool_attention``):
                     ONE launch per pool scan, O(1) in pool depth,
- ``pool_paged``     the ragged paged kernel (``ops.pool_attention_paged``):
                     ONE launch AND zero gather — pages read in place.

``launches_*`` count RUNTIME kernel launches of the pool part
(``ops.count_launches``): O(slots) -> O(1) is the point; the wall-time win
from amortized launch overhead needs real TPU (off-TPU the pallas numbers
are INTERPRET-mode — a correctness harness, expected slower than jnp on
CPU). Alongside wall time we report the analytic TPU-v5e roofline time for
the same flops/bytes, which is backend-independent, and the DETERMINISTIC
HBM cost of the gather copy the paged kernel deletes:
``hbm_gather_bytes`` (what the gathered slot-grid path writes+reads per
pool scan) vs ``hbm_gather_bytes_paged`` (pinned 0), plus the roofline
speedup ``paged_speedup`` that traffic delta buys — the compare.py gate
pins all three exactly.

Writes artifacts/bench/attn_backend.json. Usage:
  PYTHONPATH=src python -m benchmarks.attn_backend [--iters 3] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import OUT_DIR, table
from repro.core import attention as A
from repro.kernels import ops
from repro.roofline.analysis import HW_V5E

# (b, c, kvh, g, d, n_pool_chunks): pipeline-shaped cases; --quick trims
CASES = [
    (1, 128, 2, 4, 64, 3),
    (1, 256, 4, 4, 128, 3),
    (2, 128, 8, 4, 128, 6),
]


def _wire_bytes(b, c, kvh, g, d, npool):
    """§3.4 per-(layer, tick) remote-traffic pricing of this case's
    geometry, fp32 wire (the bench tensors' dtype) — the same formulas the
    transport CollectiveLedger is pinned to at runtime within 1%
    (core.transport.analytic_wire_bytes / tests/test_transport.py):

      fetch  = n_remote chunk-layer payloads (2 * C * kvh * hd each),
      qship  = one Q ship + one (m, l) fp32 + acc return, n_remote-free.

    Deterministic byte counts -> exact directional gates in compare.py
    (remote traffic must never regress upward unnoticed)."""
    h = kvh * g
    fetch = npool * (2 * b * c * kvh * d) * 4.0
    qship = (b * c * h * d) * 4.0 + 2 * (b * h * c) * 4.0 \
        + (b * c * h * d) * 4.0
    return fetch, qship


def _pool_fns(kpool, vpool, scale):
    """The four pool-scan traversal orders under test, as (name, fn) with
    fn: (qg, state) -> state over the SAME stacked pool KV (the paged
    backend views the stack as identity-handle pages — zero copy)."""
    valid = jnp.ones(kpool.shape[0], bool)
    be_jnp = A.get_backend("jnp")
    be_pal = A.get_backend("pallas")
    be_paged = A.get_backend("paged")
    per_slot = A.PallasBackend()
    per_slot.batched_pool = False  # pool_block honors the flag
    return [
        ("jnp", lambda q, st: be_jnp.pool_block(
            q, kpool, vpool, None, None, valid, scale, st)),
        ("pallas_scan", lambda q, st: per_slot.pool_block(
            q, kpool, vpool, None, None, valid, scale, st)),
        ("pool_batched", lambda q, st: be_pal.pool_block(
            q, kpool, vpool, None, None, valid, scale, st)),
        ("pool_paged", lambda q, st: be_paged.pool_block(
            q, kpool, vpool, None, None, valid, scale, st)),
    ]


def _composite(pool_fn, self_be, qg, scale):
    b, c, kvh, g, d = qg.shape
    st = A.attn_init(b, c, kvh, g, d)
    st = pool_fn(qg, st)
    st = self_be.self_block(qg, qg[:, :, :, 0], qg[:, :, :, 0], scale, st)
    return A.attn_finish(st, jnp.float32)


def _time(fn, *args, iters: int) -> float:
    out = fn(*args)              # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def run(iters: int = 3, quick: bool = False) -> dict:
    cases = CASES[:1] if quick else CASES
    rows = []
    for (b, c, kvh, g, d, npool) in cases:
        ks = jax.random.split(jax.random.key(0), 3)
        qg = jax.random.normal(ks[0], (b, c, kvh, g, d), jnp.float32)
        kpool = jax.random.normal(ks[1], (npool, b, c, kvh, d), jnp.float32)
        vpool = jax.random.normal(ks[2], (npool, b, c, kvh, d), jnp.float32)
        scale = 1.0 / float(np.sqrt(d))
        h = kvh * g
        t_kv = (npool + 1) * c
        flops = 4.0 * b * c * t_kv * h * d
        bytes_ = 2.0 * (b * c * h * d * 2 + 2 * b * t_kv * kvh * d)  # bf16
        tpu_s = max(flops / HW_V5E["peak_flops"], bytes_ / HW_V5E["hbm_bw"])

        outs, times, launches = {}, {}, {}
        for name, pool_fn in _pool_fns(kpool, vpool, scale):
            self_be = A.get_backend("jnp" if name == "jnp" else "pallas")
            fn = jax.jit(lambda q, pf=pool_fn, sb=self_be:
                         _composite(pf, sb, q, scale))
            times[name] = _time(fn, qg, iters=iters)
            outs[name] = np.asarray(fn(qg))
            # pool-part launch count (the O(slots) -> O(1) claim), counted
            # from the traced program of a pool-only closure
            pfn = lambda q, pf=pool_fn: pf(q, A.attn_init(b, c, kvh, g, d))[1]
            launches[name] = ops.count_launches(pfn, qg)["count"]
        parity = float(np.max(np.abs(outs["jnp"] - outs["pool_batched"])))
        parity_scan = float(np.max(np.abs(outs["pallas_scan"]
                                          - outs["pool_batched"])))
        parity_paged = float(np.max(np.abs(outs["pool_paged"]
                                           - outs["pool_batched"])))
        wire_fetch, wire_qship = _wire_bytes(b, c, kvh, g, d, npool)
        # HBM cost of the dense-slot-stack gather the paged kernel deletes:
        # the gathered path WRITES the [S, B, C, KVH, D] k/v stack then the
        # kernel reads it back; the paged kernel DMAs pages in place. Pool-
        # scan roofline with vs without that traffic = the deterministic
        # paged >= batched gate (wall clock off-TPU is interpret noise).
        gather_bytes = 2.0 * npool * b * c * kvh * d * 4.0  # k + v, fp32
        pool_flops = 4.0 * b * c * (npool * c) * h * d
        pool_bytes = (b * c * h * d * 4.0        # q read
                      + gather_bytes             # page reads (both paths)
                      + 2 * b * c * h * d * 4.0)  # state out
        roof = lambda extra: max(pool_flops / HW_V5E["peak_flops"],
                                 (pool_bytes + extra) / HW_V5E["hbm_bw"])
        paged_speedup = roof(2.0 * gather_bytes) / roof(0.0)
        rows.append({
            "shape": f"b{b} c{c} kv{kvh} g{g} d{d} pool{npool}",
            "jnp_ms": round(times["jnp"] * 1e3, 2),
            "pallas_scan_ms": round(times["pallas_scan"] * 1e3, 2),
            "pool_batched_ms": round(times["pool_batched"] * 1e3, 2),
            "pool_paged_ms": round(times["pool_paged"] * 1e3, 2),
            "parity_abs": f"{parity:.1e}",
            "launches_scan": launches["pallas_scan"],
            "launches_batched": launches["pool_batched"],
            "launches_paged": launches["pool_paged"],
            "hbm_gather_bytes": int(2 * gather_bytes),
            "hbm_gather_bytes_paged": 0,
            "paged_speedup": round(paged_speedup, 4),
            "wire_bytes_fetch": int(wire_fetch),
            "wire_bytes_qship": int(wire_qship),
            "tpu_roofline_us": round(tpu_s * 1e6, 1),
        })
        assert parity < 1e-4, f"backend divergence: {parity}"
        assert parity_scan < 1e-4, f"scan/batched divergence: {parity_scan}"
        assert parity_paged < 1e-4, f"paged/batched divergence: {parity_paged}"
        assert launches["pallas_scan"] == npool, launches
        assert launches["pool_batched"] == 1, launches  # O(1) in pool depth
        assert launches["pool_paged"] == 1, launches    # O(1) AND zero gather
        assert launches["jnp"] == 0, launches
        assert paged_speedup >= 1.0, paged_speedup

    result = {
        "device": str(jax.devices()[0].platform),
        "quick": quick,
        "note": ("pallas timings are interpret-mode off-TPU (correctness "
                 "harness, not a speed claim); launches_* count runtime "
                 "kernel launches of the pool scan (O(slots) vs O(1)); "
                 "tpu_roofline_us is the analytic v5e bound for the "
                 "composite"),
        "iters": iters,
        "rows": rows,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "attn_backend.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(table(rows, ["shape", "jnp_ms", "pallas_scan_ms", "pool_batched_ms",
                       "pool_paged_ms", "parity_abs", "launches_scan",
                       "launches_batched", "launches_paged",
                       "hbm_gather_bytes", "hbm_gather_bytes_paged",
                       "paged_speedup", "wire_bytes_fetch",
                       "wire_bytes_qship", "tpu_roofline_us"]))
    print(f"-> {path}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    run(iters=a.iters, quick=a.quick)
