"""Quickstart: MOCAP chunked-pipeline prefill on fake local devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py [--arch qwen3-8b]

Builds a reduced model, partitions a prompt into chunks with LBCP, runs the
MBKR-orchestrated pipeline over 4 stages x 2-way TP, and checks the result
against the plain full-sequence forward.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import compat

compat.ensure_host_devices()

import jax
import jax.numpy as jnp
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import lbcp, mbkr, pipeline as pp
from repro.core import costmodel as cm
from repro.launch.mesh import make_test_topology
from repro.models.api import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--chunks", type=int, default=8)
    ap.add_argument("--attn-backend", default="jnp",
                    choices=("jnp", "pallas"))
    args = ap.parse_args()

    cfg = replace(get_smoke_config(args.arch), dtype="float32")
    model = build_model(cfg)
    tp = 2
    topo = make_test_topology(num_stages=8 // tp, tp=tp)
    print(f"arch={args.arch} mesh={dict(topo.mesh.shape)} "
          f"stages={topo.num_stages} tp={topo.tp_size}")

    # 1. the MBKR slot plan: how much pool the cross-half pairing saves
    plan_m = mbkr.plan(args.chunks, topo.num_stages)
    print(f"MBKR: {plan_m.describe()}  -> pool {plan_m.num_slots} slots "
          f"vs Terapipe {args.chunks} "
          f"(max-seq headroom ~{args.chunks/plan_m.peak:.2f}x)")

    # 2. LBCP: latency-balanced chunk sizes (analytic, production scale)
    from repro.configs.base import get_config
    pplan = lbcp.plan_partition(get_config("llama3-70b"), 65536, 8, 16,
                                cm.WSC_PAPER, sa_iters=40)
    print(f"LBCP @70B/64k: chunks={pplan.chunks} (later chunks shrink to "
          f"offset attention growth)")

    # 3. run the pipeline for real and verify
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, args.seq), 0,
                              cfg.vocab_size)
    run = RunConfig(num_chunks=args.chunks, num_stages=topo.num_stages,
                    attn_backend=args.attn_backend)
    plan = pp.build_plan(cfg, topo.num_stages, args.seq, run)
    staged = pp.stage_params(cfg, params, plan)
    with compat.set_mesh(topo.mesh):
        logits = jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, topo))(staged, toks)
    ref = model.forward(params, toks)[:, -1]
    err = float(jnp.max(jnp.abs(logits - ref)))
    print(f"pipeline vs full-forward: max abs err {err:.2e}  "
          f"next tokens {jnp.argmax(logits, -1).tolist()}")
    assert err < 1e-2
    print("OK")


if __name__ == "__main__":
    main()
