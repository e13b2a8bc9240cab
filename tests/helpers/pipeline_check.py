"""Subprocess worker: chunked-pipeline vs full-forward equivalence on N fake
devices. Run with XLA_FLAGS=--xla_force_host_platform_device_count=8.

Usage:
  python tests/helpers/pipeline_check.py <arch> <mode> <remote_attn> \
      [spill_dtype] [deep] [backend] [kv_dtype] [page_tokens]

``backend`` (jnp | pallas | both) picks the attention backend (for the ssm
family it also picks the SSD inner loop); ``both`` additionally asserts
jnp-vs-pallas parity directly. ``kv_dtype`` (auto | int8 | fp8) selects the
KV page codec and ``page_tokens`` the page size (0 = one page per chunk).
Prints "PASS <max_err>" or raises.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.models.api import build_model
from repro.models.topology import Topology


def main(arch: str, mode: str, remote_attn: str, spill_dtype: str = "bfloat16",
         deep: str = "", backend: str = "jnp", kv_dtype: str = "auto",
         page_tokens: str = "0"):
    cfg = replace(get_smoke_config(arch), dtype="float32")
    if cfg.moe is not None:
        # chunked dispatch uses PER-CHUNK capacity; lift it so no tokens drop
        # and the pipeline is exactly comparable to the full-sequence oracle.
        import dataclasses
        cfg = replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    # "deep": 8 stages x tp 1 -> p2 = 6 < M-1, so REMOTE chunk 6 is actually
    # consumed by chunk 7's attention (exercises fetch/qship VALUES and the
    # int8 wire quantization, not just their masking)
    n_stages, tp = (8, 1) if deep else (4, 2)
    mesh = compat.make_mesh((n_stages, tp), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
    topo = Topology(mesh=mesh)
    m_chunks, c = 8, 16
    s = m_chunks * c
    b = 2
    if mode == "gpipe":
        b, m_chunks = 8, 4  # microbatch pipeline splits the BATCH

    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)

    kw = {}
    n_front = 0
    if cfg.family == "encdec" or cfg.frontend.kind != "none":
        n_front = c * 2 + 5  # deliberately NOT chunk-aligned (splice test)
        kw["embeds"] = jax.random.normal(
            jax.random.key(2), (b, n_front, cfg.d_model), jnp.float32) * 0.02
        if cfg.frontend.kind == "vision_stub":
            tokens = tokens[:, : s - n_front]  # embeds splice in front

    # oracle: full forward, last-token logits
    ref = model.forward(params, tokens, **kw)
    ref_last = np.asarray(ref[:, -1, :].astype(jnp.float32))

    def run_pipeline(attn_backend: str) -> np.ndarray:
        run = RunConfig(num_chunks=m_chunks, num_stages=n_stages,
                        mbkr=(mode == "mocap"), remote_attn=remote_attn,
                        kv_spill_dtype=spill_dtype, attn_backend=attn_backend,
                        ssm_backend=attn_backend,  # same knob for ssm archs
                        kv_dtype=kv_dtype, kv_page_tokens=int(page_tokens))
        plan = pp.build_plan(cfg, n_stages, s, run, mode=mode)
        staged = pp.stage_params(cfg, params, plan)
        specs = pp.stage_param_specs(cfg, plan, topo)

        def to_sharded(x, spec):
            return jax.device_put(x, NamedSharding(mesh, spec))

        st = {k: jax.tree.map(to_sharded, staged[k], specs[k],
                              is_leaf=lambda x: hasattr(x, "shape"))
              if k in specs else staged[k] for k in staged}
        with compat.set_mesh(mesh):
            fn = jax.jit(lambda st, tk, **k: pp.prefill_pipeline(
                cfg, st, tk, plan, topo, **k))
            out = fn(st, tokens, **kw)
        return np.asarray(out.astype(jnp.float32))

    backends = ("jnp", "pallas") if backend == "both" else (backend,)
    outs = {bk: run_pipeline(bk) for bk in backends}
    for bk, out in outs.items():
        rel = np.abs(out - ref_last) / (np.abs(ref_last) + 1e-3)
        if spill_dtype == "int8" or kv_dtype in ("int8", "fp8"):
            # int8/fp8 KV quantization is REAL lossy compression, so bound
            # the tail, not the single worst (near-zero-logit) element.
            # Spill-only int8 (2 of 8 chunks quantized) sits at p99 ~0.02;
            # kv_dtype=int8 quantizes EVERY stored chunk on this tiny
            # random-weight smoke model and lands at p99 ~0.065 (fp8-e4m3:
            # 3 mantissa bits, ~0.14). The per-ATTENTION-OUTPUT error is
            # bounded at the old 0.05 tolerance in test_kvstore.py.
            p99_tol, max_tol = {
                "int8": (0.12, 0.35), "fp8": (0.35, 1.2),
            }.get(kv_dtype, (0.05, 0.3))
            err = float(np.percentile(rel, 99))
            assert err < p99_tol and rel.max() < max_tol, \
                f"{arch}/{mode}/{remote_attn}/{bk}: p99 {err} max {rel.max()}"
            assert (out.argmax(-1) == ref_last.argmax(-1)).all()
        else:
            err = float(rel.max())
            assert err < 2e-3, \
                f"{arch}/{mode}/{remote_attn}/{bk}: max rel err {err}"
        assert np.isfinite(out).all()
        print(f"PASS {arch} {mode} {remote_attn} {spill_dtype} "
              f"kv={kv_dtype} backend={bk} err={err:.2e}")
    if backend == "both":
        perr = np.max(np.abs(outs["jnp"] - outs["pallas"])
                      / (np.abs(outs["jnp"]) + 1e-3))
        # both backends read the SAME quantized pages; their divergence
        # stays at numerics level even under int8/fp8 storage
        assert perr < 2e-3, f"jnp vs pallas diverge: {perr}"
        print(f"PASS backend-parity jnp~pallas err={perr:.2e}")


if __name__ == "__main__":
    main(*sys.argv[1:])
