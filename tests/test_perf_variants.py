"""Optimized-lowering variants (§Perf) stay bit-comparable to the oracle:
kv_split attention mesh, q-head padding, expert parallelism padding.

Under GSPMD these lowerings use auto-typed TP axes of size > 1 inside
shard_map; the MANUAL lowering (explicit transport psums + manual expert
parallelism, DESIGN.md §3.6) is the alternative. The snippets print the
resolved lowering so CI logs show which path ran."""
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")

SNIPPET_PAD_HEADS = r"""
import jax, jax.numpy as jnp
from repro import compat
from repro.compat import AxisType
from repro.configs.base import ModelConfig, RunConfig
from repro.core import pipeline as pp
from repro.models.api import build_model
from repro.models.topology import Topology

cfg = ModelConfig(arch="padtest", family="dense", num_layers=2, d_model=48,
                  num_heads=6, num_kv_heads=2, d_ff=96, vocab_size=128,
                  head_dim=8, dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 128)
ref = model.forward(params, toks)[:, -1, :]
mesh = compat.make_mesh((2, 2, 2), ("data", "kv", "qg"),
                        axis_types=(AxisType.Auto,)*3)
topo = Topology(mesh=mesh, tp_axis=("kv", "qg"))
factors = pp.kv_split_axes(cfg, 4)
assert factors == (2, 2, 4), factors
cfg_pad, params_pad = pp.pad_q_heads(cfg, params, factors[2])
assert cfg_pad.num_heads == 8
plan = pp.build_plan(cfg_pad, 2, 64, RunConfig(num_chunks=8, num_stages=2))
staged = pp.stage_params(cfg_pad, params_pad, plan)
with compat.set_mesh(mesh):
    out = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg_pad, st, tk, plan, topo))(staged, toks)
err = float(jnp.max(jnp.abs(out - ref) / (jnp.abs(ref) + 1e-3)))
assert err < 2e-3, err
print("PASS", err)
"""

SNIPPET_EP = r"""
import jax, jax.numpy as jnp
from repro import compat
from repro.compat import AxisType
from repro.configs.base import ModelConfig, MoEConfig, RunConfig
from repro.core import pipeline as pp
from repro.models.api import build_model
from repro.models.topology import Topology

cfg = ModelConfig(arch="eptest", family="moe", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=128,
                  head_dim=8, dtype="float32",
                  moe=MoEConfig(num_experts=6, top_k=2, d_expert=64,
                                capacity_factor=8.0))
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 128)
ref = model.forward(params, toks)[:, -1, :]
mesh = compat.make_mesh((2, 2, 2), ("data", "kv", "qg"),
                        axis_types=(AxisType.Auto,)*3)
topo = Topology(mesh=mesh, tp_axis=("kv", "qg"))
cfg2, params2 = pp.pad_experts(cfg, params, 8)
assert cfg2.moe.num_experts == 8 and cfg2.moe.real_experts == 6
plan = pp.build_plan(cfg2, 2, 64, RunConfig(num_chunks=8, num_stages=2))
staged = pp.stage_params(cfg2, params2, plan)
with compat.set_mesh(mesh):
    out = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg2, st, tk, plan, topo))(staged, toks)
err = float(jnp.max(jnp.abs(out - ref) / (jnp.abs(ref) + 1e-3)))
assert err < 2e-3, err
print("PASS", plan.tp_lowering, err)
"""


def _run(snippet):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", snippet], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "PASS" in r.stdout


def test_kv_split_with_head_padding():
    _run(SNIPPET_PAD_HEADS)


def test_expert_parallel_with_padding():
    _run(SNIPPET_EP)


def test_pad_experts_masks_router():
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    # padded experts must never be selected even with favorable logits
    d, e = 8, 4
    params = {
        "router": jnp.ones((d, e)),            # pads have HIGH raw logits
        "wg": jnp.ones((e, d, 8)) * 0.1,
        "wu": jnp.ones((e, d, 8)) * 0.1,
        "wd": jnp.ones((e, 8, d)) * 0.1,
    }
    x = jnp.ones((1, 4, d))
    full = L.moe_layer(params, x, num_experts=e, top_k=2,
                       capacity_factor=8.0, num_real=2)
    only_real = L.moe_layer(
        {k: (v[:, :2] if k == "router" else v[:2]) for k, v in params.items()},
        x, num_experts=2, top_k=2, capacity_factor=8.0)
    assert jnp.allclose(full, only_real, atol=1e-6)
