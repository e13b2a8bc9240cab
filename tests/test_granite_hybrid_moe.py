"""granite-4.0-h-small's layers against the plain reference
(``bench/reference/granite_hybrid_moe.py``, loaded by path), on seeded
random weights at the smoke size: one whole period of the pattern (nine
Mamba-2 mixers and one NoPE attention mixer), 8 routed experts, top 2.

Tolerances. Both sides compute in float32 here (the reference under
``default_matmul_precision("highest")``), so they agree to rounding: the
relative L2 error of a logit row reads ~2e-7 on three seeds. The limit,
1e-4, lies far below what the same weights read served in bfloat16
(0.006-0.008) or with one pick of the top-k dropped (0.012-0.015); both are
held to 10x the limit (``test_forward_in_bfloat16_is_caught``,
``test_dropped_pick_is_caught``).
"""
import importlib.util
import json
import os
import subprocess
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import RunConfig, get_smoke_config
from repro.core import pipeline as pp
from repro.models import layers as L
from repro.models.api import build_model
from repro.models.topology import Topology

REF_PATH = os.path.join(os.path.dirname(__file__), "..", "bench", "reference",
                        "granite_hybrid_moe.py")
TOL = 1e-4
SEQ, CHUNKS = 128, 4


def _reference():
    spec = importlib.util.spec_from_file_location("granite_reference",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _cfg(periods=1, dtype="float32", **moe):
    cfg = get_smoke_config("granite-4.0-h-small")
    return replace(cfg, num_layers=10 * periods,
                   layer_types=cfg.layer_types * periods, dtype=dtype,
                   moe=replace(cfg.moe, **moe))


def _hf(cfg):
    """The reference's configuration (Hugging Face's keys) of ``cfg``."""
    s, m = cfg.ssm, cfg.moe
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
            "mamba_expand": s.expand,
            "mamba_n_heads": s.expand * cfg.d_model // s.head_dim,
            "mamba_d_head": s.head_dim, "mamba_n_groups": s.n_groups,
            "mamba_d_state": s.d_state, "mamba_d_conv": s.conv_kernel,
            "num_local_experts": m.held, "num_experts_per_tok": m.top_k,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "layer_types": list(cfg.layer_types),
            "num_hidden_layers": cfg.num_layers}


def _layer_weights(cfg, params):
    """layer i's tensors under the reference's names: its mixer's and its
    MoE's."""
    at = {"mamba": 0, "attention": 0}
    out = []
    for i, kind in enumerate(cfg.kinds):
        group = params["mamba" if kind == "mamba" else "attn"]
        lw = {n: a[at[kind]] for n, a in group.items()}
        lw.update({n: a[i] for n, a in params["moe"].items()})
        out.append(lw)
        at[kind] += 1
    return out


def _ref_logits(cfg, params, tokens):
    lws = _layer_weights(cfg, params)
    g = {"embed": params["embed"], "final_norm": params["final_norm"]}
    return REF.last_logits(_hf(cfg), g, lambda i: lws[i],
                           [np.asarray(tokens)])[0]["f32"]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# the init's projections, scaled up so that every layer moves the logits
SCALE = 5.0
KEEP = ("ln", "ln1", "ln2", "gate_norm", "final_norm", "a_log", "dt_bias",
        "d_skip", "conv_b")


def _setup(cfg, seed=0):
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in KEEP else a * SCALE,
        build_model(cfg).init(jax.random.key(seed)))
    tokens = jax.random.randint(jax.random.key(seed + 1), (SEQ,), 0,
                                cfg.vocab_size)
    return params, tokens


@pytest.fixture(scope="module")
def smoke():
    cfg = _cfg()
    params, tokens = _setup(cfg)
    return cfg, params, tokens, _ref_logits(cfg, params, tokens)


def test_model_forward_matches_reference(smoke):
    """(a) ``models/hybrid_moe.forward``, the whole sequence at once."""
    cfg, params, tokens, ref = smoke
    got = build_model(cfg).forward(params, tokens[None])[0, -1]
    assert _rel(got[:cfg.vocab_size], ref) < TOL


def test_forward_in_bfloat16_is_caught(smoke):
    """The same weights served in bfloat16 miss the tolerance by far."""
    cfg, params, tokens, ref = smoke
    bf = replace(cfg, dtype="bfloat16")
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, params)
    got = build_model(bf).forward(low, tokens[None])[0, -1]
    assert _rel(got[:cfg.vocab_size], ref) > 10 * TOL


def test_dropped_pick_is_caught(smoke, monkeypatch):
    """The experts' layer keeping one pick fewer than top-k misses it."""
    cfg, params, tokens, ref = smoke
    real = L.held_moe
    monkeypatch.setattr(L, "held_moe", lambda p, x, *, top_k, **k:
                        real(p, x, top_k=top_k - 1, **k))
    got = build_model(cfg).forward(params, tokens[None])[0, -1]
    assert _rel(got[:cfg.vocab_size], ref) > 10 * TOL


def _pipeline_check(stages):
    """(relative error of the served logits, held pairs per layer)."""
    cfg = _cfg(periods=stages)
    params, tokens = _setup(cfg, seed=3)
    mesh = Mesh(np.asarray(jax.devices()[:stages]).reshape(stages, 1),
                ("data", "model"))
    run = RunConfig(num_chunks=CHUNKS, num_stages=stages,
                    mbkr_spill_chunks=2 if stages > 1 else 0,
                    attn_backend="pallas", pool_backend="paged")
    plan = pp.build_plan(cfg, stages, SEQ, run)
    assert plan.stage_kinds == cfg.kinds[:10] and plan.pool_layers == 1
    assert plan.p2 == (2 if stages > 1 else CHUNKS)
    staged = pp.stage_params(cfg, params, plan)
    logits, held = jax.jit(lambda s, t: pp.prefill_pipeline(
        cfg, s, t, plan, Topology(mesh=mesh)))(staged, tokens[None])
    return (_rel(logits[0, :cfg.vocab_size], _ref_logits(cfg, params, tokens)),
            np.asarray(held).tolist())


@pytest.mark.parametrize("stages", [1, 2])
def test_served_pipeline_matches_reference(stages):
    """(b) ``prefill_pipeline`` as served (the Pallas chunk kernel and the
    paged pool kernel, in interpret mode here): 4 chunks a prompt, the Mamba
    state carried from tick to tick and the NoPE attention layer reading
    its earlier chunks from the paged pool. Two stages (on two host devices,
    in a subprocess; the test process keeps one) take two periods, a stage
    holding whole periods, and MBKR spills two chunks to the pair, so the
    attention layer reads a remote prefix too."""
    if stages == 1:
        err, held = _pipeline_check(1)
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={stages}").strip())
        root = os.path.join(os.path.dirname(__file__), "..")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
        out = subprocess.run([sys.executable, __file__, str(stages)], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-4000:]
        err, held = json.loads(out.stdout.strip().splitlines()[-1])
    assert err < TOL
    # every expert is held: each layer routed every token's top-k
    assert held == [SEQ * 2] * (10 * stages)


def _moe_params(cfg, seed):
    p = _setup(cfg, seed)[0]["moe"]
    return {n: a[0] for n, a in p.items()}


def test_expert_shares_add_up_to_the_whole_layer():
    """(c) Two chips' halves of the experts (each routing over all 8),
    with the shared expert counted once, add up to the reference's uncut
    layer."""
    cfg = _cfg()
    lw = _moe_params(cfg, 5)
    x = jax.random.normal(jax.random.key(6), (1, 64, cfg.d_model))
    hn = L.rms_norm(x, lw["ln2"], cfg.norm_eps)
    half = cfg.moe.num_experts // 2
    parts = [L.held_moe({"router": lw["router"],
                         "e_wgu": lw["e_wgu"][first:first + half],
                         "e_wd": lw["e_wd"][first:first + half]},
                        hn, top_k=cfg.moe.top_k, held_first=first)
             for first in (0, half)]
    shared = L.swiglu({"wg": lw["s_wg"], "wu": lw["s_wu"], "wd": lw["s_wd"]},
                      hn)
    got = parts[0][0] + parts[1][0] + shared
    with jax.default_matmul_precision("highest"):
        want = REF._moe(_hf(cfg), lw, x[0], "f32")
    assert _rel(got[0], want) < TOL
    # every pick lands on exactly one of the two chips
    assert int(parts[0][1]) + int(parts[1][1]) == 64 * cfg.moe.top_k
    assert 0 < int(parts[0][1]) < 64 * cfg.moe.top_k


def test_one_hot_expert_set_drops_nothing():
    """(d) A router that sends every token to the same k experts: the
    dropless layer computes every pick and matches the reference; the
    capacity-bounded ``moe_layer`` (1.25 x the even share per expert)
    drops most of them."""
    cfg = _cfg()
    m = cfg.moe
    lw = _moe_params(cfg, 7)
    picked = np.zeros(m.num_experts, bool)
    picked[[1, 6]] = True
    lw["router"] = jnp.where(picked[None, :], 1.0, -1.0) * jnp.ones(
        (cfg.d_model, m.num_experts))
    x = 1.0 + 0.1 * jax.random.normal(jax.random.key(8), (1, 64, cfg.d_model))
    hn = L.rms_norm(x, lw["ln2"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        want = REF._moe(_hf(cfg), lw, x[0], "f32") - L.swiglu(
            {"wg": lw["s_wg"], "wu": lw["s_wu"], "wd": lw["s_wd"]}, hn)[0]
    got, n_held = L.held_moe({k: lw[k] for k in ("router", "e_wgu", "e_wd")},
                             hn, top_k=m.top_k)
    assert int(n_held) == 64 * m.top_k
    assert _rel(got[0], want) < TOL
    f = m.d_expert
    capped = L.moe_layer({"router": lw["router"],
                          "wg": lw["e_wgu"][..., :f], "wu": lw["e_wgu"][..., f:],
                          "wd": lw["e_wd"]}, hn, num_experts=m.num_experts,
                         top_k=m.top_k, capacity_factor=1.25)
    assert _rel(capped[0], want) > 0.5


def test_pattern_that_splits_unevenly_is_refused():
    """A stage count whose stages would hold different local patterns."""
    cfg = _cfg()
    with pytest.raises(ValueError, match="local pattern"):
        pp.build_plan(cfg, 2, SEQ, RunConfig(num_chunks=CHUNKS))


if __name__ == "__main__":
    print(json.dumps(_pipeline_check(int(sys.argv[1]))))
