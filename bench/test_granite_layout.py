"""The granite-4.0-h-small layout (``layouts/granite_hybrid_moe.py``) and
its reference, on the CPU: ``pytest bench``.

- the layout's tree is the system's, for the benchmark's configuration and
  a tiny one of the same keys;
- its counts equal a hand count at 32768 tokens;
- the weights the system gets equal the ones the reference makes layer by
  layer, and a cut to fewer experts holds the whole model's first ones;
- a tiny whole run comes out correct, and does not with the timed path
  broken underneath: the Mamba state zeroed at every chunk, or rotary
  positions applied on the attention layer;
- the three new readers on hand-made runs.
"""
import json
import os
import time
import types

import numpy as np
import pytest

import flops
import harness
import lookup
import weights

BENCH = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(BENCH, "configs", "granite-4.0-h-small-s10.json")) as f:
    CONFIG = json.load(f)
LAYOUT = lookup.layout(CONFIG)

# the benchmark configuration's keys at a tiny size: one period (the
# attention layer at index 5), 16 routed experts of which 8 are held, top 4
TINY = dict(
    CONFIG, attention_multiplier=0.0625, hidden_size=64,
    intermediate_size=32, shared_intermediate_size=48, mamba_chunk_size=32,
    mamba_d_head=16, mamba_d_state=16, mamba_n_heads=8,
    num_attention_heads=4, num_key_value_heads=2, num_experts_per_tok=4,
    num_local_experts=8, vocab_size=256,
    published={"num_hidden_layers": 40, "num_local_experts": 16},
    serve={"stages": 1, "tp": 1, "attn_backend": "pallas",
           "pool_backend": "paged", "kv_dtype": "auto"})
TRAFFIC = {"loop": "closed", "clients": 1,
           "lengths": {"kind": "fixed", "tokens": 128}, "buckets": [128],
           "num_chunks": 4, "max_batch": 1, "drain_cap_s": 30}
# Served in bfloat16 at this size the path reads 0.0051-0.0057 over three
# seeds on the CPU: the limit lies above it.
LIMIT = 0.015
# In bfloat16 the tiny model's rounding hides both faults of the timed path
# below: at 64 wide a layer writes 8x less into the residual stream beside
# the embedding than at 4096 (the seeded projections' std does not grow with
# the width), and each fault moves the logit row by less than the rounding
# does. So the faults are read in float32, with the embedding's x12 taken
# down by that factor to x1.5. There the path reads 2.9e-7-3.7e-7 over three
# seeds on the CPU, the Mamba state zeroed at every chunk 1.4e-3-1.9e-3,
# rotary positions on the attention layer 1.5e-3-2.5e-3: the limit lies
# between, about 8x above the one and 500x below the others. (At the
# benchmark's size, in bfloat16 on the chip, PERF.md section 4 has both
# faults' readings against the cell's own limit.)
TINY32 = dict(TINY, torch_dtype="float32", embedding_multiplier=1.5)
LIMIT32 = 3e-6


def _system_tree(c):
    import jax
    from repro.models.api import build_model
    return jax.eval_shape(build_model(LAYOUT.model_config(c)).init,
                          jax.random.key(0))


@pytest.mark.parametrize("c", [CONFIG, TINY], ids=["s10", "tiny"])
def test_layout_tree_is_the_systems(c):
    import jax
    made = jax.eval_shape(lambda k: LAYOUT.flat_params(k, c),
                          weights.base_key(1))
    weights.check_layout(made, _system_tree(c))
    cfg = LAYOUT.model_config(c)
    assert cfg.moe.num_experts == c["published"]["num_local_experts"]
    assert cfg.moe.held == c["num_local_experts"]
    assert cfg.nope and cfg.kinds.count("attention") == 1


def test_counts_by_hand_at_32768():
    s, m = 32768, 16
    ch = s // m
    # one Mamba layer: in_proj 4096 x (8192 + 8448 + 128), out_proj 8192 x
    # 4096; the scan: 32768 x 257 / 2 causal pairs in blocks of 256, each
    # 2 (1 x 128 + 128 x 64), and 4 x 128 x 64 x 128 per token
    scan = 2 * (s * 257 / 2) * (128 + 128 * 64) + 4 * s * 128 * 64 * 128
    mamba = 2 * (4096 * 16768 + 8192 * 4096) * s + scan
    attn = (2 * (4096 * 48 * 128 + 4096 * 4096) * s
            + 4 * 32 * 128 * s * (s + 1) / 2)
    # every layer: router 4096 x 72, shared 3 x 4096 x 1536, and
    # 10 x 36 / 72 = 5 held picks a token at 6 x 4096 x 768 each
    moe = 2 * s * (4096 * 72 + 3 * 4096 * 1536) + 6 * 4096 * 768 * 5 * s
    head = 2 * 4096 * 100352
    assert flops.model_flops(CONFIG, s) == pytest.approx(
        9 * mamba + attn + 10 * moe + head, rel=1e-12)
    fl, by = LAYOUT.ssd_kernel(CONFIG, ch)
    assert fl == 2 * (ch * 257 / 2) * (128 + 8192) + 4 * ch * 128 * 64 * 128
    assert by == (ch * (2 * 8192 * 2 + 128 * 4 + 2 * 128 * 2)
                  + 2 * 128 * 64 * 128 * 4)
    fl, by = LAYOUT.moe_experts_kernel(CONFIG, 10240)
    assert fl == 6 * 4096 * 768 * 10240
    assert by == (36 * 3 * 4096 * 768 + 2 * 10240 * 4096) * 2
    assert LAYOUT.attention_layers(CONFIG) == 1
    peak = flops.peaks("TPU v5 lite")
    # the held experts' work of a 2048-token chunk lies near the ridge
    # (0.98 ms of operations, 1.03 ms of bytes)
    assert LAYOUT.least_seconds(fl, by, peak) == pytest.approx(
        by / 819e9)


def test_served_weights_equal_the_reference_layers():
    import jax
    key = weights.base_key(2**40 + 3)
    flat = jax.jit(lambda k: LAYOUT.flat_params(k, TINY))(key)
    layer = jax.jit(lambda k, i: LAYOUT.layer(k, TINY, i))
    at = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(LAYOUT.kinds(TINY)):
        one = layer(key, i)
        group = "mamba" if kind == "mamba" else "attn"
        for name, w in flat[group].items():
            np.testing.assert_array_equal(np.asarray(w[at[kind]]),
                                          np.asarray(one[name]))
        for name, w in flat["moe"].items():
            np.testing.assert_array_equal(np.asarray(w[i]),
                                          np.asarray(one[name]))
        at[kind] += 1
    a = np.exp(np.asarray(flat["mamba"]["a_log"]))
    lo, hi = LAYOUT.A_RANGE
    assert lo <= a.min() and a.max() <= hi
    cut = jax.jit(lambda k: LAYOUT.flat_params(
        k, dict(TINY, num_local_experts=4)))(key)
    for name in LAYOUT.EXPERT:
        np.testing.assert_array_equal(np.asarray(cut["moe"][name]),
                                      np.asarray(flat["moe"][name])[:, :4])


def _cell(config, limit):
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bm = json.load(f)
    config = dict(config, check={"sample": 2,
                                 "limits": {"logit_rel_err": limit}})
    return types.SimpleNamespace(name="tiny", config=config, traffic=TRAFFIC,
                                 chips=1, end_to_end=bm["end_to_end"][:2],
                                 per_layer=[])


def _run(config=TINY, limit=LIMIT, seed=2**32 + 11):
    import jax
    return harness.run(_cell(config, limit), seed, 1.0, False, jax.devices(),
                       time.perf_counter())


@pytest.mark.parametrize("config,limit", [(TINY, LIMIT), (TINY32, LIMIT32)],
                         ids=["bfloat16", "float32"])
def test_tiny_run_is_correct(config, limit):
    res = _run(config, limit)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 2


def test_state_zeroed_at_every_chunk_is_not_correct(monkeypatch):
    """Each chunk's Mamba mixers start from a zero conv and SSD state, as
    if nothing carried from tick to tick."""
    from repro.models import ssm
    real = ssm.block_apply
    monkeypatch.setattr(ssm, "block_apply", lambda cfg, lp, x, *, state=None,
                        **k: real(cfg, lp, x, state=None, **k))
    res = _run(TINY32, LIMIT32)
    assert not res["correct"], res["check"]


def test_rotary_positions_on_attention_is_not_correct(monkeypatch):
    """The served attention layer rotates q and k by position."""
    from repro.configs.base import replace
    real = LAYOUT.model_config
    monkeypatch.setattr(LAYOUT, "model_config",
                        lambda c: replace(real(c), nope=False))
    res = _run(TINY32, LIMIT32)
    assert not res["correct"], res["check"]


# ---------------------------------------------------------------- readers

def _view(by_scope, waves=(), requests=(), config=CONFIG):
    """A run whose scope table is given (the readers' one join); a scope
    given as (op name, scope) names its op."""
    table = {"/device:TPU:0": [
        (scope[0], 0, 1, t * 1e9, scope[1], 0) if isinstance(scope, tuple)
        else (f"op{i}", 0, 1, t * 1e9, scope, 0)
        for i, (scope, t) in enumerate(by_scope)]}
    return types.SimpleNamespace(
        trace=object(), _scopes_op_table=table, waves=list(waves),
        requests=list(requests), config=config,
        traffic={"num_chunks": 16}, peak=flops.peaks("TPU v5 lite"),
        flops=flops)


RAGGED = ("ragged-dot-none.6", "unscoped")


def test_dispatch_frac_reads_the_moe_scopes():
    read = harness.metric_reader("moe.dispatch_frac")
    run = _view([("layer.moe_route", 1.0), ("layer.moe_experts", 5.0),
                 ("layer.moe_combine", 0.5), ("layer.moe_shared", 1.5),
                 ("layer.ssd", 9.0)])
    assert read(run) == pytest.approx(1.5 / 8.0)
    assert read(_view([("layer.mlp", 1.0)])) is None
    # XLA's ragged dots carry no scope and count as the experts' time
    run = _view([("layer.moe_route", 1.0), (RAGGED, 5.0),
                 ("layer.moe_combine", 0.5), ("layer.moe_shared", 1.5),
                 (("fusion.3", "unscoped"), 2.0)])
    assert read(run) == pytest.approx(1.5 / 8.0)


def test_expert_roofline_reads_the_wave_counter():
    read = harness.metric_reader("kernel.moe_experts_roofline")
    wave = {"chunks": [2048] * 16, "moe_held_picks": [16 * 10240] * 10}
    least = 10 * 16 * LAYOUT.least_seconds(
        *LAYOUT.moe_experts_kernel(CONFIG, 10240), flops.peaks("TPU v5 lite"))
    run = _view([("layer.moe_experts", 2 * least)], waves=[wave])
    assert read(run) == pytest.approx(50.0)
    # a program without the counter or without the scope reads nothing
    assert read(_view([("layer.moe_experts", 1.0)],
                      waves=[{"chunks": [2048]}])) is None
    assert read(_view([("layer.mlp", 1.0)], waves=[wave])) is None
    with pytest.raises(ValueError):
        read(_view([("layer.moe_experts", 0.5 * least)], waves=[wave]))
    # XLA's ragged dots (no scope) are the grouped matmuls; other
    # unscoped ops are not
    run = _view([(RAGGED, 1.5 * least), ("layer.moe_experts", 0.5 * least),
                 (("fusion.3", "unscoped"), 9.0)], waves=[wave])
    assert read(run) == pytest.approx(50.0)


def test_ssd_roofline_reads_the_scan_scope():
    read = harness.metric_reader("kernel.ssd_roofline")
    per_call = LAYOUT.least_seconds(*LAYOUT.ssd_kernel(CONFIG, 2048),
                                    flops.peaks("TPU v5 lite"))
    least = 9 * 16 * per_call
    run = _view([("layer.ssd", 4 * least), ("layer.ssm_proj", 1.0)],
                requests=[{"seq": 32768}])
    assert read(run) == pytest.approx(25.0)
    assert read(_view([("layer.ssm_proj", 1.0)],
                      requests=[{"seq": 32768}])) is None
    # a dense layout counts no scan
    dense = dict(CONFIG)
    dense.pop("layout")
    assert read(_view([("layer.ssd", 1.0)], requests=[{"seq": 32768}],
                      config=dense)) is None
