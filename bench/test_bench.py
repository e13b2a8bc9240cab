"""Tests of the benchmark itself, on the CPU: ``pytest bench``.

- operation and byte counts against a hand count at a tiny size;
- the peaks table refuses an unknown device kind;
- the traffic generator gives every seed the same work;
- the dense configurations' counts and weights are frozen at the values
  they had before layouts were looked up by name;
- the weights a run serves equal the ones the reference makes layer by
  layer, for each layout; tensor ids never collide across layouts;
- the trace reduction on hand-made intervals and on a small trace
  recorded on four TPU v5e chips;
- whole runs at a tiny size with the timed path broken underneath
  (an answer altered where it is produced; the exchange between pipeline
  stages left out; the fp8 control in the system's place; a request never
  handed back) come out not correct, and the unbroken run comes out
  correct;
- a second parameter tree, a dropless MoE whose layout and reference are
  fixtures in ``testdata/``, runs correct through the same harness, and
  not correct with the system's top-k routing broken.
"""
import glob
import hashlib
import json
import os
import time
import types

import numpy as np
import pytest

import flops
import harness
import lookup
import tracefile
import traffic
import weights

BENCH = os.path.dirname(os.path.abspath(__file__))
TESTDATA = os.path.join(BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "pipeline4.xplane.pb.gz")

TINY = {
    "arch": "qwen3-8b", "reference": "dense_gqa", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "qk_norm": True,
    "torch_dtype": "bfloat16",
    "serve": {"stages": 1, "tp": 1, "attn_backend": "pallas",
              "pool_backend": "paged", "kv_dtype": "auto"},
}
# Qwen3-MoE's keys at a tiny size; layout and reference under testdata/
TINY_MOE = dict(TINY, arch="tiny-moe", layout="dropless_moe",
                reference="dropless_moe", num_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=32,
                norm_topk_prob=True)
TINY_TRAFFIC = {"loop": "closed", "clients": 1,
                "lengths": {"kind": "fixed", "tokens": 128},
                "buckets": [128], "num_chunks": 4, "max_batch": 1,
                "drain_cap_s": 30}


# -------------------------------------------------------------- counting

def test_model_flops_hand_count():
    c = dict(TINY, num_hidden_layers=2)
    s = 8
    d, f, h, kvh, hd, v = 64, 128, 4, 2, 16, 256
    linear = 2 * s * 2 * (d * h * hd + 2 * d * kvh * hd + h * hd * d
                          + 3 * d * f)
    pairs = sum(i + 1 for i in range(s))          # causal, with the diagonal
    attention = 2 * 2 * 2 * h * hd * pairs        # QK and PV, 2 layers
    assert pairs == 36
    assert flops.model_flops(c, s) == linear + attention + 2 * d * v


def test_kernel_counts_cover_the_causal_triangle():
    """Self calls plus pool calls over all chunks count every causal
    (query, key) pair once."""
    c = dict(TINY, num_hidden_layers=1)
    s, m = 64, 4
    ch = s // m
    per_pair = 4 * c["num_attention_heads"] * c["head_dim"]
    self_f = sum(flops.self_kernel(c, ch)[0] for _ in range(m))
    pool_f = sum(flops.pool_kernel(c, ch, j * ch)[0] for j in range(1, m))
    assert self_f + pool_f == per_pair * flops.causal_pairs(s)
    # bytes of one pool call: q and two prefix pages in, f32 state out
    fl, by = flops.pool_kernel(c, 16, 32)
    assert by == (16 * 4 * 16 + 2 * 32 * 2 * 16) * 2 + 16 * 4 * (16 + 2) * 4


def test_kernel_min_seconds_picks_the_binding_bound():
    c = dict(TINY, num_hidden_layers=3)
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    out = flops.kernel_min_seconds(c, 64, 4, peak)
    want = 3 * sum(max(*flops.pool_kernel(c, 16, j * 16))
                   for j in range(1, 4)) / 1e12
    assert out["pool"] == pytest.approx(want)
    slow_hbm = dict(peak, hbm_bytes_per_s=1e6)
    assert flops.kernel_min_seconds(c, 64, 4, slow_hbm)["self"] == (
        pytest.approx(3 * 4 * flops.self_kernel(c, 16)[1] / 1e6))


# The dense configurations' counts as they read before layouts were looked
# up by name, at their cells' lengths and chunk counts: exact, not approx.
FROZEN_COUNTS = [
    ("qwen3-8b-s9", 32768, 16, 192967951253504.0,
     0.025128011391350256, 0.37673621763898474),
    ("qwen3-8b-s9", 2048, 8, 7423099142144.0,
     0.000835403956043956, 0.0014058431332689238),
    ("qwen3-8b-s9", 4096, 8, 15463428915200.0,
     0.001670807912087912, 0.005494069840568528),
    ("qwen3-8b-s9", 8192, 8, 33399514333184.0,
     0.003341615824175824, 0.02197627936227411),
    ("qwen3-8b-s9", 16384, 8, 76693388656640.0,
     0.012564005695675128, 0.08790511744909645),
    ("qwen3-8b-pp4", 32768, 16, 771868071034880.0,
     0.10051204556540103, 1.506944870555939),
    ("mistral-123b-s4", 16384, 8, 207810113568768.0,
     0.016752007594233502, 0.11720682326546193),
]


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,seq,chunks,model,self_s,pool_s",
                         FROZEN_COUNTS)
def test_dense_counts_are_frozen(name, seq, chunks, model, self_s, pool_s):
    c = _config(name)
    assert "layout" not in c        # a file without the key is dense GQA
    peak = flops.peaks("TPU v5 lite")
    assert flops.model_flops(c, seq) == model
    assert flops.kernel_min_seconds(c, seq, chunks, peak) == {
        "self": self_s, "pool": pool_s}


def _write_layout(root, name, body):
    os.makedirs(os.path.join(root, "layouts"), exist_ok=True)
    with open(os.path.join(root, "layouts", name + ".py"), "w") as f:
        f.write(body)


def test_one_layer_in_four_attends_hand_count(tmp_path):
    """A layout, added as a file, in which 1 of 4 layers attends: its
    attention kernels' least time is the per-layer calls, counted by hand,
    once, and exactly a quarter of the dense count at the same widths."""
    _write_layout(str(tmp_path), "one_in_four", (
        "def attention_layers(c):\n"
        "    return c['num_hidden_layers'] // 4\n"))
    dense = dict(TINY, num_hidden_layers=4)
    one = dict(dense, layout="one_in_four")
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    seq, m = 64, 4
    ch = seq // m
    least = lambda fl, by: max(fl / 1e12, by / 1e9)
    by_hand = {"self": sum(least(*flops.self_kernel(one, ch))
                           for _ in range(m)),
               "pool": sum(least(*flops.pool_kernel(one, ch, j * ch))
                           for j in range(1, m))}
    got = flops.kernel_min_seconds(one, seq, m, peak, root=str(tmp_path))
    assert got == by_hand
    full = flops.kernel_min_seconds(dense, seq, m, peak)
    assert got == {k: v / 4 for k, v in full.items()}


def test_moe_fixture_model_flops_hand_count():
    c = TINY_MOE
    s = 8
    d, h, kvh, hd, v, e, k, fe = 64, 4, 2, 16, 256, 8, 2, 32
    per_token = 2 * 4 * (d * h * hd + 2 * d * kvh * hd + h * hd * d
                         + d * e + k * 3 * d * fe)
    attention = 4 * 4 * h * hd * 36               # 4 layers, 36 pairs
    assert flops.model_flops(c, s, root=TESTDATA) == (
        per_token * s + attention + 2 * d * v)


def test_share_over_one_is_an_error():
    assert flops.share(1.0, 2.0) == 0.5
    with pytest.raises(ValueError):
        flops.share(2.0, 1.0)
    with pytest.raises(ValueError):
        flops.share(1.0, 0.0)


def test_peaks_known_and_unknown():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bytes_per_s"] == 1600e9 / 8
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


# --------------------------------------------------------------- traffic

def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["docs32k", "mixed-open", "docs16k"])
def test_every_seed_gets_the_same_work(mix):
    t = _mix(mix)
    runs = [traffic.Schedule(t, seed, 50.0) for seed in (1, 2**33 + 5)]
    n = (round(t["rate_per_s"] * 50.0) if t["loop"] == "open"
         else traffic.CLOSED_CYCLE)
    lens = [sorted(r.length(i) for i in range(n)) for r in runs]
    assert lens[0] == lens[1]
    assert set(lens[0]) <= set(t["buckets"])
    if t["loop"] == "open":
        assert len(runs[0].arrivals) == len(runs[1].arrivals) == round(
            t["rate_per_s"] * 50.0)
        assert all(0 <= a < 50.0 for a in runs[0].arrivals)
        replayed = runs[0].arrivals == runs[1].arrivals
        assert replayed == ("schedule_seed" in t)
        other = traffic.Schedule(dict(t, schedule_seed=2), 1, 50.0)
        assert other.arrivals != runs[0].arrivals


def test_lognormal_buckets_and_tokens():
    t = _mix("mixed-open")
    lens = traffic.length_multiset(t, 1000)
    share = {b: lens.count(b) / 1000 for b in t["buckets"]}
    # P(len <= 2048) = Phi(ln(1/2)) = 0.244 for median 4096, sigma 1
    assert share[2048] == pytest.approx(0.244, abs=0.01)
    assert share[16384] == pytest.approx(0.244, abs=0.01)
    a = traffic.tokens(7, 3, 2048, 151936)
    assert a.shape == (2048,) and a.dtype == np.int32
    assert (a == traffic.tokens(7, 3, 2048, 151936)).all()
    assert not (a == traffic.tokens(8, 3, 2048, 151936)).all()


# --------------------------------------------------------------- weights

def _digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, x in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).view(np.uint16).tobytes())
    return h.hexdigest()


# sha256 of the dense tensors as made before layouts were looked up by
# name (seed 2**40 + 3): the flat tree, the layers, the globals
FROZEN_WEIGHTS = [
    (TINY, ("bca643ffbd6e3401a814e47b24ecd94232ae8a42ff3eb7b605f503241b39adef",
            "137dd2067004b6c5a7e5efc61092e2385b74401a3d5916f6c21baaac5695a086",
            "1da50382301e198b900a73979fd05cab8446a73800a0d1ddf3daa9fc0cfdf8d5")),
    (dict(TINY, qk_norm=False, tie_word_embeddings=True, num_hidden_layers=2),
     ("e1d430970b059034bf143017a7e8350ce7d5ff1ec3c9271198661b6535526185",
      "0aeacc6af8e41e14bda880ee5da2af745ed29e099c361dd6f56701de1104a566",
      "a7a3cdf5cb6bfdea1df51eaab9caa83067b85531bc2b23afed0f9e2e289fd109")),
]


@pytest.mark.parametrize("c,want", FROZEN_WEIGHTS,
                         ids=["qk_norm-untied", "no_qk_norm-tied"])
def test_dense_weights_are_frozen(c, want):
    import jax
    dense = lookup.layout(c)
    key = weights.base_key(2**40 + 3)
    flat = jax.jit(lambda k: dense.flat_params(k, c))(key)
    layers = {str(i): dense.layer(key, c, i)
              for i in range(c["num_hidden_layers"])}
    assert (_digest(flat), _digest(layers),
            _digest(dense.globals_(key, c))) == want


# sha256 of each dense configuration file's tree (names, shapes, dtypes)
# before layouts were looked up by name
FROZEN_TREES = {
    "qwen3-8b-s9":
        "e122376c501686000fe107c5c0619a3c4e60901d7af11b2a42535b7461867dc1",
    "qwen3-8b-pp4":
        "afd0f54b83096731c6f72524cb36a30d09f31cabb2b23c2605f20d43ff596388",
    "mistral-123b-s4":
        "4e9751f59456a70a4c3e8a4e50d2fe8f84445253d25ccbcaed59b748f6284468",
}


@pytest.mark.parametrize("name", sorted(FROZEN_TREES))
def test_dense_trees_are_frozen(name):
    import jax
    c = _config(name)
    tree = jax.eval_shape(lambda k: lookup.layout(c).flat_params(k, c),
                          weights.base_key(1))
    leaves = sorted((jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
                    for p, x in jax.tree_util.tree_leaves_with_path(tree))
    assert hashlib.sha256(repr(leaves).encode()).hexdigest() == (
        FROZEN_TREES[name])


@pytest.mark.parametrize("c,root", [(TINY, BENCH), (TINY_MOE, TESTDATA)],
                         ids=["dense_gqa", "dropless_moe"])
def test_served_weights_equal_the_reference_layers(c, root):
    import jax
    from repro.models.api import build_model
    lay = lookup.layout(c, root)
    key = weights.base_key(2**40 + 3)
    flat = jax.jit(lambda k: lay.flat_params(k, c))(key)
    weights.check_layout(flat, jax.eval_shape(
        build_model(harness.model_config(c, root)).init, jax.random.key(0)))
    layer = jax.jit(lambda k, i: lay.layer(k, c, i))    # as the check makes it
    for i in range(c["num_hidden_layers"]):
        one = layer(key, i)
        assert set(one) == set(flat["layers"])
        for name, w in one.items():
            np.testing.assert_array_equal(np.asarray(flat["layers"][name][i]),
                                          np.asarray(w))
    for name, w in lay.globals_(key, c).items():
        np.testing.assert_array_equal(np.asarray(flat[name]), np.asarray(w))
    assert not np.array_equal(np.asarray(flat["layers"]["wq"][0]),
                              np.asarray(flat["layers"]["wq"][1]))


def test_tensor_ids_never_collide_across_layouts():
    """A name means one id in every layout, and no two names share one."""
    paths = (glob.glob(os.path.join(BENCH, "layouts", "*.py"))
             + glob.glob(os.path.join(TESTDATA, "layouts", "*.py")))
    seen = {}
    for path in paths:
        root = os.path.dirname(os.path.dirname(path))
        name = os.path.basename(path)[:-3]
        for tensor, tid in lookup.module("layouts", name, root).TENSOR_IDS.items():
            assert seen.setdefault(tensor, tid) == tid, (path, tensor)
    assert len(set(seen.values())) == len(seen), seen
    assert len(paths) >= 2


def test_held_experts_equal_the_whole_models():
    """A model cut to fewer experts holds the whole model's first ones."""
    import jax
    moe = lookup.layout(TINY_MOE, TESTDATA)
    key = weights.base_key(2**35 + 1)
    whole = jax.jit(lambda k: moe.flat_params(k, TINY_MOE))(key)
    cut = dict(TINY_MOE, num_experts=4)
    held = jax.jit(lambda k: moe.flat_params(k, cut))(key)
    for name in moe.EXPERT:
        np.testing.assert_array_equal(np.asarray(held["layers"][name]),
                                      np.asarray(whole["layers"][name])[:, :4])
    assert not np.array_equal(np.asarray(whole["layers"]["e_wg"][0, 0]),
                              np.asarray(whole["layers"]["e_wg"][0, 1]))


def test_check_layout_refuses_another_tree():
    """The dense tree against the system's MoE tree, and the MoE tree
    against a system with other expert counts, are refused."""
    import jax
    from repro.configs.base import replace
    from repro.models.api import build_model
    key = weights.base_key(5)
    moe_cfg = harness.model_config(TINY_MOE, TESTDATA)
    system = jax.eval_shape(build_model(moe_cfg).init, jax.random.key(0))
    dense = jax.eval_shape(lambda k: lookup.layout(TINY).flat_params(k, TINY),
                           key)
    with pytest.raises(ValueError):
        weights.check_layout(dense, system)
    made = jax.eval_shape(
        lambda k: lookup.layout(TINY_MOE, TESTDATA).flat_params(k, TINY_MOE),
        key)
    weights.check_layout(made, system)
    fewer = replace(moe_cfg, moe=replace(moe_cfg.moe, num_experts=4))
    with pytest.raises(ValueError):
        weights.check_layout(made, jax.eval_shape(
            build_model(fewer).init, jax.random.key(0)))


# ---------------------------------------------------------------- traces

def test_interval_arithmetic():
    u = tracefile.union([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert u == [[0, 12], [20, 31]]
    assert tracefile.total(u) == 23
    assert tracefile.subtract([[0, 12], [20, 31]], [[2, 4], [10, 25]]) == [
        [0, 2], [4, 10], [25, 31]]


def test_trace_reduction_hand_made():
    """One chip: a loop holding a matmul and a collective, then a kernel;
    a host step around the idle gap between them."""
    dev = {"/device:TPU:0": [("while.5", 100, 600), ("fusion.1", 100, 300),
                             ("collective-permute-done.2", 300, 600),
                             ("chunk_attention.3", 700, 900)]}
    host = [("bench_window", 0, 1000), ("bench_step", 600, 700)]
    tr = tracefile.Trace(dev, host)
    assert tr.busy_s() == {"/device:TPU:0": pytest.approx(700e-9)}
    # the loop holds the collective but does no work of its own there
    assert tr.exposed_collective_s() == {"/device:TPU:0": pytest.approx(
        300e-9)}
    assert tr.op_seconds(lambda n: n == "chunk_attention") == pytest.approx(
        200e-9)
    top = dict(tr.top_ops())
    assert top["collective-permute-done.2"] == pytest.approx(300e-9)
    assert top["while.5"] == pytest.approx(0.0)
    gaps = tr.idle_gaps()
    assert len(gaps) == 3 and all(g == pytest.approx(100e-9)
                                  for _, g in gaps)
    assert sorted(label for label, _ in gaps) == [
        "bench_step", "host_other", "host_other"]
    with pytest.raises(tracefile.TraceError):
        tracefile.Trace(dev, [])


def test_trace_names_and_nesting():
    name = ("%fusion.162 = bf16[1,2048,4096]{2,1,0} fusion(bf16[1,2048,4096]"
            " %p0), kind=kOutput")
    assert tracefile.short_name(name) == "fusion.162"
    assert tracefile.base_name("pool_attention_paged.11") == (
        "pool_attention_paged")
    assert tracefile.base_name("copy") == "copy"
    out = tracefile.self_times([("a", 0, 10), ("b", 1, 4), ("c", 4, 6),
                                ("d", 12, 14)])
    assert [(n, o) for n, _, _, o in out] == [("a", 5), ("b", 3), ("c", 2),
                                              ("d", 2)]


def test_trace_reduction_recorded():
    """One 32768-token request through the four-stage pipeline, traced on
    four v5e chips (``qwen3-8b-pp4.docs32k``, ``--seconds 1 --trace 1``)."""
    tr = tracefile.Trace.load(RECORDED)
    assert len(tr.devices) == 4
    busy = tr.busy_s()
    for plane, b in busy.items():
        ops = tr.ops(plane)
        # busy is the union: no more than the summed op time, no less
        # than the longest op, inside the window
        assert max(e - s for _, s, e in ops) * 1e-9 <= b
        assert b <= sum(e - s for _, s, e in ops) * 1e-9 + 1e-12
        assert 0 < b <= tr.window_s
    exposed = tr.exposed_collective_s()
    coll = {p: sum(e - s for n, s, e in tr.ops(p)
                   if tracefile.COLLECTIVE.search(n)) * 1e-9
            for p in tr.devices}
    assert all(0 <= exposed[p] <= coll[p] + 1e-12 for p in tr.devices)
    assert sum(coll.values()) > 0
    top = tr.top_ops()
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    gaps = tr.idle_gaps()
    assert all(g[1] > 0 for g in gaps)
    # what the traced run reported for this trace
    run = types.SimpleNamespace(trace=tr)
    read_exposed = harness.metric_reader("pipeline.ici_exposed_frac")
    assert read_exposed(run) == pytest.approx(0.11723, rel=1e-4)
    read_idle = harness.metric_reader("device.idle_frac")
    assert read_idle(run) == pytest.approx(0.00168, rel=1e-2)
    names = {tracefile.base_name(n) for n, _ in top}
    assert {"chunk_attention", "pool_attention_paged",
            "collective-permute-start"} <= names
    idle = tracefile.total(tracefile.subtract(
        [[tr.w0, tr.w1]], tracefile.union(
            (s, e) for _, s, e in tr.ops(max(busy, key=busy.get))))) * 1e-9
    assert idle == pytest.approx(tr.window_s - max(busy.values()))


# ------------------------------------------------------------ whole runs

def _cell(config, traffic_spec, chips=1, limit=0.015):
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bm = json.load(f)
    # At this size the served path reads 0.0043-0.0057 and the fp8 control
    # 0.037-0.044 over six seeds on the CPU: the limit lies between.
    config = dict(config, check={"sample": 2,
                                 "limits": {"logit_rel_err": limit}})
    return types.SimpleNamespace(name="tiny", config=config,
                                 traffic=traffic_spec, chips=chips,
                                 end_to_end=bm["end_to_end"][:2],
                                 per_layer=[])


def _run(cell, seed=2**32 + 11, root=BENCH):
    import jax
    return harness.run(cell, seed, 1.0, False, jax.devices(),
                       time.perf_counter(), root=root)


def test_unbroken_run_is_correct():
    res = _run(_cell(TINY, TINY_TRAFFIC))
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert list(res)[-1] == "check"
    assert res["metrics"]["ttft_p50_s"]["value"] > 0


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.runtime import engine

    real = engine.JaxExecutor.run

    def altered(self, requests, *a, **k):
        out = real(self, requests, *a, **k)
        for r in requests:
            r.result = np.roll(np.asarray(r.result), 1)
        return out

    monkeypatch.setattr(engine.JaxExecutor, "run", altered)
    res = _run(_cell(TINY, TINY_TRAFFIC))
    assert not res["correct"], res["check"]


def test_dropped_stage_exchange_is_not_correct(monkeypatch):
    """Four stages on four host devices; the ring shift that hands each
    chunk to the next stage returns its input instead."""
    from repro.core import transport
    cfg4 = dict(TINY, serve=dict(TINY["serve"], stages=4))
    ok = _run(_cell(cfg4, TINY_TRAFFIC, chips=4))
    assert ok["correct"], ok["check"]
    monkeypatch.setattr(transport.JaxCollectiveTransport, "ring_shift",
                        lambda self, x, axis, perm, led=None, active=None:
                        (x, led))
    res = _run(_cell(cfg4, TINY_TRAFFIC, chips=4))
    assert not res["correct"], res["check"]


def test_fp8_control_in_the_systems_place_is_not_correct(monkeypatch):
    """The control, the reference computed in float8, served as the
    system's answer."""
    import jax
    from repro.runtime import engine
    cell = _cell(TINY, TINY_TRAFFIC)
    seed = 2**32 + 11
    real = engine.JaxExecutor.run

    def control(self, requests, *a, **k):
        out = real(self, requests, *a, **k)
        for r in requests:
            if r.rid >= 0:
                r.result = harness.reference_logits(
                    TINY, seed, [r.tokens], ("fp8",), jax.devices()[0])[0][
                        "fp8"]
        return out

    monkeypatch.setattr(engine.JaxExecutor, "run", control)
    res = _run(cell, seed)
    assert not res["correct"], res["check"]


def test_lost_request_is_not_correct(monkeypatch):
    """A request the engine never hands back counts as failed, and the run
    is not correct even though every answer that came is right."""
    from repro.runtime import engine
    real = engine.PrefillEngine.poll
    monkeypatch.setattr(engine.PrefillEngine, "poll", lambda self: [
        r for r in real(self) if r.rid != 1])
    res = _run(_cell(TINY, dict(TINY_TRAFFIC, drain_cap_s=2)))
    assert res["failed"] == 1 and res["attempted"] == 2
    assert not res["correct"], res


# The MoE fixture's served path reads 0.0045-0.0071 over eleven seeds on the
# CPU and 0.0138 on a twelfth, where the last token's 2nd and 3rd router
# logits lie 1e-4 apart in the last layer and the bfloat16 router flips the
# pick; the fp8 control reads 0.0375-0.0445 (3 seeds), top k - 1 routing
# 0.038-0.042 (3 seeds).
MOE_LIMIT = 0.025


def test_moe_fixture_run_is_correct():
    """A configuration of another parameter tree enters through a layout
    and a reference under ``testdata/`` alone, and runs correct."""
    res = _run(_cell(TINY_MOE, TINY_TRAFFIC, limit=MOE_LIMIT), root=TESTDATA)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 2


def test_broken_topk_routing_is_not_correct(monkeypatch):
    """The system's MoE routes each token to its top k - 1 experts."""
    from repro.models import layers
    real = layers.moe_layer
    monkeypatch.setattr(layers, "moe_layer", lambda params, x, *, top_k, **k:
                        real(params, x, top_k=top_k - 1, **k))
    res = _run(_cell(TINY_MOE, TINY_TRAFFIC, limit=MOE_LIMIT), root=TESTDATA)
    assert not res["correct"], res["check"]
