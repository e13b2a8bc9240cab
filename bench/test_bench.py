"""Tests of the benchmark itself, on the CPU: ``pytest bench``.

- operation and byte counts against a hand count at a tiny size;
- the peaks table refuses an unknown device kind;
- the traffic generator gives every seed the same work;
- the weights a run serves equal the ones the reference makes layer by
  layer;
- the trace reduction on hand-made intervals and on a small trace
  recorded on four TPU v5e chips;
- whole runs at a tiny size with the timed path broken underneath
  (an answer altered where it is produced; the exchange between pipeline
  stages left out; the fp8 control in the system's place; a request never
  handed back) come out not correct, and the unbroken run comes out
  correct.
"""
import json
import os
import time
import types

import numpy as np
import pytest

import flops
import harness
import tracefile
import traffic
import weights

BENCH = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(BENCH, "testdata", "pipeline4.xplane.pb.gz")

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

def test_served_weights_equal_the_reference_layers():
    import jax
    key = weights.base_key(2**40 + 3)
    flat = jax.jit(lambda k: weights.flat_params(k, TINY))(key)
    for i in range(TINY["num_hidden_layers"]):
        one = weights.layer(key, TINY, i)
        for name, w in one.items():
            np.testing.assert_array_equal(np.asarray(flat["layers"][name][i]),
                                          np.asarray(w))
    assert not np.array_equal(np.asarray(flat["layers"]["wq"][0]),
                              np.asarray(flat["layers"]["wq"][1]))


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

def _cell(config, traffic_spec, chips=1):
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bm = json.load(f)
    # At this size the served path reads 0.0043-0.0057 and the fp8 control
    # 0.037-0.044 over six seeds on the CPU: the limit lies between.
    config = dict(config, check={"sample": 2,
                                 "limits": {"logit_rel_err": 0.015}})
    return types.SimpleNamespace(name="tiny", config=config,
                                 traffic=traffic_spec, chips=chips,
                                 end_to_end=bm["end_to_end"][:2],
                                 per_layer=[])


def _run(cell, seed=2**32 + 11):
    import jax
    return harness.run(cell, seed, 1.0, False, jax.devices(),
                       time.perf_counter())


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
