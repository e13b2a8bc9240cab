"""repro.obs tests (ISSUE 6): the device StageTelemetry profile matches the
analytic occupancy/byte models and the CollectiveLedger, the disabled path
is bit-identical with zero extra collectives, the merged Perfetto trace
carries every surface in one file, the metrics exporters produce valid
JSON-lines/Prometheus output atomically, and ``count_launches`` nests with
per-kernel attribution."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(snippet, extra_env=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(extra_env or {})
    r = subprocess.run([sys.executable, "-c", snippet], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "PASS" in r.stdout, r.stdout
    return r.stdout


# --------------------------------------------------- analytic occupancy model

def test_analytic_occupancy_mbkr_vs_terapipe():
    """The Fig-1-style imbalance: MBKR's live slot peak is p2 (< m) on every
    stage, terapipe's is m — the cross-half pairing flattens residency."""
    from repro.core import mbkr
    from repro.obs import telemetry as obs_t
    for m, n in ((8, 8), (16, 16)):
        plan = mbkr.plan(m, n)
        own, hosted = obs_t.analytic_occupancy(m, n, plan.p2)
        occ = own + hosted
        assert occ.shape == (n, m + n - 1)
        assert int(occ.max()) == plan.num_slots  # peak == provisioned slots
        assert (occ.max(axis=1) == plan.p2).all()  # every stage, same peak
        own_t, hosted_t = obs_t.analytic_occupancy(m, n, m, mode="terapipe")
        assert (hosted_t == 0).all()  # no hosting without MBKR
        assert int((own_t + hosted_t).max()) == m  # full pool on every stage
        assert occ.max() < (own_t + hosted_t).max()


def test_occupancy_model_record():
    from repro.configs.base import RunConfig, get_smoke_config
    from repro.core import pipeline as pp
    from repro.obs.telemetry import occupancy_model
    cfg = get_smoke_config("qwen3-8b")
    plan = pp.build_plan(cfg, 8, 128, RunConfig(num_chunks=8, num_stages=8))
    om = occupancy_model(plan)
    assert om["stages"] == 8 and om["ticks"] == 15
    assert om["peak_slots"] == om["num_slots"] == plan.num_slots
    assert len(om["table"]) == 8 and len(om["table"][0]) == 15
    assert max(max(row) for row in om["table"]) == plan.num_slots


def test_chunk_stored_bytes_matches_kvlease_accounting():
    """The device-side KV-byte price and the scheduler's lease accounting
    (costmodel.kv_chunk_bytes x kvstore.kv_compress_factor) are the SAME
    number — one chunk is priced identically by both bookkeepers."""
    from repro.configs.base import RunConfig, get_config
    from repro.core import costmodel as cm
    from repro.core import pipeline as pp
    from repro.kvstore import quant as kvq
    from repro.obs.telemetry import chunk_stored_bytes
    cfg = get_config("qwen3-8b")
    n, m, s = 8, 8, 4096
    c = s // m
    for kv_dtype, page_tokens in (("auto", 0), ("int8", 0), ("int8", 128),
                                  ("fp8", 256)):
        run = RunConfig(num_chunks=m, num_stages=n, kv_dtype=kv_dtype,
                        kv_page_tokens=page_tokens)
        plan = pp.build_plan(cfg, n, s, run)
        lps = plan.layers_per_stage
        dev = chunk_stored_bytes(plan, lps, 1, c, cfg.num_kv_heads,
                                 cfg.resolved_head_dim)
        sm = cm.StageModel.build(cfg, n, 1)
        sched = cm.kv_chunk_bytes(sm, c) * kvq.kv_compress_factor(
            plan.codec, model_dtype=cfg.dtype,
            page_tokens=page_tokens or c, head_dim=cfg.resolved_head_dim)
        assert np.isclose(dev, sched, rtol=1e-9), (kv_dtype, dev, sched)


def test_skew_all_empty_key_is_zero():
    """Regression (ISSUE 8 satellite): ``skew`` on an all-empty key — every
    per-stage peak 0, e.g. ``kv_bytes`` on an attention-free run — must
    return 0.0, not divide by zero (it previously returned nan and poisoned
    downstream comparisons)."""
    from repro.obs.telemetry import TelemetryProfile, safe_ratio
    zeros = np.zeros((4, 7))
    prof = TelemetryProfile({"own_chunks": zeros, "hosted_chunks": zeros,
                             "kv_bytes": zeros})
    assert prof.skew("kv_bytes") == 0.0
    assert prof.skew() == 0.0
    # nonzero keys keep the (max - min) / max definition
    kv = np.zeros((4, 7))
    kv[0, :] = 4.0
    kv[1:, :] = 1.0
    prof2 = TelemetryProfile({"own_chunks": zeros, "hosted_chunks": zeros,
                              "kv_bytes": kv})
    assert prof2.skew("kv_bytes") == pytest.approx((4.0 - 1.0) / 4.0)
    # the underlying helper: 0/0 -> 0.0, x/0 -> 0.0, normal division intact
    assert safe_ratio(0.0, 0.0) == 0.0
    assert safe_ratio(3.0, 0.0) == 0.0
    assert safe_ratio(3.0, 4.0) == pytest.approx(0.75)


# ------------------------------------------------ device telemetry (8 chips)

SNIPPET_TELEMETRY = """
import numpy as np, jax
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.core import transport as tx
from repro.models.api import build_model
from repro.models.topology import Topology
from repro.obs import telemetry as obs_t

cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, m, s, b = 8, 8, 128, 2
c = s // m
mesh = compat.make_mesh((n, 1), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
topo = Topology(mesh=mesh)
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)

run = RunConfig(num_chunks=m, num_stages=n, remote_attn="fetch")
plan = pp.build_plan(cfg, n, s, run)
staged = pp.stage_params(cfg, params, plan)
with compat.set_mesh(mesh):
    logits, led, tel = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo, return_ledger=True,
        return_telemetry=True))(staged, toks)
    logits0 = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo))(staged, toks)
led = tx.ledger_to_dict(led)
prof = obs_t.TelemetryProfile.from_run(tel)
assert prof.data["own_chunks"].shape == (n, m + n - 1)

# 1) occupancy == the analytic MBKR residency model, tick by tick
own, hosted = obs_t.analytic_occupancy(m, n, plan.p2)
assert np.allclose(prof.data["own_chunks"], own)
assert np.allclose(prof.data["hosted_chunks"], hosted)
assert prof.peak() == plan.num_slots

# 2) resident KV bytes == occupancy x the quantized chunk price
cb = obs_t.chunk_stored_bytes(plan, plan.layers_per_stage, b, c,
                              cfg.num_kv_heads, cfg.resolved_head_dim)
assert np.allclose(prof.data["kv_bytes"], (own + hosted) * cb)

# 3) event counts x analytic per-event price == the CollectiveLedger
pe = obs_t.per_event_wire_bytes(plan, cfg, b)
tot = prof.totals()
assert tot["spill_events"] == n * (m - plan.p2)
assert np.isclose(tot["spill_events"] * pe["spill"], led["spill"], rtol=1e-5)
assert np.isclose(tot["fetch_events"] * pe["fetch"], led["fetch"], rtol=1e-5)
assert tot["qship_events"] == 0.0 and tot["attn_work"] > 0
assert tot["launches"] > 0

# 4) the disabled path is bit-identical
assert (np.asarray(logits) == np.asarray(logits0)).all()

# 5) terapipe shows the paper's imbalance: full-pool peak m vs MBKR's p2
plan_t = pp.build_plan(cfg, n, s, run, mode="terapipe")
staged_t = pp.stage_params(cfg, params, plan_t)
with compat.set_mesh(mesh):
    _, tel_t = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan_t, topo, return_telemetry=True))(staged_t, toks)
prof_t = obs_t.TelemetryProfile.from_run(tel_t)
own_t, hosted_t = obs_t.analytic_occupancy(m, n, plan_t.p2, mode=plan_t.mode)
assert np.allclose(prof_t.data["own_chunks"], own_t)
assert np.allclose(prof_t.data["hosted_chunks"], hosted_t)
assert prof_t.peak() == m and prof.peak() == plan.p2 < m
print("PASS")
"""


def test_device_telemetry_matches_models():
    """Tentpole acceptance: the per-(stage, tick) device counters reproduce
    the analytic MBKR occupancy, the kvstore byte pricing, the ledger's
    wire categories, AND the MBKR-vs-terapipe imbalance — while the
    telemetry-off path returns bit-identical logits."""
    _run(SNIPPET_TELEMETRY)


SNIPPET_ZERO_COST = """
import re, jax
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.models.api import build_model
from repro.models.topology import Topology

cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, m, s, b = 8, 8, 128, 2
mesh = compat.make_mesh((n, 1), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
topo = Topology(mesh=mesh)
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
plan = pp.build_plan(cfg, n, s, RunConfig(num_chunks=m, num_stages=n))
staged = pp.stage_params(cfg, params, plan)

COLL = re.compile(r"collective-permute|collective_permute|all-reduce|"
                  r"all_reduce|all-gather|all_gather|reduce-scatter|"
                  r"reduce_scatter")
def collectives(telemetry):
    with compat.set_mesh(mesh):
        low = jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, topo,
            return_telemetry=telemetry)).lower(staged, toks)
    return len(COLL.findall(low.as_text()))

off, on = collectives(False), collectives(True)
assert off > 0  # the pipeline itself does communicate
# telemetry is carry-threaded local arithmetic: ZERO extra collectives
assert on == off, (off, on)
print("PASS", off)
"""


def test_telemetry_adds_zero_collectives():
    _run(SNIPPET_ZERO_COST)


# ------------------------------------------------------------- merged trace

def test_trace_recorder_merged_format(tmp_path):
    from repro.obs.trace import TraceRecorder
    rec = TraceRecorder(enabled=True)
    rec.task(rid=1, chunk=0, stage=2, start=0.5, finish=1.0)
    rec.mark(rid=1, kind="arrival", time=0.1)
    rec.span("wave0", pid="engine", tid=0, start=0.0, finish=2.0,
             cat="wave", args={"rids": [1]})
    rec.counter("kv_resident_bytes", pid=2, time=0.5, values={"w0": 42.0})
    rec.process_name("engine", "engine (wall clock)")
    evs = rec.chrome_trace()["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    task = next(e for e in by_ph["X"] if e["cat"] == "chunk")
    assert task["pid"] == 2 and task["tid"] == 1
    assert task["ts"] == 0.5e6 and task["dur"] == 0.5e6  # seconds -> us
    wave = next(e for e in by_ph["X"] if e["cat"] == "wave")
    assert wave["pid"] == "engine" and wave["args"]["rids"] == [1]
    (ctr,) = by_ph["C"]
    assert ctr["name"] == "kv_resident_bytes" and ctr["args"] == {"w0": 42.0}
    names = {e["pid"]: e["args"]["name"] for e in by_ph["M"]}
    assert names["engine"] == "engine (wall clock)"
    assert names[2] == "stage 2"  # default label for int pids
    # disabled recorder records nothing
    off = TraceRecorder(enabled=False)
    off.task(1, 0, 0, 0.0, 1.0)
    off.counter("x", pid=0, time=0.0, values={"v": 1})
    assert off.chrome_trace()["traceEvents"] == []
    # export is atomic: real content, no stray tmp siblings
    out = tmp_path / "nested" / "trace.json"
    path = rec.export(str(out))
    assert json.load(open(path))["traceEvents"]
    assert [p.name for p in out.parent.iterdir()] == ["trace.json"]


def test_sched_trace_shim():
    """sched.trace keeps re-exporting the recorder (old imports work)."""
    from repro.obs import trace as obs_trace
    from repro.sched import trace as sched_trace
    assert sched_trace.TraceRecorder is obs_trace.TraceRecorder
    assert sched_trace.TaskEvent is obs_trace.TaskEvent


def test_engine_merged_trace_sim(tmp_path):
    """One ContinuousEngine run -> ONE trace with scheduler task spans,
    lease/wire counter tracks and process metadata; exports are valid."""
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.runtime.engine import (ContinuousEngine, EngineConfig,
                                      Request, SimExecutor)
    cfg = get_config("llama3-70b")
    ec = EngineConfig(model=cfg, hw=cm.WSC_PAPER, num_stages=8, tp=1,
                      num_chunks=8, max_batch=4, buckets=(8192,),
                      partition="lbcp", sa_iters=4, policy="fcfs", trace=True)
    eng = ContinuousEngine(ec, SimExecutor(cfg, ec.hw))
    for i in range(4):
        eng.submit(Request(rid=i, arrival=0.0, seq_len=8192))
    eng.run_until_drained()
    evs = eng.merged_trace().chrome_trace()["traceEvents"]
    assert any(e["ph"] == "X" and e.get("cat") == "chunk" for e in evs)
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"kv_lease_bytes", "wire_bytes"} <= counters
    # pure: a second build yields the same event count
    assert len(eng.merged_trace().chrome_trace()["traceEvents"]) == len(evs)
    paths = eng.export_obs(trace_out=str(tmp_path / "t.json"),
                           metrics_out=str(tmp_path / "m.prom"))
    assert json.load(open(paths["trace"]))["traceEvents"]
    prom = open(paths["metrics"]).read()
    assert "# TYPE repro_completed counter" in prom
    assert "# TYPE repro_ttft_seconds histogram" in prom


# ------------------------------------------------------------------ metrics

def test_metrics_registry_formats(tmp_path):
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("repro_done", "done").inc(3)
    reg.gauge("repro_depth", "queue depth").set(1.5)
    h = reg.histogram("repro_lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    # idempotent getters; kind conflicts are errors
    assert reg.counter("repro_done") is reg.counter("repro_done")
    with pytest.raises(TypeError):
        reg.gauge("repro_done")
    lines = [json.loads(s) for s in reg.to_jsonl().splitlines()]
    by_name = {r["name"]: r for r in lines}
    assert by_name["repro_done"]["value"] == 3.0
    assert by_name["repro_lat_seconds"]["count"] == 3
    assert by_name["repro_lat_seconds"]["sum"] == pytest.approx(5.55)
    prom = reg.to_prom()
    assert "# TYPE repro_done counter" in prom
    assert 'repro_lat_seconds_bucket{le="0.1"} 1' in prom
    assert 'repro_lat_seconds_bucket{le="1.0"} 2' in prom
    assert 'repro_lat_seconds_bucket{le="+Inf"} 3' in prom  # cumulative
    assert "repro_lat_seconds_count 3" in prom
    # extension picks the format
    jl = reg.export(str(tmp_path / "m.jsonl"))
    pm = reg.export(str(tmp_path / "m.prom"))
    assert json.loads(open(jl).readline())["name"]
    assert open(pm).read().startswith("# HELP")


def test_export_engine_metrics_records(tmp_path):
    from repro.obs.metrics import export_engine_metrics
    from repro.sched.metrics import RequestRecord
    recs = [RequestRecord(rid=0, arrival=0.0, seq_len=8, bucket=8,
                          admit=0.25, finish=1.0),
            RequestRecord(rid=1, arrival=0.0, seq_len=8, bucket=8,
                          rejected=True)]  # inf times must not poison sums
    path = export_engine_metrics(
        str(tmp_path / "m.jsonl"),
        {"completed": 1, "avg_ttft": 1.0, "policy": "fcfs"},
        records=recs, extra={"wall_seconds": 2.0})
    rows = {r["name"]: r for r in map(json.loads, open(path))}
    assert rows["repro_completed"]["kind"] == "counter"
    assert rows["repro_ttft_seconds"]["count"] == 1  # rejected row skipped
    assert rows["repro_ttft_seconds"]["sum"] == pytest.approx(1.0)
    assert rows["repro_queue_wait_seconds"]["sum"] == pytest.approx(0.25)
    assert rows["repro_wall_seconds"]["value"] == 2.0
    assert "repro_policy" not in rows  # non-numeric summary entries skipped


def test_atomic_write(tmp_path):
    from repro.obs._io import atomic_write_text
    out = tmp_path / "a" / "b.txt"
    atomic_write_text(str(out), "one")
    atomic_write_text(str(out), "two")  # atomic replace, not append
    assert out.read_text() == "two"
    assert [p.name for p in out.parent.iterdir()] == ["b.txt"]


# ------------------------------------------------------------ kernel launches

def test_count_launches_nested_and_tagged():
    """Launches come from the traced program: a kernel call inside a jitted
    function inside a scan counts once per iteration, per kernel tag, and
    counting runs nothing."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    q = jnp.zeros((1, 8, 2, 16), jnp.float32)
    k = jnp.zeros((1, 8, 2, 16), jnp.float32)

    def attend(q):
        return ops.chunk_attention(q, k, k)

    assert ops.count_launches(attend, q) == {"count": 1,
                                             "chunk_attention": 1}

    @jax.jit
    def twice(q):
        return attend(attend(q))

    def scanned(q):
        return jax.lax.scan(lambda c, _: (twice(c), None), q, None,
                            length=3)[0]

    lc = ops.count_launches(scanned, q)
    assert lc == {"count": 6, "chunk_attention": 6}
    assert "pool_attention" not in lc  # only tags that actually launch
    assert ops.count_launches(lambda q: q * 2, q) == {"count": 0}


# ----------------------------------------------------------- serve smoke

def test_serve_sim_metrics_smoke(tmp_path):
    """End-to-end exporter path: one sim serve run writes the merged trace
    and a Prometheus textfile via the CLI flags."""
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.prom"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--executor", "sim",
         "--scheduler", "continuous", "--requests", "4",
         "--trace-out", str(trace), "--metrics-out", str(metrics)],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "metrics ->" in r.stdout and "trace ->" in r.stdout
    evs = json.load(open(trace))["traceEvents"]
    assert any(e["ph"] == "C" for e in evs)
    assert "repro_completed 4.0" in open(metrics).read()
