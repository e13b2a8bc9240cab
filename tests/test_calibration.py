"""Closed-loop profiling tests (ISSUE 8): the chunk-cost feature matrix is
an exact linear factorization of the analytic cost, ``obs.calibrate``
recovers a perturbed ground-truth profile from noiseless spans, the
calibrated-profile JSON round-trips bit-identically into ``plan_partition``
/ ``chunk_cost_arrays``, a mid-stream scheduler recalibration never reorders
admitted history, and the health sentinels are
provably free when disarmed (zero extra collectives) and bit-identical when
armed."""
import os
import subprocess
import sys
from dataclasses import replace as dc_replace

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


def _true_hw():
    """The calibration benchmark's ground truth: datasheet rates off by
    -20% gemm, +10% attention, -10% HBM, -5% interconnect."""
    from repro.core import costmodel as cm
    return dc_replace(cm.WSC_PAPER, name="truth",
                      gemm_eff=cm.WSC_PAPER.gemm_eff * 0.8,
                      attn_eff=cm.WSC_PAPER.attn_eff * 1.1,
                      hbm_bw=cm.WSC_PAPER.hbm_bw * 0.9,
                      link_bw=cm.WSC_PAPER.link_bw * 0.95)


def _spans(sm, chunks, mplan, hw, n=16):
    """Noiseless [N, T] spans: chunk ph's cost under ``hw`` at every valid
    (stage, stage + ph)."""
    from repro.core import costmodel as cm
    cost = cm.chunk_cost_features(sm, chunks, cm.WSC_PAPER,
                                  mbkr_plan=mplan) @ cm.profile_theta(hw,
                                                                     sm.tp)
    m = len(chunks)
    tick_s = np.zeros((n, m + n - 1))
    for s in range(n):
        tick_s[s, s:s + m] = cost
    return tick_s


# ------------------------------------------------------- linear factorization

@pytest.mark.parametrize("arch", ["llama3-70b", "mamba2-130m"])
@pytest.mark.parametrize("use_mbkr", [True, False])
def test_chunk_cost_features_exact_identity(arch, use_mbkr):
    """``X @ profile_theta == dur + comm + spill_t + fetch_t`` EXACTLY —
    the linearity the least-squares fit inverts."""
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.core import mbkr
    cfg = get_config(arch)
    for tp in (1, 2):
        sm = cm.StageModel.build(cfg, 16, tp)
        chunks = [1024 + 256 * (i % 3) for i in range(16)]
        mplan = (mbkr.plan(16, 16)
                 if use_mbkr and not cfg.attn_free else None)
        feats = cm.chunk_cost_features(sm, chunks, cm.WSC_PAPER,
                                       mbkr_plan=mplan)
        dur, comm, _, spill_t, fetch_t = cm.chunk_cost_arrays(
            sm, chunks, cm.WSC_PAPER, mbkr_plan=mplan)
        assert feats.shape == (16, len(cm.FEATURE_TERMS))
        np.testing.assert_allclose(
            feats @ cm.profile_theta(cm.WSC_PAPER, tp),
            dur + comm + spill_t + fetch_t, rtol=1e-12)


def test_noiseless_fit_recovers_ground_truth():
    """Spans generated under a perturbed profile the fit never sees:
    nominal MAPE is a real gap (>1%), calibrated MAPE collapses to float
    noise, and the fitted profile reprices chunks like the ground truth."""
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.core import mbkr
    from repro.obs import calibrate as cal
    cfg = get_config("llama3-70b")
    sm = cm.StageModel.build(cfg, 16, 1)
    chunks = [2048] * 16
    mplan = mbkr.plan(16, 16)
    truth = _true_hw()
    fit = cal.fit_profile(sm, chunks, _spans(sm, chunks, mplan, truth),
                          cm.WSC_PAPER, mbkr_plan=mplan)
    assert fit.mape_nominal > 0.01
    assert fit.mape_calibrated < 1e-9
    assert np.abs(fit.residual_s).max() < 1e-9
    assert len(fit.rows) == 16 * 16          # every valid (stage, tick)
    def total(hw):
        dur, comm, _, sp, ft = cm.chunk_cost_arrays(sm, chunks, hw,
                                                    mbkr_plan=mplan)
        return dur + comm + sp + ft
    np.testing.assert_allclose(total(fit.profile), total(truth), rtol=1e-9)


# ----------------------------------------------------- persistence round-trip

def test_calibrated_profile_roundtrip_bit_identical(tmp_path):
    """save -> load -> the SAME HardwareProfile bit-for-bit, and
    ``plan_partition`` fed the JSON path reproduces the in-memory plan
    exactly (chunks AND objective) — json floats round-trip via repr."""
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.core import lbcp, mbkr
    from repro.obs import calibrate as cal
    cfg = get_config("llama3-70b")
    sm = cm.StageModel.build(cfg, 16, 1)
    chunks = [2048] * 16
    mplan = mbkr.plan(16, 16)
    fit = cal.fit_profile(sm, chunks, _spans(sm, chunks, mplan, _true_hw()),
                          cm.WSC_PAPER, mbkr_plan=mplan)
    path = str(tmp_path / "cal.json")
    cal.save_profile(path, fit.profile, fit=fit, meta={"src": "test"})
    loaded, blob = cal.load_profile(path)
    assert loaded == fit.profile             # dataclass eq: every field
    assert cm.resolve_profile(path) == fit.profile
    assert blob["fit"]["feature_terms"] == list(cm.FEATURE_TERMS)
    assert len(blob["fit"]["residuals"]) == len(fit.rows)
    kw = dict(sa_iters=8, sa_rounds=2, seed=3)
    mem = lbcp.plan_partition(cfg, 32768, 16, 16, fit.profile, **kw)
    disk = lbcp.plan_partition(cfg, 32768, 16, 16, path, **kw)
    assert disk.chunks == mem.chunks
    assert disk.dp_objective == mem.dp_objective
    assert disk.t_prefill == mem.t_prefill
    # and the calibrated plan actually differs from the nominal one's cost
    nom = lbcp.plan_partition(cfg, 32768, 16, 16, cm.WSC_PAPER, **kw)
    assert nom.t_prefill != pytest.approx(mem.t_prefill, rel=1e-6)


def test_resolve_profile_names_and_errors(tmp_path):
    from repro.core import costmodel as cm
    assert cm.resolve_profile(cm.WSC_PAPER) is cm.WSC_PAPER
    assert cm.resolve_profile("wsc-gr24") == cm.WSC_PAPER
    with pytest.raises((KeyError, ValueError, FileNotFoundError)):
        cm.resolve_profile("no-such-profile-or-file")


# ------------------------------------------------------- scheduler recalib

def test_scheduler_rebase_keeps_admitted_history():
    """Swapping nominal -> calibrated admission costs mid-stream leaves the
    already-admitted prefix untouched (same rids, same finish times) while
    future requests are priced with the new vectors."""
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.core import mbkr
    from repro.sched.scheduler import (ChunkPlan, ChunkScheduler,
                                       SchedRequest)
    cfg = get_config("llama3-70b")
    sm = cm.StageModel.build(cfg, 16, 1)
    mplan = mbkr.plan(16, 16)

    def plan_for(hw):
        def build(bucket):
            return ChunkPlan.build(bucket, [bucket // 16] * 16, sm, hw,
                                   mbkr_plan=mplan)
        return build

    sched = ChunkScheduler(16, plan_for(cm.WSC_PAPER), policy="sjf")
    for i in range(4):
        sched.submit(SchedRequest(rid=i, arrival=0.0, seq_len=32768,
                                  bucket=32768))
    sched.run()
    before = [(r.rid, r.admit_time, r.finish_time) for r in sched.admitted]
    assert len(before) == 4

    sched.rebase_costs(plan_for(_true_hw()))
    t1 = float(sched.stage_free.max()) + 1.0
    for i in range(4, 8):
        sched.submit(SchedRequest(rid=i, arrival=t1, seq_len=32768,
                                  bucket=32768))
    sched.run()
    after = [(r.rid, r.admit_time, r.finish_time) for r in sched.admitted]
    assert after[:4] == before               # history never reordered
    assert sorted(r[0] for r in after[4:]) == [4, 5, 6, 7]
    # the calibrated (slower-gemm) plan really is costlier per task
    assert (plan_for(_true_hw())(32768).work
            > plan_for(cm.WSC_PAPER)(32768).work)


def test_engine_recalibrate_swaps_costs_in_place():
    """ContinuousEngine.recalibrate(path) resolves the JSON, rebuilds the
    stage model/plan cache and rebases the scheduler — without dropping
    completed requests."""
    from repro.configs.base import get_config
    from repro.core import costmodel as cm
    from repro.obs import calibrate as cal
    from repro.runtime.engine import (ContinuousEngine, EngineConfig,
                                      Request, SimExecutor)
    import tempfile
    cfg = get_config("llama3-70b")
    ec = EngineConfig(model=cfg, hw=cm.WSC_PAPER, num_stages=8, tp=1,
                      num_chunks=8, max_batch=4, buckets=(8192,),
                      partition="lbcp", sa_iters=4)
    eng = ContinuousEngine(ec, SimExecutor(cfg, ec.hw))
    for i in range(2):
        eng.submit(Request(rid=i, arrival=0.0, seq_len=8192))
    eng.run_until_drained()
    done_before = eng.metrics()["completed"]
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "cal.json")
        cal.save_profile(path, _true_hw())
        hw = eng.recalibrate(path)
    assert hw.name == "truth" and eng.ec.hw == hw
    for i in range(2, 4):
        eng.submit(Request(rid=i, arrival=0.0, seq_len=8192))
    eng.run_until_drained()
    assert eng.metrics()["completed"] == done_before + 2


# ------------------------------------------------- health sentinels (8 chips)

SNIPPET_HEALTH = """
import re
import numpy as np, jax
import jax.numpy as jnp
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.models.api import build_model
from repro.models.topology import Topology
from repro.obs.health import HealthMonitor

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
def lowered(monitor):
    with compat.set_mesh(mesh):
        return jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, topo, health=monitor)).lower(staged, toks)

# 1) disarmed (health=None) == the plain pipeline, same HLO text: ZERO
#    extra anything, not merely zero extra collectives
off = lowered(None).as_text()
with compat.set_mesh(mesh):
    base = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo)).lower(staged, toks).as_text()
assert off == base
# 2) armed: the per-stage isfinite reduction is shard-local arithmetic —
#    zero extra collectives even when the sentinel IS traced
mon = HealthMonitor()
on = lowered(mon).as_text()
assert len(COLL.findall(on)) == len(COLL.findall(off)) > 0

# 3) armed on a healthy run: bit-identical logits, zero alerts
with compat.set_mesh(mesh):
    logits0 = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo))(staged, toks)
    logits1 = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo, health=mon))(staged, toks)
    jax.block_until_ready(logits1)
    jax.effects_barrier()
assert (np.asarray(logits0) == np.asarray(logits1)).all()
assert mon.alerts == [], mon.summary()

# 4) poisoned params -> nonfinite alerts with (stage, tick) attribution
bad = jax.tree_util.tree_map(
    lambda a: a * jnp.nan if jnp.issubdtype(a.dtype, jnp.floating) else a,
    staged)
mon2 = HealthMonitor()
with compat.set_mesh(mesh):
    out = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo, health=mon2))(bad, toks)
    jax.block_until_ready(out)
    jax.effects_barrier()
assert mon2.alerts, "NaN run fired no sentinel"
kinds = {a.kind for a in mon2.alerts}
assert kinds == {"nonfinite"}
assert all(a.severity == "crit" and a.stage is not None and
           a.tick is not None for a in mon2.alerts)
assert mon2.counts()["nonfinite"] == len(mon2.alerts)
print("PASS")
"""


def test_health_sentinels_zero_cost_and_nan_detection():
    """Tentpole acceptance (health leg): disarmed sentinels leave the HLO
    byte-identical; armed ones add zero collectives, keep logits
    bit-identical, stay silent on healthy runs, and catch NaN poisoning
    with per-(stage, tick) attribution."""
    _run(SNIPPET_HEALTH)


# ------------------------------------------------------ host-side sentinels

def test_health_drift_and_slo_sentinels():
    from repro.obs.health import HealthMonitor, slo_burn_rate
    from repro.obs.metrics import Histogram, MetricsRegistry
    from repro.obs.trace import TraceRecorder
    mon = HealthMonitor(ledger_threshold=0.01, burn_threshold=1.0)
    # ledger drift: 10% off the analytic model trips, 0.1% does not
    worst = mon.check_ledger({"ring": 1.10e9, "fetch": 1.000e8},
                             {"ring": 1.00e9, "fetch": 1.001e8})
    assert worst == pytest.approx(0.10)
    assert [a.kind for a in mon.alerts] == ["ledger_drift"]
    # SLO burn: 5 of 10 beyond a 1.0s SLO at target 99% -> burn 50x
    h = Histogram("ttft", buckets=(0.5, 1.0, 2.0))
    for v in (0.1,) * 5 + (1.5,) * 5:
        h.observe(v)
    assert slo_burn_rate(h, 1.0, target=0.99) == pytest.approx(50.0)
    burn = mon.check_slo(h, 1.0)
    assert burn == pytest.approx(50.0)
    assert mon.counts()["slo_burn"] == 1
    # empty histogram burns nothing
    assert slo_burn_rate(Histogram("x"), 1.0) == 0.0
    # exports: per-kind counters + burn gauge; one trace row per alert
    reg = MetricsRegistry()
    mon.to_metrics(reg)
    rows = {m.name: m for m in reg.metrics()}
    assert rows["repro_health_alerts_total"].value == 2
    assert rows["repro_health_ledger_drift_total"].value == 1
    assert rows["repro_health_slo_burn_rate"].value == pytest.approx(50.0)
    rec = TraceRecorder(enabled=True)
    mon.to_trace(rec)
    evs = rec.chrome_trace()["traceEvents"]
    alerts = [e for e in evs if e.get("cat") == "alert"]
    assert len(alerts) == 2 and all(e["pid"] == "health" for e in alerts)
    assert any(e["args"]["name"] == "health sentinels"
               for e in evs if e["ph"] == "M")


def test_health_occupancy_drift_sentinel():
    """A telemetry profile matching the analytic twin stays silent; a
    corrupted one trips occupancy_drift."""
    from repro.core import mbkr
    from repro.obs import telemetry as obs_t
    from repro.obs.health import HealthMonitor

    class FakePlan:
        num_chunks, num_stages = 8, 8
        p2 = mbkr.plan(8, 8).p2
        mode = "mocap"

    own, hosted = obs_t.analytic_occupancy(8, 8, FakePlan.p2)
    zeros = np.zeros_like(own)
    good = obs_t.TelemetryProfile({"own_chunks": own,
                                   "hosted_chunks": hosted})
    mon = HealthMonitor()
    assert mon.check_occupancy(good, FakePlan) == 0.0
    assert mon.alerts == []
    bad = obs_t.TelemetryProfile({"own_chunks": own * 2,
                                  "hosted_chunks": hosted})
    drift = mon.check_occupancy(bad, FakePlan)
    assert drift > mon.occupancy_threshold
    assert [a.kind for a in mon.alerts] == ["occupancy_drift"]
