"""Fleet-layer tests (ISSUE 9): the multi-cell serving fabric behind the
narrow ``CellHandle`` protocol.

- the lease/cost-aware router (jsf) strictly beats round-robin on p99 TTFT
  over a heterogeneous hot/cold cell pair at equal offered load,
- drain semantics: a draining cell admits ZERO new requests but completes
  everything in flight; the fabric retires it from routing,
- heterogeneous kv_dtype cells price their KV leases independently,
- the 2-cell sim end-to-end: shared arrival stream, fleet roll-up metrics,
  ONE merged trace with per-cell process rows, elastic resize,
- protocol hygiene: serve.py and repro/fleet touch engines ONLY through
  ``CellHandle`` (source scan, same idiom as the PR 5 transport grep),
- ServeOptions: JSON round-trip, explicit-flags-as-overrides, fleet spec,
- the deprecated ContinuousEngine kwargs still work and warn.

Everything here is sim-executor / stdlib-only: no jax device state, no new
skip classes (tests/check_skips.py stays exact on both jaxlib legs).
"""
import json
import math
import os
import re
import subprocess
import sys
import warnings
from dataclasses import replace as dc_replace

import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import costmodel as cm
from repro.fleet import (CellSignals, FleetFabric, FleetRouter,
                         PlacementDecision, score_cells)
from repro.runtime.engine import (CellHandle, ContinuousEngine, EngineConfig,
                                  Request, SimExecutor)
from repro.sched import fleet_summary, poisson_arrivals
from repro.sched.metrics import RequestRecord

ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = get_config("llama3-70b")

SLOW_HW = dc_replace(cm.WSC_PAPER, name="wsc-degraded",
                     gemm_eff=cm.WSC_PAPER.gemm_eff * 0.55,
                     attn_eff=cm.WSC_PAPER.attn_eff * 0.55)


def _cell(hw=cm.WSC_PAPER, *, kv_dtype="auto", trace=False, slo=None,
          buckets=(32768,), inflight=2):
    ec = EngineConfig(model=CFG, hw=hw, num_stages=16, tp=1, num_chunks=16,
                      buckets=buckets, partition="uniform", sa_iters=8,
                      kv_dtype=kv_dtype, trace=trace, slo=slo,
                      inflight=inflight)
    return ContinuousEngine(ec, SimExecutor(CFG, hw))


def _pair(policy, *, trace=False):
    return FleetFabric({"fast": _cell(trace=trace),
                        "slow": _cell(SLOW_HW, trace=trace)},
                       FleetRouter(policy))


def _drive(fab, n=24, rate=6.0, seq=30000, seed=0):
    for i, t in enumerate(poisson_arrivals(rate, n, seed=seed)):
        fab.submit(Request(rid=i, arrival=float(t), seq_len=seq))
    fab.pump()
    return fab.metrics()


# ------------------------------------------------------------ protocol seam

def test_continuous_engine_is_a_cell_handle():
    eng = _cell()
    assert isinstance(eng, CellHandle)


def test_estimate_admission_matches_realized_finish():
    """The jsf signal is honest: an empty cell's quoted ETA for a request
    IS the finish time the scheduler then realizes for it."""
    eng = _cell()
    eta, fits = eng.estimate_admission(30000, arrival=0.0)
    assert fits
    eng.submit(Request(rid=0, arrival=0.0, seq_len=30000))
    eng.run_until_drained()
    [done] = eng.poll()
    assert done.finish_time == pytest.approx(eta, rel=1e-9)


def test_protocol_only_access_source_scan():
    """serve.py and the whole fleet package must consume engines through
    the CellHandle protocol: no scheduler/lease/executor internals, no
    poking executor observability flags, no reading .done/.waves directly
    (the PR 5 transport-grep idiom applied to the engine seam)."""
    forbidden = re.compile(
        r"\.scheduler\.|\.lease\.|\.collect_telemetry"
        r"|\.stage_free|\.metrics\.records|\bexecutor\.[a-z_]+\s*="
        r"|eng\.done\b|cell\.done\b|\.executor\.")
    files = [os.path.join(ROOT, "src", "repro", "launch", "serve.py")]
    fleet_dir = os.path.join(ROOT, "src", "repro", "fleet")
    files += [os.path.join(fleet_dir, f) for f in sorted(os.listdir(fleet_dir))
              if f.endswith(".py")]
    for path in files:
        src = open(path).read()
        hits = [(i + 1, line) for i, line in enumerate(src.splitlines())
                if forbidden.search(line)]
        assert not hits, f"engine internals poked in {path}: {hits}"


def test_legacy_engine_kwargs_deprecated_but_work():
    ec = _cell().ec
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng = ContinuousEngine(ec, SimExecutor(CFG, ec.hw), policy="edf",
                               slo=2.0, inflight=3, trace=True)
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    # the kwargs landed on the config
    assert eng.ec.policy == "edf" and eng.ec.slo == 2.0
    assert eng.ec.inflight == 3 and eng.ec.trace is True
    eng.submit(Request(rid=0, arrival=0.0, seq_len=30000))
    eng.run_until_drained()
    assert eng.metrics()["completed"] == 1
    # config-only construction warns nothing
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ContinuousEngine(dc_replace(ec, policy="edf"),
                         SimExecutor(CFG, ec.hw))
    assert not [w for w in rec
                if issubclass(w.category, DeprecationWarning)]


# ------------------------------------------------------------------ routing

def test_jsf_beats_round_robin_on_hot_cold_pair():
    """Equal offered load, one fast + one degraded cell: the lease/cost-
    aware router must strictly beat round-robin on p99 TTFT (the ISSUE 9
    acceptance criterion; same construction as the gated bench row)."""
    m_jsf = _drive(_pair("jsf"))
    m_rr = _drive(_pair("rr"))
    assert m_jsf["completed"] == m_rr["completed"] == 24
    assert m_jsf["p99_ttft"] < m_rr["p99_ttft"], (
        f"jsf {m_jsf['p99_ttft']:.3f}s vs rr {m_rr['p99_ttft']:.3f}s")
    # jsf steers the bulk of the stream at the fast cell
    assert (m_jsf["per_cell"]["fast"]["completed"]
            > m_jsf["per_cell"]["slow"]["completed"])


def test_least_loaded_routes_by_queue_depth():
    m = _drive(_pair("least-loaded"))
    assert m["completed"] == 24
    assert all(pc["completed"] > 0 for pc in m["per_cell"].values())


def test_router_rejects_unknown_policy():
    with pytest.raises(ValueError):
        FleetRouter("fifo")
    with pytest.raises(ValueError):
        score_cells("rr", [])


def test_placement_decisions_record_all_candidates():
    fab = _pair("jsf")
    fab.submit(Request(rid=0, arrival=0.0, seq_len=30000))
    [dec] = fab.router.decisions
    assert isinstance(dec, PlacementDecision)
    assert {s.name for s in dec.signals} == {"fast", "slow"}
    assert math.isfinite(dec.eta)


# -------------------------------------------------------------------- drain

def test_drain_admits_zero_completes_inflight():
    """drain() on a cell: everything already submitted finishes; any later
    submit raises; the fabric retires it and routes around it."""
    fab = _pair("jsf")
    for i in range(6):
        fab.submit(Request(rid=i, arrival=0.1 * i, seq_len=30000),
                   pump=False)
    placed_fast = [r for r, c in fab.placements.items() if c == "fast"]
    done = fab.drain_cell("fast")
    assert sorted(r.rid for r in done) == sorted(placed_fast)
    assert all(r.state == "done" and math.isfinite(r.finish_time)
               for r in done)
    with pytest.raises(RuntimeError):
        fab.retired["fast"].submit(Request(rid=99, arrival=9., seq_len=100))
    # routing continues on the surviving cell only
    dec = fab.submit(Request(rid=50, arrival=1.0, seq_len=30000))
    assert dec.cell == "slow"
    fab.pump()
    assert fab.metrics()["completed"] == 7


def test_all_cells_draining_closes_admission():
    fab = _pair("jsf")
    fab.drain_all()
    with pytest.raises(RuntimeError):
        fab.submit(Request(rid=0, arrival=0.0, seq_len=100))


# -------------------------------------------------------- heterogeneous kv

def test_heterogeneous_kv_dtype_cells_price_leases_independently():
    """An int8 cell's lease for the SAME request costs ~half the bytes of
    the bf16 cell's (stored-byte accounting is per-cell state)."""
    peaks = {}
    for kd in ("auto", "int8"):
        cell = _cell(kv_dtype=kd)
        base = cell.free_lease_bytes()
        cell.submit(Request(rid=0, arrival=0.0, seq_len=32768))
        cell.run_until_drained()
        peaks[kd] = base - float(cell.lease.headroom(after=0.0).min())
    assert peaks["auto"] > 0
    ratio = peaks["int8"] / peaks["auto"]
    assert 0.45 < ratio < 0.60, ratio


# ------------------------------------------------------------------- e2e

def test_two_cell_e2e_metrics_trace_and_resize():
    """2-cell sim fleet end-to-end: every request of the shared stream
    completes exactly once, the fleet summary reconciles with per-cell
    counts, the merged trace shows BOTH cells' process rows, and resize()
    grows/drains the fleet mid-stream."""
    fab = _pair("jsf", trace=True)
    _drive(fab, n=16)
    m = fab.metrics()
    assert m["completed"] == 16 and m["rejected"] == 0
    assert sum(pc["completed"] for pc in m["per_cell"].values()) == 16
    evs = fab.merged_trace().chrome_trace()["traceEvents"]
    pids = {str(e["pid"]) for e in evs}
    assert any(p.startswith("fast/stage") for p in pids)
    assert any(p.startswith("slow/stage") for p in pids)
    assert any(p == "fast/requests" for p in pids)
    # elastic resize: fast+slow -> fast+extra (slow drains, extra joins)
    fab.resize(["fast", "extra"], factory=lambda name: _cell(trace=True))
    assert set(fab.cells) == {"fast", "extra"}
    assert "slow" in fab.retired
    for i in range(16, 24):
        fab.submit(Request(rid=i, arrival=3.0 + 0.1 * i, seq_len=30000))
    fab.pump()
    m2 = fab.metrics()
    assert m2["completed"] == 24 and m2["cells"] == 3
    # retired cells keep their history in the roll-up
    assert m2["per_cell"]["slow"]["completed"] == m["per_cell"]["slow"]["completed"]


def test_fleet_summary_merges_records():
    recs = {
        "a": [RequestRecord(rid=0, arrival=0.0, seq_len=10, bucket=16,
                            admit=0.0, finish=1.0, deadline=2.0)],
        "b": [RequestRecord(rid=1, arrival=0.0, seq_len=10, bucket=16,
                            admit=0.5, finish=4.0, deadline=2.0),
              RequestRecord(rid=2, arrival=1.0, seq_len=10, bucket=16,
                            rejected=True)],
    }
    s = fleet_summary(recs)
    assert s["cells"] == 2 and s["completed"] == 2 and s["rejected"] == 1
    assert s["makespan"] == 4.0
    assert s["throughput"] == pytest.approx(0.5)
    assert s["slo_total"] == 2 and s["slo_met"] == 1
    assert s["per_cell"]["b"]["rejected"] == 1


# ------------------------------------------------------------ serve options

def test_serve_options_json_round_trip():
    from repro.launch.options import ServeOptions
    opts = ServeOptions(arch="llama3-70b", executor="sim", cells=3,
                        router="least-loaded", buckets=(8192, 32768),
                        slo_ms=750.0, scheduler="continuous")
    back = ServeOptions.from_json(opts.to_json())
    assert back == opts
    assert back.buckets == (8192, 32768)
    with pytest.raises(ValueError):
        ServeOptions.from_dict({"archh": "typo"})


def test_serve_options_cli_flags_are_overrides():
    """SUPPRESS-default parser: only explicitly typed flags override the
    --options-in base; everything else survives untouched."""
    import argparse
    from repro.launch.options import (ServeOptions, add_serve_args,
                                      options_from_args)
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    base = ServeOptions(executor="sim", requests=40, seq=30000,
                        scheduler="continuous")
    ns = ap.parse_args(["--requests", "8", "--router", "rr"])
    opts = options_from_args(ns, base)
    assert opts.requests == 8 and opts.router == "rr"      # overridden
    assert opts.executor == "sim" and opts.seq == 30000    # inherited
    assert opts.scheduler == "continuous"


def test_fleet_spec_per_cell_overrides(tmp_path):
    from repro.launch.options import ServeOptions, resolve_fleet
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({
        "router": "least-loaded",
        "cells": [{"kv_dtype": "int8"}, {"buckets": [8192]}],
    }))
    base = ServeOptions(executor="sim", fleet_spec=str(spec))
    router, cells = resolve_fleet(base)
    assert router == "least-loaded"
    assert len(cells) == 2
    assert cells[0].kv_dtype == "int8" and cells[0].buckets is None
    assert cells[1].buckets == (8192,) and cells[1].kv_dtype == "auto"
    # --cells N replication path
    router2, cells2 = resolve_fleet(ServeOptions(cells=3, router="rr"))
    assert router2 == "rr" and len(cells2) == 3


def test_serve_fleet_subprocess_smoke(tmp_path):
    """The CLI fleet path end-to-end: 2 sim cells, jsf router, merged
    multi-cell trace + fleet metrics JSON on disk."""
    trace = tmp_path / "fleet_trace.json"
    metrics = tmp_path / "fleet_metrics.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--executor", "sim",
         "--scheduler", "continuous", "--cells", "2", "--router", "jsf",
         "--requests", "8", "--seq", "30000", "--arrival-rate", "6",
         "--trace-out", str(trace), "--metrics-out", str(metrics)],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "[fleet jsf x2]" in r.stdout
    assert "trace ->" in r.stdout and "metrics ->" in r.stdout
    m = json.load(open(metrics))
    assert m["completed"] == 8 and m["cells"] == 2
    pids = {str(e["pid"]) for e in json.load(open(trace))["traceEvents"]}
    assert any(p.startswith("cell0/") for p in pids)
    assert any(p.startswith("cell1/") for p in pids)
