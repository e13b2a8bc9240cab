"""Spans, counters and scopes inside the served prefill path.

- A tiny ``PrefillEngine`` over ``JaxExecutor`` (one CPU device): the five
  phase spans nest inside each wave's ``engine.step`` and add up to no more
  than it, request timestamps are ordered, the wave record carries the
  phase durations, and a new bucket counts as one compiling wave while a
  repeat counts none.
- The engine readers of the benchmark (``bench/metrics/engine.*``) on that
  run, and every new reader on waves and a trace from a system without the
  instrumentation (they find nothing and return None).
- ``JaxExecutor.op_scopes()`` on a four-stage pipeline over four virtual
  CPU devices names its ring and pair collectives by transport scope.
- The tick segmentation and the scope reads of ``bench/scopes.py`` on a
  trace recorded on four TPU v5e chips with its scope map.
"""
import dataclasses
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import scopes  # noqa: E402
import tracefile  # noqa: E402

PHASES = ("engine.admit", "engine.prepare", "engine.dispatch",
          "engine.device_wait", "engine.fetch")
RECORDED = os.path.join(BENCH, "testdata", "pipeline4.xplane.pb.gz")
RECORDED_SCOPES = os.path.join(BENCH, "testdata", "pipeline4.scopes.json")
NEW_READERS = ("engine.host_frac", "engine.queue_wait_mean_s",
               "engine.window_compiles", "attn.glue_frac",
               "pipeline.bubble_frac", "pipeline.tick_imbalance_frac")


# ------------------------------------------------------------ tiny engine

@pytest.fixture(scope="module")
def served():
    """Warm-up on bucket 64, then the 'window': 64 (repeat), 128 (new),
    128 (repeat), 64 (repeat)."""
    import jax
    from jax.sharding import Mesh
    from repro.configs.base import RunConfig, get_smoke_config
    from repro.core import costmodel as cm
    from repro.core import pipeline as pp
    from repro.models.api import build_model
    from repro.models.topology import Topology
    from repro.runtime.engine import (EngineConfig, JaxExecutor,
                                      PrefillEngine, Request)
    cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype="float32")
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    run = RunConfig(num_chunks=4, num_stages=1)
    plan = pp.build_plan(cfg, 1, 128, run)
    staged = pp.stage_params(cfg, build_model(cfg).init(jax.random.key(0)),
                             plan)
    ec = EngineConfig(model=cfg, hw=cm.TPU_V5E, num_stages=1, tp=1,
                      num_chunks=4, max_batch=1, buckets=(64, 128),
                      partition="uniform")
    eng = PrefillEngine(ec, JaxExecutor(cfg, staged, Topology(mesh=mesh),
                                        run))
    rng = np.random.default_rng(0)

    def send(rid, n):
        eng.submit(Request(rid=rid, arrival=float(rid), seq_len=n,
                           tokens=rng.integers(0, cfg.vocab_size, n)
                           .astype(np.int32)))

    send(-1, 64)
    eng.run_until_drained()
    n_warm = len(eng.waves())
    for rid, n in enumerate((64, 128, 128, 64)):
        send(rid, n)
    eng.run_until_drained()
    return eng, n_warm


def _spans(eng, wave, name=None):
    return [s for s in eng.executor.spans.spans
            if s[3].get("wave") == wave and (name is None or s[0] == name)]


def test_phase_spans_nest_inside_each_wave(served):
    eng, _ = served
    for wi, w in enumerate(eng.waves()):
        (step,) = _spans(eng, wi, "engine.step")
        (wave,) = [s for s in _spans(eng, wi)
                   if s[0] == f"prefill_wave seq{w['seq']} b1"]
        phases = [s for s in _spans(eng, wi) if s[0] in PHASES]
        assert sorted(s[0] for s in phases) == sorted(PHASES)
        for _, t0, t1, _ in phases + [wave]:
            assert step[1] <= t0 <= t1 <= step[2]
        for name, t0, t1, _ in phases:
            if name != "engine.admit":   # admission picks S and B first
                assert wave[1] <= t0 <= t1 <= wave[2]
        assert sum(t1 - t0 for _, t0, t1, _ in phases) <= step[2] - step[1]
        # the wave record carries the same durations
        rec = {"engine." + k: v for k, v in w["phases"].items()}
        for name, t0, t1, _ in phases:
            assert rec[name] == pytest.approx(t1 - t0)
        assert w["step"] == pytest.approx(step[2] - step[1])
        # the wave's timed service lies inside its span
        assert w["dur"] <= wave[2] - wave[1]


def test_request_timestamps_are_ordered(served):
    eng, _ = served
    assert len(eng.done) == 5
    for r in eng.done:
        assert r.t_submit <= r.t_admit <= r.t_done
    for w in eng.waves():
        assert len(w["t_admit"]) == len(w["rids"])


def test_new_bucket_compiles_once_repeat_never(served):
    eng, n_warm = served
    window = eng.waves()[n_warm:]
    assert [w["seq"] for w in window] == [64, 128, 128, 64]
    assert [w["jit_miss"] for w in window] == [False, True, False, False]
    assert [w["compiled"] for w in window] == [False, True, False, False]
    assert window[1]["compile_events"]["traces"] > 0
    miss = _spans(eng, n_warm + 1, "engine.compile")
    assert len(miss) == 1
    assert not _spans(eng, n_warm, "engine.compile")
    view = types.SimpleNamespace(waves=window)
    assert harness.metric_reader("engine.window_compiles")(view) == 1
    view = types.SimpleNamespace(waves=window[2:])
    assert harness.metric_reader("engine.window_compiles")(view) == 0


def test_engine_readers_on_the_run(served):
    eng, n_warm = served
    window = eng.waves()[n_warm:]
    t0 = min(r.t_submit for r in eng.done if r.rid >= 0)
    t1 = max(r.t_done for r in eng.done)
    # each request was due 0.25 s before it was submitted (an arrival
    # the harness could submit only once the running step returned)
    late = 0.25
    view = types.SimpleNamespace(
        waves=window, window_s=t1 - t0,
        requests=[{"rid": r.rid, "due": r.t_submit - late}
                  for r in eng.done if r.rid >= 0])
    host = harness.metric_reader("engine.host_frac")(view)
    assert 0 < host < 1
    # everything submitted at once and served one at a time: each waits
    # through the waves before it, and the wait counts from when it was due
    wait = harness.metric_reader("engine.queue_wait_mean_s")(view)
    own = [r.t_admit - r.t_submit for r in eng.done if r.rid >= 0]
    assert wait == pytest.approx(np.mean(own) + late)
    assert wait > late


def test_metrics_report_measured_ttft(served):
    eng, _ = served
    m = eng.metrics()
    ttft = [r.t_done - r.t_submit for r in eng.done]
    assert m["completed"] == 5
    assert m["avg_ttft"] == pytest.approx(np.mean(ttft))
    assert m["p99_ttft"] == pytest.approx(np.percentile(ttft, 99))
    assert m["avg_queue_wait"] == pytest.approx(
        np.mean([r.t_admit - r.t_submit for r in eng.done]))
    assert m["makespan"] == pytest.approx(sum(w["dur"] for w in eng.waves()))


def test_batch_engine_trace_export(served, tmp_path):
    eng, _ = served
    paths = eng.export_obs(trace_out=str(tmp_path / "t.json"),
                           metrics_out=str(tmp_path / "m.jsonl"))
    ev = json.load(open(paths["trace"]))["traceEvents"]
    names = {e["name"] for e in ev if e.get("ph") == "X"}
    assert set(PHASES) | {"engine.step"} <= names
    assert {f"r{r.rid}" for r in eng.done} <= names
    lines = open(paths["metrics"]).read()
    assert "ttft_seconds" in lines and "queue_wait_seconds" in lines


def test_span_log_is_bounded():
    from repro.obs.trace import SpanLog
    log = SpanLog(maxlen=3)
    for i in range(5):
        with log.span("engine.fetch", wave=i) as s:
            pass
        assert s.dur >= 0
    assert [ids["wave"] for _, _, _, ids in log.spans] == [2, 3, 4]


def test_process_events_listen_once():
    import gc
    import jax
    from repro.obs import trace as obs_trace
    pe = obs_trace.process_events()
    assert obs_trace.process_events() is pe
    n = sum(1 for cb in gc.callbacks if cb == pe._on_gc)
    assert n == 1
    before = pe.counts()
    jax.jit(lambda x: x * 3 + 1)(np.float32(2.0))
    after = pe.counts()
    assert after["traces"] > before["traces"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_without_instrumentation(name):
    """Waves and a trace of a system built before the spans and scopes:
    every new reader returns None (and does not raise)."""
    tr = tracefile.Trace.load(os.path.join(BENCH, "testdata",
                                           "pipeline4.xplane.pb.gz"))
    old_wave = {"start": 0.0, "dur": 2.7, "seq": 32768, "num_ticks": 19,
                "num_stages": 4, "chunks": [2048] * 16, "rids": [0],
                "prefix_chunks": 0}
    view = types.SimpleNamespace(
        trace=tr, waves=[old_wave], requests=[{"rid": 0, "seq": 32768}],
        window_s=tr.window_s, t0=0.0, chips=4, config={}, traffic={},
        peak=None, flops=None)
    assert harness.metric_reader(name)(view) is None


# ------------------------------------------------------ device scope map

def test_hlo_op_scopes_innermost():
    from repro.obs.trace import hlo_op_scopes
    text = "\n".join([
        '  %fusion.3 = f32[2] fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(f)/while/body/layer.mlp/dot_general" '
        'source_file="x.py" source_line=3}',
        '  %collective-permute-start.2 = (f32[2], f32[2]) '
        'collective-permute-start(%a), metadata={op_name="jit(f)/'
        'layer.attn_pool/transport.pair_shift/qship_state/ppermute"}',
        '  ROOT %chunk_attention.8 = f32[2] custom-call(%q), '
        'metadata={op_name="jit(f)/layer.attn_self/jit(chunk_attention)/'
        'chunk_attention/pallas_call"}',
        '  %copy-start = f32[2] copy-start(%x)',
        '  %add.1 = f32[2] add(%x, %y), metadata={op_name="jit(f)/add"}',
    ])
    assert hlo_op_scopes(text) == {
        "fusion.3": "layer.mlp",
        "collective-permute-start.2": "transport.pair_shift",
        "chunk_attention.8": "layer.attn_self"}


SNIPPET_OP_SCOPES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, re
import numpy as np, jax
from jax.sharding import Mesh
from repro.configs.base import RunConfig, get_smoke_config
from repro.core import costmodel as cm
from repro.core import pipeline as pp
from repro.models.api import build_model
from repro.models.topology import Topology
from repro.obs import trace as obs_trace
from repro.runtime.engine import EngineConfig, JaxExecutor, PrefillEngine, Request

cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, m, s = 4, 8, 128
mesh = Mesh(np.asarray(jax.devices()).reshape(n, 1), ("data", "model"))
run = RunConfig(num_chunks=m, num_stages=n)
plan = pp.build_plan(cfg, n, s, run)
assert plan.p2 < m and plan.remote_attn == "qship", (plan.p2, plan.remote_attn)
staged = pp.stage_params(cfg, build_model(cfg).init(jax.random.key(0)), plan)
ec = EngineConfig(model=cfg, hw=cm.TPU_V5E, num_stages=n, tp=1, num_chunks=m,
                  max_batch=1, buckets=(s,), partition="uniform")
ex = JaxExecutor(cfg, staged, Topology(mesh=mesh), run)
eng = PrefillEngine(ec, ex)
eng.submit(Request(rid=0, arrival=0.0, seq_len=s,
                   tokens=np.arange(s, dtype=np.int32) % cfg.vocab_size))
eng.run_until_drained()
compiles = obs_trace.process_events().counts()["compiles"]
(scopes,) = ex.op_scopes().values()
# compiled afresh once for the map, then kept
assert obs_trace.process_events().counts()["compiles"] == compiles + 1
assert ex.op_scopes() == {k: scopes for k in ex._programs}
assert obs_trace.process_events().counts()["compiles"] == compiles + 1
(program,) = ex._programs.values()
assert program.stage_devices == [[0], [1], [2], [3]]
coll = {k: v for k, v in scopes.items()
        if re.match(r"(ppermute|collective-permute)", k)}
kinds = sorted(set(coll.values()))
assert kinds == ["transport.pair_shift", "transport.ring_shift"], coll
assert sum(v == "transport.ring_shift" for v in coll.values()) >= 1
assert set(scopes.values()) <= set(obs_trace.SCOPES)
for need in ("layer.attn_proj", "layer.attn_self", "layer.attn_pool",
             "layer.kv_write", "layer.mlp", "stage.embed", "stage.head"):
    assert need in scopes.values(), need
print("PASS", sorted(coll.items()))
"""


SNIPPET_STALE_CACHE = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache as cc
from repro.obs.trace import fresh_compiled_text
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
cc.reset_cache()


def program(scope):
    def f(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 2 + 1
    return jax.jit(f)


def named(text):
    return set(re.findall(r'op_name="[^"]*?(layer\.\w+)', text))


x = jnp.ones((8,))
program("layer.mlp")(x).block_until_ready()       # written to the cache
served = program("layer.attn_proj")
served(x).block_until_ready()                     # same key: loaded
assert named(served.lower(x).compile().as_text()) == {"layer.mlp"}
assert named(fresh_compiled_text(served.lower(x))) == {"layer.attn_proj"}
# the persistent cache is in use again afterwards
again = program("layer.kv_write")
again(x).block_until_ready()
assert named(again.lower(x).compile().as_text()) == {"layer.mlp"}
print("PASS")
"""


def test_fresh_compiled_text_ignores_stale_cache_entry(tmp_path):
    """The persistent cache keys a program without its scope names: a
    cached entry of the same program under other names answers both
    ``jit`` and ``Lowered.compile()``; the scope map's compile does not."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", SNIPPET_STALE_CACHE,
                        str(tmp_path)], capture_output=True, text=True,
                       env=env, timeout=300)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "PASS" in r.stdout, r.stdout


def test_op_scopes_name_ring_and_pair_collectives():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", SNIPPET_OP_SCOPES],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "PASS" in r.stdout, r.stdout


# -------------------------------------------- recorded four-chip trace

class _RecordedProgram:
    """The scope map and geometry a ``runtime.engine.Program`` gave for
    the recorded wave, as written beside the trace."""

    def __init__(self, d):
        self.stage_devices = d["stage_devices"]
        self.num_ticks = d["num_ticks"]
        self.num_chunks = d["num_chunks"]
        self._scopes = d["op_scopes"]

    def op_scopes(self):
        return self._scopes


@pytest.fixture(scope="module")
def recorded():
    """One 32768-token request through the four-stage pipeline, traced on
    four v5e chips (``qwen3-8b-pp4.docs32k``), with the scope map of the
    same program compiled for a described v5e:2x2 (``hlo_op_scopes`` of
    its text: the trace's op names are the text's instruction names)."""
    with open(RECORDED_SCOPES) as f:
        meta = json.load(f)
    programs = {k: _RecordedProgram(v) for k, v in meta["programs"].items()}
    waves = []
    for w in meta["waves"]:
        w = dict(w, program=programs[f"seq{w['seq']} b{len(w['rids'])}"])
        w.setdefault("num_ticks", w["program"].num_ticks)
        waves.append(w)
    tr = tracefile.Trace.load(RECORDED)
    return types.SimpleNamespace(trace=tr, waves=waves, window_s=tr.window_s)


def test_recorded_ticks_are_num_ticks_per_chip(recorded):
    ticks = scopes.tick_compute(recorded)
    assert len(ticks) == len(recorded.waves) == 1
    w, per_chip = ticks[0]
    assert len(per_chip) == 4
    assert sorted(stage for stage, _ in per_chip.values()) == [0, 1, 2, 3]
    for stage, c in per_chip.values():
        assert len(c) == w["num_ticks"] == 19
        assert all(ct > 0 for ct in c)
    # one ring shift fewer and the count no longer matches: no reading
    plane = sorted(recorded.trace.devices)[0]
    scope_map = w["program"].op_scopes()
    ring = {n for n, s in scope_map.items() if s == scopes.RING}
    evs = recorded.trace.devices[plane]
    last = max(i for i, (n, _, _) in enumerate(evs) if n in ring)
    cut = types.SimpleNamespace(
        waves=recorded.waves, window_s=recorded.window_s,
        trace=tracefile.Trace(
            dict(recorded.trace.devices,
                 **{plane: evs[:last] + evs[last + 1:]}),
            recorded.trace.host))
    assert scopes.tick_compute(cut) is None
    assert harness.metric_reader("pipeline.bubble_frac")(cut) is None


def test_recorded_scopes_cover_busy_time(recorded):
    busy = recorded.trace.busy_s()
    by_scope = scopes.scope_seconds(recorded)
    for plane, secs in by_scope.items():
        named = sum(v for k, v in secs.items()
                    if k not in (scopes.UNSCOPED, scopes.OUTSIDE))
        assert named >= 0.9 * busy[plane], (plane, secs)


def test_recorded_exposed_collectives_split_by_transport_scope(recorded):
    total = recorded.trace.exposed_collective_s()
    split = scopes.exposed_collective_by_scope(recorded)
    for plane, parts in split.items():
        assert set(parts) <= {"transport.ring_shift", "transport.pair_shift",
                              "transport.stage_psum"}
        assert sum(parts.values()) >= total[plane] - 1e-9
        assert sum(parts.values()) <= total[plane] * 1.01 + 1e-6


def test_recorded_pipeline_readers(recorded):
    """What the readers give on the recorded request; drain ticks compute
    a clipped last chunk, so the bubble costs more than 3 of 19 ticks'
    share of a mean tick."""
    read = lambda name: harness.metric_reader(name)(recorded)
    assert read("pipeline.bubble_frac") == pytest.approx(0.15794, rel=1e-3)
    assert read("pipeline.tick_imbalance_frac") == pytest.approx(0.05681,
                                                                 rel=1e-3)
    assert read("attn.glue_frac") == pytest.approx(0.06742, rel=1e-3)
    # the readers that need the engine's spans find none in a trace
    assert read("engine.host_frac") is None


def test_scope_readers_survive_a_failing_scope_map(recorded, capsys):
    """A program that cannot give its scope map leaves the scope metrics
    out, with the reason on stderr, and the traced run goes on."""
    class Broken(_RecordedProgram):
        def op_scopes(self):
            raise RuntimeError("no compiled text")

    w = recorded.waves[0]
    broken = Broken({"stage_devices": w["program"].stage_devices,
                     "num_ticks": 19, "num_chunks": 16, "op_scopes": {}})
    view = types.SimpleNamespace(trace=recorded.trace,
                                 waves=[dict(w, program=broken)],
                                 window_s=recorded.window_s)
    for name in ("attn.glue_frac", "pipeline.bubble_frac",
                 "pipeline.tick_imbalance_frac"):
        assert harness.metric_reader(name)(view) is None
    assert "no compiled text" in capsys.readouterr().err
