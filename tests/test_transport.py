"""Transport-layer tests (DESIGN.md §3.6): the pipeline path is grep-clean
of raw collectives, fetch-vs-qship logits agree, the runtime CollectiveLedger
matches the §3.4 analytic traffic model within 1%, batched fetch equals
streamed fetch at 1e-6 with O(1) attention launches per tick, and the manual
TP lowering (forced, so it is exercised on BOTH jaxlib legs) matches the
full-forward oracle."""
import os
import re
import subprocess
import sys

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


# ------------------------------------------------- protocol surface / hygiene

def test_no_raw_collectives_in_pipeline_path():
    """Acceptance: zero raw ppermute/psum call sites outside
    core/transport.py in the pipeline path."""
    core = os.path.join(ROOT, "src", "repro", "core")
    pat = re.compile(r"jax\.lax\.(ppermute|psum|psum_scatter|all_gather)\b")
    for name in ("remote.py", "pipeline.py", "gpipe.py", "stagestep.py"):
        src = open(os.path.join(core, name)).read()
        hits = pat.findall(src)
        assert not hits, f"raw collectives in core/{name}: {hits}"


def test_transport_registry():
    from repro.core import transport as tx
    tr = tx.get_transport("jax")
    assert tr.name == "jax"
    assert "jax" in tx.available_transports()
    with pytest.raises(KeyError):
        tx.get_transport("nope")


def test_resolve_tp_lowering():
    from repro import compat
    assert compat.resolve_tp_lowering("manual") == "manual"
    assert compat.resolve_tp_lowering("auto") == "auto"
    assert compat.resolve_tp_lowering("") == "auto"
    with pytest.raises(ValueError):
        compat.resolve_tp_lowering("gspmd")


def test_analytic_model_shapes():
    """Closed-form totals react to the knobs the §3.4 model prices."""
    from repro.configs.base import RunConfig, get_smoke_config, replace
    from repro.core import pipeline as pp
    from repro.core import transport as tx
    cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
    run_f = RunConfig(num_chunks=8, num_stages=8, remote_attn="fetch")
    run_q = RunConfig(num_chunks=8, num_stages=8, remote_attn="qship")
    wf = tx.analytic_wire_bytes(pp.build_plan(cfg, 8, 128, run_f), cfg, 2)
    wq = tx.analytic_wire_bytes(pp.build_plan(cfg, 8, 128, run_q), cfg, 2)
    assert wf["fetch"] > 0 and wf["qship_q"] == 0
    assert wq["qship_q"] > 0 and wq["fetch"] == 0
    assert wf["ring"] == wq["ring"] > 0
    assert wf["spill"] == wq["spill"] > 0
    # int8 codec compresses the spill/fetch wire, not the activation ring
    run_i8 = RunConfig(num_chunks=8, num_stages=8, remote_attn="fetch",
                       kv_dtype="int8", kv_page_tokens=8)
    wi = tx.analytic_wire_bytes(pp.build_plan(cfg, 8, 128, run_i8), cfg, 2)
    assert wi["fetch"] < wf["fetch"] and wi["spill"] < wf["spill"]
    assert wi["ring"] == wf["ring"]
    # terapipe: no MBKR traffic at all
    wt = tx.analytic_wire_bytes(
        pp.build_plan(cfg, 8, 128, run_f, mode="terapipe"), cfg, 2)
    assert wt["spill"] == wt["fetch"] == wt["qship_q"] == 0
    # ragged-occupancy variant (paged pool path): all-resident == the dense
    # closed form EXACTLY; partially-resident chunks shed wire on the paged
    # categories (spill/fetch) and nothing else
    plan_i8 = pp.build_plan(cfg, 8, 128, run_i8)
    ppc = plan_i8.pages_per_chunk
    assert ppc > 1  # the ragged model needs sub-chunk granularity to price
    wfull = tx.analytic_wire_bytes(plan_i8, cfg, 2,
                                   resident_pages=[ppc] * plan_i8.num_chunks)
    assert wfull == wi
    wrag = tx.analytic_wire_bytes(plan_i8, cfg, 2,
                                  resident_pages=[1] * plan_i8.num_chunks)
    assert 0 < wrag["fetch"] < wi["fetch"]
    assert 0 < wrag["spill"] < wi["spill"]
    assert wrag["ring"] == wi["ring"] and wrag["collect"] == wi["collect"]
    # per-chunk pricing: only the spilled chunks' residency matters, and a
    # single full chunk among them sits strictly between the extremes
    mixed = [1] * (plan_i8.num_chunks - 1) + [ppc]
    wmix = tx.analytic_wire_bytes(plan_i8, cfg, 2, resident_pages=mixed)
    assert wrag["spill"] < wmix["spill"] < wi["spill"]
    only_early = [ppc] * plan_i8.p2 + [1] * (plan_i8.num_chunks - plan_i8.p2)
    wearly = tx.analytic_wire_bytes(plan_i8, cfg, 2, resident_pages=only_early)
    assert wearly["spill"] == wrag["spill"]  # chunks < p2 never spill


@pytest.mark.parametrize("spill", [0, 1, 2, 4])
def test_mbkr_spill_chunks_reach_remote_attention(spill):
    """At N=4, M=16 the fewest-slots plan spills only the last chunk, which
    no later chunk reads: no remote attention at all. ``mbkr_spill_chunks``
    moves p2 so that later chunks read spilled ones (qship traffic)."""
    from repro.configs.base import RunConfig, get_smoke_config
    from repro.core import mbkr
    from repro.core import pipeline as pp
    from repro.core import transport as tx
    cfg = get_smoke_config("qwen3-8b")
    run = RunConfig(num_chunks=16, num_stages=4, mbkr_spill_chunks=spill)
    plan = pp.build_plan(cfg, 4, 256, run)
    w = tx.analytic_wire_bytes(plan, cfg, 1)
    if spill == 0:
        assert plan.p2 == mbkr.best_p2(16, 4)[0] == 15
    else:
        assert plan.p2 == 16 - spill
        mbkr.verify_plan(mbkr.plan(16, 4, p2=plan.p2))
    assert w["spill"] > 0
    # chunk j reads the spilled chunks p2..j-1: none when only the last spills
    assert (w["qship_q"] > 0) == (plan.p2 <= 14)
    with pytest.raises(ValueError, match="mbkr_spill_chunks"):
        pp.build_plan(cfg, 4, 256, RunConfig(num_chunks=16, num_stages=4,
                                              mbkr_spill_chunks=16))


# ---------------------------------------- runtime ledger vs the §3.4 model

SNIPPET_LEDGER = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.core import transport as tx
from repro.models.api import build_model
from repro.models.topology import Topology

# deep geometry (8 stages, p2 = 6 < M-1) so remote chunks are CONSUMED
cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, m, s, b = 8, 8, 128, 2
mesh = compat.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,)*2)
topo = Topology(mesh=mesh)
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)

outs = {}
for remote in ("fetch", "qship"):
    run = RunConfig(num_chunks=m, num_stages=n, remote_attn=remote)
    plan = pp.build_plan(cfg, n, s, run)
    staged = pp.stage_params(cfg, params, plan)
    with compat.set_mesh(mesh):
        out, led = jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, topo, return_ledger=True))(staged, toks)
    led = tx.ledger_to_dict(led)
    model_bytes = tx.analytic_wire_bytes(plan, cfg, b)
    for key, expect in model_bytes.items():
        got = led[key]
        if expect == 0.0:
            assert got == 0.0, (remote, key, got)
        else:
            rel = abs(got - expect) / expect
            assert rel < 0.01, (remote, key, got, expect, rel)
    assert led["tp"] == 0.0  # tp=1: no manual TP collectives
    outs[remote] = np.asarray(out)
    print(remote, {k: round(v) for k, v in led.items()})

# fetch-vs-qship logits parity (same math, different combine route)
rel = np.max(np.abs(outs["fetch"] - outs["qship"])
             / (np.abs(outs["fetch"]) + 1e-3))
assert rel < 1e-3, rel
print("PASS", rel)
"""


def test_ledger_matches_analytic_and_fetch_qship_parity():
    _run(SNIPPET_LEDGER)


SNIPPET_LEDGER_INT8 = r"""
import jax, jax.numpy as jnp
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.core import transport as tx
from repro.models.api import build_model
from repro.models.topology import Topology

cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, m, s, b = 8, 8, 128, 2
mesh = compat.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,)*2)
topo = Topology(mesh=mesh)
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
run = RunConfig(num_chunks=m, num_stages=n, remote_attn="fetch",
                kv_dtype="int8", kv_page_tokens=8)
plan = pp.build_plan(cfg, n, s, run)
staged = pp.stage_params(cfg, params, plan)
with compat.set_mesh(mesh):
    out, led = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo, return_ledger=True))(staged, toks)
led = tx.ledger_to_dict(led)
model_bytes = tx.analytic_wire_bytes(plan, cfg, b)
for key in ("fetch", "spill", "ring"):
    expect = model_bytes[key]
    rel = abs(led[key] - expect) / expect
    assert rel < 0.01, (key, led[key], expect)
print("PASS quantized ledger", {k: round(v) for k, v in led.items()})
"""


def test_ledger_quantized_wire():
    """The ledger counts the ENCODED wire (int8 payload + fp32 scales), and
    the analytic model agrees — quantized-aware accounting."""
    _run(SNIPPET_LEDGER_INT8)


# -------------------------------------------------------- batched fetch

SNIPPET_BATCHED_FETCH = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.kernels import ops
from repro.models.api import build_model
from repro.models.topology import Topology

# 8 stages -> p2 = 6: TWO remote chunk-layers land per (layer, tick)
cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, m, s, b = 8, 8, 128, 2
mesh = compat.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,)*2)
topo = Topology(mesh=mesh)
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)

outs, launches = {}, {}
for fb in ("off", "on"):
    run = RunConfig(num_chunks=m, num_stages=n, remote_attn="fetch",
                    attn_backend="pallas", fetch_batch=fb)
    plan = pp.build_plan(cfg, n, s, run)
    n_remote = m - plan.p2
    assert n_remote >= 2, n_remote
    staged = pp.stage_params(cfg, params, plan)
    with compat.set_mesh(mesh):
        fn = jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, topo))
        launches[fb] = ops.count_launches(fn, staged, toks)["count"]
        outs[fb] = np.asarray(fn(staged, toks))

# batched == streamed numerics at 1e-6 (same kernel, combine moved into the
# slot grid — the pool-batched reconciliation bound)
diff = float(np.max(np.abs(outs["on"] - outs["off"])))
assert diff < 1e-6, diff

# O(1) attention launches per tick for the fetch part: per (tick, layer)
# the streamed path launches one chunk_attention per landed chunk, the
# batched path ONE pool_attention regardless of n_remote (count_launches
# counts kernel call sites x scan trip counts of the per-stage program)
ticks, lps = m + n - 1, plan.layers_per_stage
# streamed: self + own-pool + n_remote fetch; batched: self + own-pool + 1
assert launches["off"] == ticks * lps * (2 + n_remote), launches
assert launches["on"] == ticks * lps * 3, launches
print("PASS", diff, launches)
"""


def test_batched_fetch_parity_and_launch_count():
    """Acceptance: batched fetch == streamed fetch at 1e-6, and
    ``count_launches`` pins the batched path at O(1) attention launches per
    (layer, tick) when >= 2 chunks land."""
    _run(SNIPPET_BATCHED_FETCH)


# -------------------------------------------------- manual TP lowering

SNIPPET_MANUAL_TP = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.core import transport as tx
from repro.models.api import build_model
from repro.models.topology import Topology
from jax.sharding import NamedSharding

cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, tp, m, s, b = 4, 2, 8, 128, 2
mesh = compat.make_mesh((n, tp), ("data", "model"), axis_types=(AxisType.Auto,)*2)
topo = Topology(mesh=mesh)
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
ref = np.asarray(model.forward(params, toks)[:, -1, :].astype(jnp.float32))

run = RunConfig(num_chunks=m, num_stages=n, tp_lowering="manual")
plan = pp.build_plan(cfg, n, s, run)
assert plan.tp_lowering == "manual"
staged = pp.stage_params(cfg, params, plan)
specs = pp.stage_param_specs(cfg, plan, topo)
staged = {k: (jax.tree.map(lambda a, sp: jax.device_put(
                  a, NamedSharding(mesh, sp)), staged[k], specs[k],
              is_leaf=lambda x: hasattr(x, "shape"))
              if k in specs else staged[k]) for k in staged}
with compat.set_mesh(mesh):
    out, led = jax.jit(lambda st, tk: pp.prefill_pipeline(
        cfg, st, tk, plan, topo, return_ledger=True))(staged, toks)
led = tx.ledger_to_dict(led)
out = np.asarray(out.astype(jnp.float32))
rel = np.max(np.abs(out - ref) / (np.abs(ref) + 1e-3))
assert rel < 2e-3, rel
# the manual lowering's explicit TP psums are on the ledger
assert led["tp"] > 0, led
# stage-pair wire categories stay at the logical totals (kv/q/state are
# genuinely sharded across tp chips; the ledger psum restores the total)
model_bytes = tx.analytic_wire_bytes(plan, cfg, b)
for key in ("spill", "qship_q", "qship_state"):
    expect = model_bytes[key]
    if expect == 0.0:
        assert led[key] == 0.0, (key, led[key])
    else:
        rel_b = abs(led[key] - expect) / expect
        assert rel_b < 0.01, (key, led[key], expect)
assert led["spill"] > 0  # shallow mocap still spills chunk M-1
# the replicated activation ring is genuinely sent by every tp chip
assert abs(led["ring"] - tp * model_bytes["ring"]) / model_bytes["ring"] < 0.01
print("PASS manual", rel, {k: round(v) for k, v in led.items()})
"""


def test_manual_tp_lowering_forced():
    """Force ``tp_lowering="manual"`` at tp=2 (so the manual path is
    exercised even on jaxlibs where "auto" resolves to GSPMD) and pin the
    oracle numerics plus the ledger's manual-TP accounting."""
    _run(SNIPPET_MANUAL_TP)


# -------------------------------------------------- paged pool backend

SNIPPET_PAGED_LEDGER = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.compat import AxisType
from repro.configs.base import RunConfig, get_smoke_config, replace
from repro.core import pipeline as pp
from repro.core import transport as tx
from repro.kernels import ops
from repro.models.api import build_model
from repro.models.topology import Topology

# deep geometry (p2 = 6 < M-1) so the paged kernel runs on BOTH pool paths:
# the own-pool scan and the batched-fetch landing buffer
cfg = replace(get_smoke_config("qwen3-8b"), dtype="float32")
n, m, s, b = 8, 8, 128, 2
mesh = compat.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,)*2)
topo = Topology(mesh=mesh)
model = build_model(cfg)
params = model.init(jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)

outs, launches = {}, {}
for pool in ("pallas", "paged"):
    run = RunConfig(num_chunks=m, num_stages=n, remote_attn="fetch",
                    attn_backend="pallas", pool_backend=pool,
                    kv_dtype="int8", kv_page_tokens=8)
    plan = pp.build_plan(cfg, n, s, run)
    assert plan.pool_backend == pool
    staged = pp.stage_params(cfg, params, plan)
    with compat.set_mesh(mesh):
        fn = jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, topo, return_ledger=True))
        launches[pool] = ops.count_launches(fn, staged, toks)
        out, led = fn(staged, toks)
    outs[pool] = np.asarray(out)
    if pool == "paged":
        # wire traffic is IDENTICAL under the paged kernel: it changes the
        # consumer-side HBM layout, not what crosses the interconnect — the
        # ledger still pins against the ragged model at full occupancy,
        # which equals the dense closed form
        led = tx.ledger_to_dict(led)
        model_bytes = tx.analytic_wire_bytes(
            plan, cfg, b, resident_pages=[plan.pages_per_chunk] * m)
        for key in ("fetch", "spill", "ring"):
            expect = model_bytes[key]
            rel = abs(led[key] - expect) / expect
            assert rel < 0.01, (key, led[key], expect)

# paged == gathered numerics (identical int8 pages, fp32-rounding bound)
diff = float(np.max(np.abs(outs["paged"] - outs["pallas"])))
assert diff < 1e-6, diff

# launch accounting: the paged run routes EVERY pool-sourced partial (own
# pool + batched fetch) through pool_attention_paged and never launches the
# gathered kernel; totals stay O(1) per (layer, tick)
ticks, lps = m + n - 1, pp.build_plan(
    cfg, n, s, RunConfig(num_chunks=m, num_stages=n)).layers_per_stage
for pool in ("pallas", "paged"):
    assert launches[pool]["count"] == ticks * lps * 3, launches
    assert launches[pool]["chunk_attention"] == ticks * lps, launches
assert launches["pallas"]["pool_attention"] == ticks * lps * 2, launches
assert "pool_attention_paged" not in launches["pallas"], launches
assert launches["paged"]["pool_attention_paged"] == ticks * lps * 2, launches
assert "pool_attention" not in launches["paged"], launches
print("PASS paged ledger", diff, launches["paged"])
"""


def test_paged_pool_ledger_parity_and_launches():
    """End-to-end paged pool backend: logits match the gathered pallas pool
    at 1e-6 on identical int8 pages, the CollectiveLedger pins against the
    ragged analytic model at full occupancy, and every pool launch carries
    the ``pool_attention_paged`` tag with zero gathered-kernel launches."""
    _run(SNIPPET_PAGED_LEDGER)
