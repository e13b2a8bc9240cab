"""Prefill-only serving driver: the MOCAP engine end-to-end.

Real execution on the available devices — one pipeline stage per device
(tp=2 once there are >= 4, unless Mosaic kernels need tp=1; see
``pipeline_shape``), so a single TPU chip runs a one-stage pipeline;
under ``JAX_PLATFORMS=cpu`` the driver gives the host 8 fake devices itself
(the multi-stage CPU test path) — or --executor sim for the analytic
executor at production scale. ``--preset full`` runs the model's published
widths in its configured dtype; ``smoke`` is the float32 toy config.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --requests 12 \
      --executor jax --attn-backend pallas

Every flag maps to a ``launch.options.ServeOptions`` field: ``--options-out``
writes the resolved options JSON, ``--options-in`` replays one, and flags
the user actually types act as overrides on top (argparse.SUPPRESS — see
launch/options.py). The engine is driven ONLY through the ``CellHandle``
protocol (runtime.engine) — no scheduler/executor internals; that seam is
what lets the same driver run one cell or a fleet.

Continuous chunk-level scheduling (cross-request pipelining, repro.sched):

  PYTHONPATH=src python -m repro.launch.serve --executor sim \
      --scheduler continuous --policy edf --arrival-rate 4 --slo-ms 2000 \
      --trace-out artifacts/sched_trace.json

Multi-cell fleet (repro.fleet): one shared arrival stream routed over N
cells — ``--cells N`` replicates the base options; ``--fleet-spec spec.json``
lists per-cell overrides (heterogeneous kv_dtype / buckets / calibrated
profiles); ``--router`` picks jsf | rr | least-loaded:

  PYTHONPATH=src python -m repro.launch.serve --executor sim \
      --scheduler continuous --cells 2 --router jsf --arrival-rate 6 \
      --requests 24 --seq 30000 --trace-out artifacts/fleet_trace.json
"""
from __future__ import annotations

import math
import os
import time

import numpy as np

from repro.configs.base import RunConfig, get_config, get_smoke_config, replace
from repro.core import costmodel as cm
from repro.launch.options import (ServeOptions, add_serve_args,
                                  options_from_args, resolve_fleet)
from repro.runtime.engine import (ContinuousEngine, EngineConfig, JaxExecutor,
                                  PrefillEngine, Request, SimExecutor)

H2D_BW = 16e9  # host<->device staging bandwidth for the cold tier (B/s)

# persistent XLA compile cache for accelerator runs: one FIXED path inside
# the checkout (the path is part of the cache key) unless the environment
# names one through JAX_COMPILATION_CACHE_DIR, which JAX reads itself
COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))

SIM_BUCKETS = (8192, 32768, 131072)


def _print_tier_summary(cfg, ec, kv_dtype: str, kv_page_tokens: int) -> None:
    """--kv-offload: plan the hot/warm/cold page placement for the engine's
    largest bucket and print it (kvstore.tiers; analytic prefetch scheduled
    off the same chunk-cost vectors the scheduler uses)."""
    from repro.core import mbkr
    from repro.kvstore import pages as kvp
    from repro.kvstore import quant as kvq
    from repro.kvstore import tiers as kvt
    m, n = ec.num_chunks, ec.num_stages
    bucket = max(ec.buckets)
    c = -(-bucket // m)
    mplan = mbkr.plan(m, n, mbkr=ec.mbkr and not cfg.attn_free)
    geom = kvp.page_geometry(c, mplan.num_slots, kv_page_tokens)
    tbl = kvp.build_slot_pages(geom)
    codec = kvq.get_codec(kv_dtype, cfg.dtype)
    sm = cm.StageModel.build(cfg, n, ec.tp)
    dur, _, _, _, _ = cm.chunk_cost_arrays(sm, [c] * m, ec.hw,
                                           mbkr_plan=mplan)
    # per-STAGE budget: tp chips' HBM minus the stage's weight slice
    # (param_count*2/n bytes, resident on those same chips)
    tp = max(ec.tp, 1)
    hot = max(ec.hw.hbm_cap * tp - cfg.param_count() * 2 / n, 0.0) * 0.5
    host_slots = (np.unique(np.concatenate(
        [mplan.host_slot_a[mplan.p2:], mplan.host_slot_b[mplan.p2:]]))
        if mplan.p2 < m else None)
    plan = kvt.plan_tiers(
        geom, codec, tbl, mplan.own_slot, mplan.p2, m,
        kvt.TierSpec(hot_bytes=hot, cold_bw=H2D_BW),
        lps=sm.attn_layers, b=1, kvh=cfg.num_kv_heads,
        hd=cfg.resolved_head_dim, tick_s=dur, host_slots=host_slots)
    s = plan.summary()
    print(f"[kv-offload] bucket {bucket} kv_dtype={codec.name} "
          f"page_tokens={geom.page_tokens}: pages {s['pages']} | "
          f"hot {s['hot_bytes']/1e9:.2f} GB | warm {s['warm_bytes']/1e9:.2f} GB"
          f" | cold {s['cold_bytes']/1e9:.2f} GB | "
          f"prefetch ops {s['prefetch_ops']} "
          f"(peak {s['worst_tick_bw']/1e9:.2f} GB/s vs {H2D_BW/1e9:.0f}) | "
          f"{'FEASIBLE' if s['feasible'] else 'INFEASIBLE'}")


def jax_devices():
    """Bring JAX up for the jax executor and return ``jax.devices()`` — the
    one place ``serve`` and ``chip_smoke.py`` configure the process: fake
    host devices only when the platform is the CPU, and the persistent
    compile cache (``COMPILE_CACHE_DIR``) on an accelerator."""
    import jax
    from repro import compat
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        compat.ensure_host_devices()
    devices = jax.devices()
    if (devices[0].platform != "cpu"
            and not os.environ.get("JAX_COMPILATION_CACHE_DIR")):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return devices


def pipeline_shape(n_dev: int, opts: ServeOptions, platform: str):
    """(stages, tp) over ``n_dev`` devices: tp=2 once there are >= 4,
    otherwise one stage per device (a single chip is a one-stage
    pipeline; MBKR then has no pair and is off). Compiled (Mosaic) Pallas
    kernels cannot be auto-partitioned, so on a TPU with a Pallas backend
    under ``tp_lowering="auto"`` every device is a stage of its own."""
    pallas = {opts.attn_backend, opts.pool_backend,
              opts.ssm_backend} & {"pallas", "paged"}
    mosaic_auto = (platform == "tpu" and bool(pallas)
                   and opts.tp_lowering == "auto")
    tp = 2 if n_dev >= 4 and not mosaic_auto else 1
    return n_dev // tp, tp


def _resolve_hw(opts: ServeOptions):
    if opts.calibrated_profile:
        hw = cm.resolve_profile(opts.calibrated_profile)
        print(f"[profile] {opts.calibrated_profile} -> {hw.name} "
              f"(gemm_eff={hw.gemm_eff:.3f} attn_eff={hw.attn_eff:.3f})")
        return hw
    if opts.executor == "jax":
        import jax
        return cm.device_profile(jax.devices()[0])
    return cm.TPU_V5E


def _build_engine(opts: ServeOptions, *, cfg=None, topo=None, jax_ctx=None):
    """One serving cell from ONE declarative ServeOptions: (cfg, ec, engine).

    ``cfg`` overrides the model config the options name (``chip_smoke.py``
    passes a depth-cut config). ``jax_ctx`` (a dict) carries the
    device-dependent pieces shared across fleet cells: {"stages": N,
    "tp": T}. ``topo`` pins the cell to a specific mesh block
    (``launch.cells.enumerate_cell_meshes``); None = one mesh over all
    devices. Engines come out config-constructed — all policy/slo/trace
    knobs ride on EngineConfig, none on kwargs."""
    if opts.executor == "jax":
        jax_devices()
    hw = _resolve_hw(opts)
    slo = opts.slo_ms / 1e3 if opts.slo_ms else None
    want_trace = opts.trace_out is not None
    if opts.executor == "sim":
        cfg = cfg or get_config(opts.arch)
        ec = EngineConfig(model=cfg, hw=hw, num_stages=16, tp=16,
                          num_chunks=16, max_batch=opts.max_batch,
                          buckets=opts.buckets or SIM_BUCKETS,
                          partition="lbcp", kv_dtype=opts.kv_dtype,
                          kv_page_tokens=opts.kv_page_tokens,
                          policy=opts.policy, slo=slo, trace=want_trace,
                          prefix_cache=opts.prefix_cache,
                          prefix_min_pages=opts.prefix_min_pages)
        executor = SimExecutor(cfg, hw)
    else:
        import jax
        from repro.core import pipeline as pp
        from repro.launch.mesh import make_test_topology
        if cfg is None:
            cfg = (replace(get_smoke_config(opts.arch), dtype="float32")
                   if opts.preset == "smoke" else get_config(opts.arch))
        if jax_ctx is None:
            stages, tp = pipeline_shape(jax.device_count(), opts,
                                        jax.devices()[0].platform)
            jax_ctx = {"stages": stages, "tp": tp}
        stages, tp = jax_ctx["stages"], jax_ctx["tp"]
        if topo is None:
            topo = make_test_topology(stages, tp)
        run = RunConfig(num_chunks=opts.num_chunks, num_stages=stages,
                        attn_backend=opts.attn_backend,
                        pool_backend=opts.pool_backend,
                        ssm_backend=opts.ssm_backend,
                        tp_lowering=opts.tp_lowering,
                        transport=opts.transport,
                        fetch_batch=opts.fetch_batch,
                        kv_dtype=opts.kv_dtype,
                        kv_page_tokens=opts.kv_page_tokens,
                        kv_offload=opts.kv_offload)
        plan = pp.build_plan(cfg, stages, opts.seq, run)
        if plan.tp_lowering == "manual" and tp > 1:
            print(f"[transport] manual TP lowering (tp={tp}, "
                  f"transport={plan.transport})")
        staged = pp.init_stage_params(cfg, plan, topo,
                                      jax.random.key(opts.seed))
        ec = EngineConfig(model=cfg, hw=hw, num_stages=stages, tp=tp,
                          num_chunks=opts.num_chunks,
                          max_batch=opts.max_batch,
                          buckets=opts.buckets or (opts.seq,),
                          partition="uniform", kv_dtype=opts.kv_dtype,
                          kv_page_tokens=opts.kv_page_tokens,
                          policy=opts.policy, slo=slo, trace=want_trace,
                          prefix_cache=opts.prefix_cache,
                          prefix_min_pages=opts.prefix_min_pages)
        executor = JaxExecutor(cfg, staged, topo, run)
    if opts.scheduler == "continuous":
        eng = ContinuousEngine(ec, executor)
    else:
        eng = PrefillEngine(ec, executor)
    return cfg, ec, eng


def _make_requests(opts: ServeOptions, vocab_size: int):
    from repro.sched import poisson_arrivals
    arrivals = poisson_arrivals(opts.arrival_rate, opts.requests,
                                seed=opts.seed)
    rng = np.random.default_rng(opts.seed)
    out = []
    for i in range(opts.requests):
        toks = (rng.integers(0, vocab_size, size=opts.seq).astype(np.int32)
                if opts.executor == "jax" else None)
        out.append(Request(rid=i, arrival=float(arrivals[i]),
                           seq_len=opts.seq, tokens=toks))
    return out


# ------------------------------------------------------------------- fleet

def _run_fleet(opts: ServeOptions) -> int:
    """N cells behind the fleet router: one shared arrival stream, per-cell
    EngineConfigs from the fleet spec, roll-up metrics + ONE merged trace
    with per-cell process rows."""
    from repro.fleet import FleetFabric, FleetRouter
    router_policy, cell_opts = resolve_fleet(opts)
    if any(co.scheduler != "continuous" for co in cell_opts):
        print("note: fleet cells require --scheduler continuous; overriding")
        cell_opts = [co.override(scheduler="continuous") for co in cell_opts]
    topos = [None] * len(cell_opts)
    jax_ctx = None
    if opts.executor == "jax":
        from repro.launch.cells import enumerate_cell_meshes
        devices = jax_devices()
        n_dev = len(devices)
        stages, tp = pipeline_shape(n_dev, opts, devices[0].platform)
        jax_ctx = {"stages": stages, "tp": tp}
        topos = list(enumerate_cell_meshes(len(cell_opts), stages, tp))
        if len(cell_opts) * stages * tp > n_dev:
            print(f"note: {len(cell_opts)} cells x {stages}x{tp} exceeds "
                  f"{n_dev} devices; cells share device blocks "
                  f"(replicated-cell mode, serialized execution)")
    cells = {}
    vocab = 0
    for i, (co, topo) in enumerate(zip(cell_opts, topos)):
        cfg, ec, eng = _build_engine(co, topo=topo, jax_ctx=jax_ctx)
        cells[f"cell{i}"] = eng
        vocab = cfg.vocab_size
    fab = FleetFabric(cells, FleetRouter(router_policy))
    monitor = None
    if opts.health:
        from repro.obs.health import HealthMonitor
        monitor = HealthMonitor()
        fab.configure_obs(health=monitor)
    if opts.trace_out:
        fab.configure_obs(telemetry=True)

    t0 = time.time()
    for req in _make_requests(opts, vocab):
        fab.submit(req)
    fab.pump()
    wall = time.time() - t0

    m = fab.metrics()
    slo_txt = (f" | SLO {m['slo_met']}/{m['slo_total']}"
               if m["slo_total"] else "")
    print(f"[fleet {router_policy} x{m['cells']}] completed {m['completed']} "
          f"(rejected {m['rejected']}) in {wall:.2f}s wall | "
          f"makespan {m['makespan']:.3f}s | "
          f"avg TTFT {m['avg_ttft']:.3f}s | p99 {m['p99_ttft']:.3f}s | "
          f"{m['throughput']:.3f} req/s{slo_txt}")
    for name, pc in m["per_cell"].items():
        print(f"  {name}: {pc['completed']} done "
              f"(rejected {pc['rejected']}) | p99 {pc['p99_ttft']:.3f}s")
    if opts.trace_out or opts.metrics_out:
        paths = fab.export_obs(trace_out=opts.trace_out,
                               metrics_out=opts.metrics_out)
        for kind, path in paths.items():
            print(f"{kind} -> {path}")
    return 0


# ------------------------------------------------------------- single cell

def _run_single(opts: ServeOptions) -> int:
    cfg, ec, eng = _build_engine(opts)
    if opts.kv_offload:
        _print_tier_summary(cfg, ec, opts.kv_dtype, opts.kv_page_tokens)
    slo = opts.slo_ms / 1e3 if opts.slo_ms else None

    if opts.trace_out:
        # the merged timeline wants the device-side (stage, tick) profile:
        # switch the jit cache to the return_telemetry=True pipeline (the
        # sim executor has no telemetry switch — configure_obs skips it)
        eng.configure_obs(telemetry=True)
    monitor = None
    if opts.health:
        from repro.obs.health import HealthMonitor
        monitor = HealthMonitor()
        eng.configure_obs(health=monitor)
    arrival_rate = opts.arrival_rate
    if opts.scheduler == "batch" and arrival_rate > 0:
        # the batch-synchronous engine admits everything at clock 0 and its
        # E2E metric is finish - arrival: staggered arrivals would produce
        # negative latencies there, so open-loop arrivals are continuous-only
        print("note: --arrival-rate requires --scheduler continuous; "
              "running the batch engine as a closed loop (arrivals at t=0)")
        opts = opts.override(arrival_rate=0.0)
    for req in _make_requests(opts, cfg.vocab_size):
        eng.submit(req)
    t0 = time.time()
    if opts.profile_dir and opts.executor == "jax":
        import jax
        with jax.profiler.trace(opts.profile_dir):
            eng.run_until_drained()
        print(f"xla profile -> {opts.profile_dir}")
    else:
        if opts.profile_dir:
            print("note: --profile-dir needs --executor jax; skipping")
        eng.run_until_drained()
    wall = time.time() - t0
    finished = eng.poll()

    if monitor is not None:
        if slo is not None and opts.scheduler == "continuous":
            from repro.obs.metrics import Histogram
            h = Histogram("ttft")
            for rec in eng.records():
                if math.isfinite(rec.finish):
                    h.observe(rec.finish - rec.arrival)
            monitor.check_slo(h, slo)
        s = monitor.summary()
        burn = (f" | burn {s['burn_rate']:.2f}x"
                if s["burn_rate"] is not None else "")
        print(f"[health] alerts {s['alerts_total']} {s['by_kind']}{burn}")

    m = eng.metrics()
    if opts.scheduler == "continuous":
        slo_txt = (f" | SLO {m['slo_met']}/{m['slo_total']}"
                   if m["slo_total"] else "")
        print(f"[{opts.policy}] completed {m['completed']} "
              f"(rejected {m['rejected']}) in {wall:.2f}s wall | "
              f"sched clock {m['makespan']:.3f}s | "
              f"avg TTFT {m['avg_ttft']:.3f}s | p99 {m['p99_ttft']:.3f}s | "
              f"avg queue {m['avg_queue_wait']:.3f}s | "
              f"{m['throughput']:.3f} req/s | "
              f"bubble {m['bubble_frac']*100:.1f}%{slo_txt}")
        if opts.trace_out or opts.metrics_out:
            paths = eng.export_obs(trace_out=opts.trace_out,
                                   metrics_out=opts.metrics_out,
                                   extra={"wall_seconds": wall})
            for kind, path in paths.items():
                print(f"{kind} -> {path}")
    else:
        print(f"completed {m['completed']} requests in {wall:.2f}s wall | "
              f"engine clock {eng.clock:.3f}s | avg E2E {m['avg_e2e']:.3f}s | "
              f"p99 {m['p99_e2e']:.3f}s | {m['throughput']:.3f} req/s | "
              f"stages {m['num_stages']}")
        if opts.trace_out:
            print("note: --trace-out needs --scheduler continuous; skipping")
        if opts.metrics_out:
            from repro.obs.metrics import export_engine_metrics
            path = export_engine_metrics(opts.metrics_out, m,
                                         extra={"wall_seconds": wall},
                                         health=monitor)
            print(f"metrics -> {path}")
    if opts.executor == "jax":
        for r in sorted(finished, key=lambda r: r.rid)[:3]:
            top = int(np.argmax(r.result))
            print(f"  request {r.rid}: next-token argmax = {top}")
    return 0


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    ap.add_argument("--options-in", default=None,
                    help="load a ServeOptions JSON (written by "
                         "--options-out); explicit flags override it")
    ap.add_argument("--options-out", default=None,
                    help="write the RESOLVED options JSON here (replayable "
                         "via --options-in), then run")
    ns = ap.parse_args(argv)
    base = ServeOptions()
    if ns.options_in:
        with open(ns.options_in) as f:
            base = ServeOptions.from_json(f.read())
    opts = options_from_args(ns, base)
    if ns.options_out:
        from repro.obs._io import atomic_write_text
        path = atomic_write_text(ns.options_out, opts.to_json())
        print(f"options -> {path}")
    if opts.cells > 1 or opts.fleet_spec:
        return _run_fleet(opts)
    return _run_single(opts)


if __name__ == "__main__":
    raise SystemExit(main())
