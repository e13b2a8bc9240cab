"""Prefill-only serving engine: request queue -> chunked-pipeline execution
with MOCAP plans, plus the fault-tolerance / elasticity layer.

Responsibilities:
- ADMISSION: requests are bucketed by padded sequence length; each bucket has
  a cached LBCP plan (DP+SA is amortized across requests — plans are a pure
  function of (bucket, N, M)).
- EXECUTION: pluggable executor. ``JaxExecutor`` drives the real jit'd
  ``core.pipeline.prefill_pipeline``; ``SimExecutor`` drives the analytic cost
  model with fault/straggler injection (tests, capacity planning).
- FAULT TOLERANCE: a stage failure loses that stage's layer-slice KV, so
  in-flight requests cannot be resumed mid-chunk — the engine re-forms the
  pipeline WITHOUT the failed stage (N -> N-1... rounded down to even, MBKR
  needs pairs), re-plans all buckets, and REPLAYS in-flight requests from
  their admission watermark. Completed requests are never recomputed.
- STRAGGLER MITIGATION: per-stage chunk-latency EWMA; sustained skew above
  ``straggler_threshold`` triggers a re-plan with the observed per-stage speed
  factors folded into the cost model; a stage past ``evict_threshold`` is
  treated as failed (same re-mesh path).
- CHECKPOINT/RESTART: the full engine state (queue, watermarks, plans, clock,
  EWMA) serializes through ``runtime.checkpoint`` next to the model params.

Two engines share the executors:
- ``PrefillEngine``: BATCH-SYNCHRONOUS — one bucket-batch runs to completion
  before the next forms; every request pays the pipeline fill/drain bubble.
- ``ContinuousEngine``: drives the executor through the chunk-level scheduler
  (``repro.sched``) for cross-request pipelining — bubble-free across request
  boundaries, policy-ordered (FCFS/SJF/EDF) KV-lease-gated admission.
"""
from __future__ import annotations

import math
import time
import warnings
from dataclasses import dataclass, replace as dc_replace
from typing import (Any, Callable, Dict, List, Optional, Protocol, Sequence,
                    Tuple, runtime_checkable)

import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.core import costmodel as cm
from repro.core import lbcp, mbkr
from repro.obs import trace as obs_trace


@dataclass
class Request:
    rid: int
    arrival: float
    seq_len: int
    tokens: Optional[np.ndarray] = None
    state: str = "queued"          # queued | running | done
    bucket: int = 0
    finish_time: float = math.inf
    replays: int = 0
    result: Any = None
    deadline: float = math.inf     # absolute SLO deadline (continuous mode)
    # chained chunk-content hashes (kvstore.prefix.chunk_hashes); filled by
    # ContinuousEngine.submit from tokens when the prefix cache is armed,
    # or supplied directly by token-free (sim / bench) callers
    prefix_hashes: Tuple[int, ...] = ()
    # measured host times (time.perf_counter seconds): handed to the
    # engine, picked into a batch, result on the host. ``arrival`` and
    # ``finish_time`` stay on the engine's own clock.
    t_submit: float = math.nan
    t_admit: float = math.nan
    t_done: float = math.nan


def bucket_of(buckets: Sequence[int], seq_len: int) -> int:
    for b in buckets:
        if seq_len <= b:
            return b
    return buckets[-1]


@dataclass(frozen=True)
class EngineConfig:
    model: ModelConfig
    hw: cm.HardwareProfile = cm.TPU_V5E
    num_stages: int = 16
    tp: int = 16
    num_chunks: int = 16
    max_batch: int = 8
    buckets: Tuple[int, ...] = (8192, 32768, 131072)
    partition: str = "lbcp"        # uniform | lbcp
    mbkr: bool = True
    compress: float = 1.0
    # KV page store codec (repro.kvstore): admission leases count the
    # STORED (quantized) bytes, so "int8"/"fp8" grow capacity ~2x
    kv_dtype: str = "auto"
    kv_page_tokens: int = 0
    sa_iters: int = 60
    straggler_threshold: float = 1.3   # max/median EWMA tick latency
    evict_threshold: float = 3.0
    ewma_alpha: float = 0.3
    # Continuous-serving policy knobs (formerly ContinuousEngine kwargs):
    # engines are constructible from config alone, so a fleet cell is fully
    # described by ONE declarative EngineConfig (repro.fleet / fleet specs)
    policy: str = "fcfs"               # fcfs | sjf | edf admission order
    slo: Optional[float] = None        # seconds; deadline = arrival + slo
    inflight: int = 2                  # MBKR slot pools provisioned
    trace: bool = False                # record the scheduler trace
    # Cross-request prefix KV reuse (repro.kvstore.prefix, DESIGN.md §11):
    # "on" arms the radix index — an admitted request whose leading chunks
    # are already resident leases ONLY its novel suffix and is priced
    # against the shorter effective sequence; "off" (default) keeps the
    # lowering bit-identical to a build without the feature
    prefix_cache: str = "off"          # off | on
    prefix_min_pages: int = 1          # ignore hits smaller than this


class StageFailure(RuntimeError):
    def __init__(self, stage: int):
        super().__init__(f"stage {stage} failed")
        self.stage = stage


# ----------------------------------------------------------- cell protocol

@runtime_checkable
class CellHandle(Protocol):
    """The NARROW seam between one serving cell and everything above it.

    A cell is one pipeline (scheduler + lease manager + executor) behind a
    handful of methods; the fleet router (``repro.fleet``) and the serve
    driver (``launch.serve``) consume ONLY this protocol — no reaching into
    ``.scheduler`` / ``.lease`` / ``.executor`` internals (source-scan
    enforced by ``tests/test_fleet.py``, the same idiom as the PR 5
    transport grep). ``ContinuousEngine`` is the canonical implementation.

    Lifecycle: ``submit`` -> ``run_until_drained`` (re-entrant pump) ->
    ``poll`` (completed requests since the last poll). ``drain`` stops
    admission permanently and completes in-flight work. Router signals:
    ``queue_depth``, ``free_lease_bytes``, ``estimate_admission`` — the
    load-, lease- and cost-aware placement inputs.
    """

    draining: bool

    # ------------------------------------------------------------ lifecycle
    def submit(self, req: "Request") -> None: ...
    def run_until_drained(self) -> None: ...
    def poll(self) -> List["Request"]: ...
    def drain(self) -> List["Request"]: ...

    # -------------------------------------------------------------- signals
    def queue_depth(self) -> int: ...
    def free_lease_bytes(self) -> float: ...
    def estimate_admission(self, seq_len: int, arrival: float = 0.0,
                           prefix_hashes: Optional[Sequence[int]] = None
                           ) -> Tuple[float, bool]: ...
    def prefix_stats(self) -> Dict[str, Any]: ...
    def prefix_hit_pages(self, prefix_hashes: Sequence[int]) -> int: ...

    # ----------------------------------------------------- metrics / obs
    def metrics(self) -> Dict[str, Any]: ...
    def records(self) -> List[Any]: ...
    def recalibrate(self, hw: Any) -> Any: ...
    def merged_trace(self) -> Any: ...
    def export_obs(self, trace_out: Optional[str] = None,
                   metrics_out: Optional[str] = None,
                   extra: Optional[Dict[str, float]] = None,
                   health: Any = None) -> Dict[str, str]: ...
    def configure_obs(self, *, telemetry: Optional[bool] = None,
                      health: Any = None) -> None: ...
    def waves(self) -> List[Dict[str, Any]]: ...


# ---------------------------------------------------------------- executors

class SimExecutor:
    """Analytic executor: returns per-stage makespan from the cost model.
    Fault/straggler injection for engine tests:
      fail_at[(batch_counter)] = stage    -> raise StageFailure mid-batch
      slow = {stage: factor}              -> inflate that stage's tick times

    BATCH-SYNCHRONOUS semantics: requests in a batch run to completion one
    after another, each paying the full pipeline fill/drain (this is the
    baseline that ``ContinuousEngine`` + ``sched.ChunkScheduler`` eliminate).
    Straggler factors scale only the affected stage's task durations; the
    per-request makespan is recomputed from per-stage times by the shared
    list-scheduling core, so an off-critical-path slow stage no longer
    inflates the whole makespan.
    """

    def __init__(self, cfg: ModelConfig, hw: cm.HardwareProfile,
                 fail_at: Optional[Dict[int, int]] = None,
                 slow: Optional[Dict[int, float]] = None):
        self.cfg, self.hw = cfg, hw
        self.fail_at = fail_at or {}
        self.slow = slow or {}
        self.batch_counter = 0

    def stage_scale(self, num_stages: int) -> np.ndarray:
        scale = np.ones(num_stages)
        for s, f in self.slow.items():
            if s < num_stages:
                scale[s] = max(float(f), 1e-9)
        return scale

    def chunk_costs(self, chunks: Sequence[int], num_stages: int, tp: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """(per-chunk task seconds, per-chunk boundary comm seconds)."""
        sm = cm.StageModel.build(self.cfg, num_stages, tp)
        dur, comm, _, _, _ = cm.chunk_cost_arrays(sm, chunks, self.hw)
        return dur, comm

    def run(self, requests: Sequence[Request], chunks: Sequence[int],
            num_stages: int, tp: int) -> Tuple[float, np.ndarray]:
        """Returns (makespan seconds, per-stage avg tick latency [N])."""
        from repro.sim.engine import schedule_request
        self.batch_counter += 1
        if self.batch_counter in self.fail_at:
            raise StageFailure(self.fail_at[self.batch_counter])
        dur, comm, = self.chunk_costs(chunks, num_stages, tp)
        scale = self.stage_scale(num_stages)
        finish = schedule_request(dur, comm, num_stages, np.zeros(num_stages),
                                  stage_scale=scale)
        lat_req = float(finish[-1][-1])
        lat = np.full(num_stages, dur.mean()) * scale
        makespan = lat_req * max(len(requests), 1)
        return makespan, lat


class JaxExecutor:
    """Real executor: jit'd chunked-pipeline prefill on the current mesh.

    ``collect_telemetry`` (settable any time; keyed into the jit cache)
    switches the pipeline to ``return_telemetry=True`` and records one
    entry per wave in ``self.waves``: wall-clock (start, dur) relative to
    executor construction, the [N, T] StageTelemetry profile, and the
    per-event wire prices — ``ContinuousEngine.merged_trace`` turns these
    into engine wave spans, per-stage tick spans and KV/wire counter
    tracks. Off by default: the compiled program is the plain pipeline.

    ``health`` (an ``obs.health.HealthMonitor``) arms the non-finite
    sentinels in the pipeline and, when telemetry is also on, runs the
    occupancy-drift check against each wave. Attach BEFORE the first run
    at a given shape — the monitor is captured at trace time.

    ``prefix_enabled`` (set by ``ContinuousEngine`` when
    ``EngineConfig.prefix_cache == "on"``) arms the DEVICE half of the
    prefix cache: every wave runs with ``return_kv=True`` and lands each
    request's batch element of the final paged pool in a per-geometry
    ``kvstore.prefix.DeviceSeedCache``; a later wave whose requests all
    share a cached prefix of ``k`` chunks is seeded from those snapshots
    and compiled with ``prefix_chunks=k`` (hit chunks read cached KV, their
    writes land in the scratch slot).

    Every wave is measured from inside: ``spans`` (``obs.trace.SpanLog``)
    records ``prefill_wave seq<S> b<B>`` around ``engine.prepare`` (token
    pad and stack, seed assembly), ``engine.dispatch`` (the jitted call
    returns; ``engine.compile`` inside it on a jit-cache miss),
    ``engine.device_wait`` (``block_until_ready``) and ``engine.fetch``
    (results to the host), plus the ``host.gc`` pauses; the wave record
    carries the phase durations, whether the wave compiled, traced or
    loaded anything, its requests' ``t_admit`` and its ``Program``, whose
    ``op_scopes()`` names the device ops; a ``hybrid_moe`` wave also
    carries ``moe_held_picks``, the (token, pick) pairs that went to the
    held experts, per layer."""

    def __init__(self, cfg: ModelConfig, staged_params, topo, run: RunConfig):
        from repro.core import pipeline as pp
        self.cfg, self.topo, self.run_cfg = cfg, topo, run
        self.staged = staged_params
        self._fns: Dict[Tuple, Tuple[Callable, Any]] = {}
        self._programs: Dict[Tuple, Program] = {}
        self._pp = pp
        self.collect_telemetry = False
        self.health = None
        self.waves: List[Dict[str, Any]] = []
        self._epoch = time.perf_counter()
        self.spans = obs_trace.SpanLog()
        self._gc_read = self._epoch
        self.prefix_enabled = False
        self.prefix_seed_entries = 8       # DeviceSeedCache LRU bound
        self._seed_caches: Dict[Tuple, Any] = {}   # (seq, m) -> DeviceSeedCache
        self.prefix_device_hit_chunks = 0  # sum of seeded k over waves

    # ----------------------------------------------------- device prefix
    def _seed_cache(self, seq: int, m: int):
        from repro.kvstore.prefix import DeviceSeedCache
        key = (seq, m)
        if key not in self._seed_caches:
            self._seed_caches[key] = DeviceSeedCache(self.prefix_seed_entries)
        return self._seed_caches[key]

    @staticmethod
    def _wave_chains(requests: Sequence[Request]) -> List[Tuple[int, ...]]:
        return [tuple(getattr(r, "prefix_hashes", ()) or ()) for r in requests]

    def _assemble_seed(self, cache, chains: List[Tuple[int, ...]], k: int):
        """Stack each request's cached batch element into one stage-stacked
        ``PagedPool`` [n, P, lps, B, ...] for ``prefill_pipeline``'s
        ``prefix_pool`` input. None if any element is missing."""
        from repro.kvstore.pages import PagedPool
        elems = [cache.lookup(ch, k) for ch in chains]
        if any(e is None for e in elems):
            return None
        stack = lambda key: (None if elems[0][key] is None else
                             np.stack([e[key] for e in elems], axis=3))
        return PagedPool(stack("k"), stack("v"),
                         stack("k_scale"), stack("v_scale"))

    def run(self, requests: Sequence[Request], chunks: Sequence[int],
            num_stages: int, tp: int) -> Tuple[float, np.ndarray]:
        import jax
        seq = int(sum(chunks))
        collect = bool(self.collect_telemetry)
        health = self.health
        armed = bool(self.prefix_enabled)
        wi = len(self.waves)
        span = self.spans.span
        events = obs_trace.process_events()
        events0 = events.counts()
        with span(f"prefill_wave seq{seq} b{len(requests)}", wave=wi):
            with span("engine.prepare", wave=wi) as prepare:
                # ---- device prefix: wave-uniform seedable hit length k
                # (static — keyed into the jit cache) + the stacked seed
                # pool when k > 0
                k, seed_pool, chains, seed_cache = 0, None, [], None
                if armed:
                    seed_cache = self._seed_cache(seq, len(chunks))
                    chains = self._wave_chains(requests)
                    if all(chains):
                        k = min(seed_cache.match(ch) for ch in chains)
                key = (seq, len(chunks), collect, health is not None, armed, k)
                if key not in self._fns:
                    plan = self._pp.build_plan(
                        self.cfg, num_stages, seq,
                        dc_replace(self.run_cfg, num_chunks=len(chunks)))
                    self._fns[key] = (None, plan)   # fn built below
                _, plan = self._fns[key]
                if armed:
                    k = min(k, plan.p2, len(chunks) - 1)
                    if k > 0:
                        seed_pool = self._assemble_seed(seed_cache, chains, k)
                        if seed_pool is None:
                            k = 0
                    self.prefix_device_hit_chunks += k
                miss = self._fns[key][0] is None
                if miss:
                    cfg, topo = self.cfg, self.topo
                    kk = k
                    if armed and kk > 0:
                        fn = jax.jit(lambda st, tk, pool: self._pp.prefill_pipeline(
                            cfg, st, tk, plan, topo, return_telemetry=collect,
                            prefix_chunks=kk, prefix_pool=pool, return_kv=True,
                            health=health))
                    elif armed:
                        fn = jax.jit(lambda st, tk: self._pp.prefill_pipeline(
                            cfg, st, tk, plan, topo, return_telemetry=collect,
                            return_kv=True, health=health))
                    else:
                        fn = jax.jit(lambda st, tk: self._pp.prefill_pipeline(
                            cfg, st, tk, plan, topo, return_telemetry=collect,
                            health=health))
                    self._fns[key] = (fn, plan)
                fn, plan = self._fns[key]
                toks = np.stack([np.pad(r.tokens, (0, seq - len(r.tokens)))
                                 for r in requests]).astype(np.int32)
                args = ((self.staged, toks, seed_pool) if seed_pool is not None
                        else (self.staged, toks))
                if miss:
                    self._programs[key] = Program(fn, args, self.topo, plan)
            t0 = time.perf_counter()
            with span("engine.dispatch", wave=wi) as dispatch:
                if miss:
                    with span("engine.compile", wave=wi):
                        res = fn(*args)
                else:
                    res = fn(*args)
                if not isinstance(res, tuple):
                    res = (res,)
                out = res[0]
                tel = res[1] if collect else None
                kv = res[1 + int(collect)] if armed else None
                held = res[-1] if self.cfg.family == "hybrid_moe" else None
            with span("engine.device_wait", wave=wi) as device_wait:
                out.block_until_ready()
            dt = time.perf_counter() - t0
            with span("engine.fetch", wave=wi) as fetch:
                if kv is not None and seed_cache is not None:
                    # snapshot each request's batch element of the final
                    # pool for future waves (keyed by its full hash chain)
                    for i, ch in enumerate(chains):
                        if ch:
                            seed_cache.put(ch, {
                                f: (None if getattr(kv, f) is None else
                                    np.asarray(getattr(kv, f)[:, :, :, i]))
                                for f in ("k", "v", "k_scale", "v_scale")})
                if health is not None:
                    jax.effects_barrier()  # order debug callbacks first
                for r, row in zip(requests, np.asarray(out)):
                    r.result = row
                if tel is not None:
                    tel = {name: np.asarray(v) for name, v in tel.items()}
                if held is not None:
                    held = np.asarray(held).tolist()
        events1 = events.counts()
        compile_events = {e: events1[e] - events0[e] for e in events1}
        pauses = events.gc_pauses(self._gc_read)
        self._gc_read = time.perf_counter()
        for g0, g1, gen in pauses:
            self.spans.add("host.gc", g0, g1, wave=wi, generation=gen)
        wave: Dict[str, Any] = {
            "start": t0 - self._epoch, "dur": dt, "seq": seq,
            "num_ticks": int(plan.num_ticks), "num_stages": num_stages,
            "chunks": list(chunks), "rids": [r.rid for r in requests],
            "prefix_chunks": k,
            "phases": {"prepare": prepare.dur, "dispatch": dispatch.dur,
                       "device_wait": device_wait.dur, "fetch": fetch.dur},
            "jit_miss": miss, "compile_events": compile_events,
            "compiled": miss or any(compile_events.values()),
            "t_admit": [r.t_admit for r in requests],
            "program": self._programs[key],
        }
        if held is not None:
            # (token, pick) pairs routed to the held experts, per layer
            wave["moe_held_picks"] = held
        if tel is not None:
            from repro.obs import telemetry as obs_t
            wave["telemetry"] = tel
            wave["per_event_wire"] = obs_t.per_event_wire_bytes(
                plan, self.cfg, len(requests))
            if health is not None:
                health.check_occupancy(wave["telemetry"], plan)
        self.waves.append(wave)
        return dt, np.full(num_stages, dt / max(len(chunks), 1))

    def op_scopes(self) -> Dict[Tuple, Dict[str, str]]:
        """Per jit key of every program built so far: ``{HLO instruction
        name: innermost device scope}`` (``Program.op_scopes``). Lowers and
        compiles each program's text on the first call; never called on
        the serving path."""
        return {key: p.op_scopes() for key, p in self._programs.items()}


class Program:
    """One jitted pipeline program of a ``JaxExecutor``: the function, the
    shapes and shardings it was first called with (a host array or an
    uncommitted one leaves its sharding to jit, as the call did), and
    which devices hold each stage (``stage_devices[s]``: device ids of
    stage ``s``)."""

    def __init__(self, fn, args, topo, plan):
        import jax

        def spec(a):
            sharding = a.sharding if getattr(a, "committed", False) else None
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                        sharding=sharding)
        self.fn = fn
        self.arg_specs = jax.tree.map(spec, args)
        mesh = topo.mesh
        ids = np.vectorize(lambda d: int(d.id))(mesh.devices)
        ids = np.moveaxis(ids, mesh.axis_names.index(topo.stage_axis), 0)
        self.stage_devices = ids.reshape(ids.shape[0], -1).tolist()
        self.num_ticks = int(plan.num_ticks)
        self.num_chunks = int(plan.num_chunks)
        self._scopes: Optional[Dict[str, str]] = None

    def op_scopes(self) -> Dict[str, str]:
        """``{HLO instruction name: innermost scope of obs.trace.SCOPES}``
        from the compiled program's text (``op_name`` metadata); computed
        on the first call, by a fresh compile (``obs.trace.
        fresh_compiled_text``)."""
        if self._scopes is None:
            text = obs_trace.fresh_compiled_text(
                self.fn.lower(*self.arg_specs))
            self._scopes = obs_trace.hlo_op_scopes(text)
        return self._scopes


# ------------------------------------------------------------------- engine

def _configure_executor(ex, telemetry: Optional[bool], health: Any) -> None:
    """The shared ``configure_obs`` seam of both engines."""
    if telemetry is not None and hasattr(ex, "collect_telemetry"):
        ex.collect_telemetry = bool(telemetry)
    if health is not None:
        ex.health = health


def _trace_waves(rec, waves: Sequence[Dict[str, Any]]) -> None:
    """Engine wave spans + per-(stage, tick) device spans and
    ``kv_resident_bytes`` / ``device_wire_bytes`` tracks of the waves that
    carry telemetry, on the ``engine`` row (wall clock since executor
    construction)."""
    if waves:
        rec.process_name("engine", "engine (wall clock)")
    for wi, w in enumerate(waves):
        rec.span(f"wave{wi} seq{w['seq']} b{len(w['rids'])}",
                 pid="engine", tid=0, start=w["start"],
                 finish=w["start"] + w["dur"], cat="wave",
                 args={"rids": w["rids"], "chunks": w["chunks"]})
        tel = w.get("telemetry")
        if tel is None:
            continue
        pe = w.get("per_event_wire", {})
        n_st, ticks = tel["own_chunks"].shape
        tick_dur = w["dur"] / max(ticks, 1)
        kv, occ = tel["kv_bytes"], tel["own_chunks"] + tel["hosted_chunks"]
        wire = (tel["spill_events"] * pe.get("spill", 0.0)
                + tel["fetch_events"] * pe.get("fetch", 0.0)
                + tel["qship_events"] * pe.get("qship", 0.0))
        for s in range(n_st):
            for t in range(ticks):
                ts = w["start"] + t * tick_dur
                phase = t - s
                if 0 <= phase < len(w["chunks"]):
                    rec.span(f"tick{t} c{phase}", pid="engine",
                             tid=s + 1, start=ts, finish=ts + tick_dur,
                             cat="tick",
                             args={"stage": s, "chunk": phase,
                                   "occupancy": float(occ[s, t])})
                rec.counter("kv_resident_bytes", pid=s, time=ts,
                            values={f"w{wi}": float(kv[s, t])})
                rec.counter("device_wire_bytes", pid=s, time=ts,
                            values={f"w{wi}": float(wire[s, t])})


class PrefillEngine:
    def __init__(self, ec: EngineConfig, executor):
        self.ec = ec
        self.executor = executor
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._polled = 0
        self.clock = 0.0
        self.num_stages = ec.num_stages
        self.failed_stages: List[int] = []
        self.ewma: Optional[np.ndarray] = None  # lazily seeded by first obs
        self.replans = 0
        self.remeshes = 0
        self._plans: Dict[Tuple[int, int], List[int]] = {}
        # engine.step / engine.admit land in the executor's span record
        # (the engine keeps its own beside an executor without one)
        self.spans = getattr(executor, "spans", None) or obs_trace.SpanLog()
        self._epoch = getattr(executor, "_epoch", time.perf_counter())

    # ---------------------------------------------------------- admission
    def submit(self, req: Request) -> None:
        req.bucket = self._bucket(req.seq_len)
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _bucket(self, seq_len: int) -> int:
        return bucket_of(self.ec.buckets, seq_len)

    def _plan_for(self, bucket: int) -> List[int]:
        key = (bucket, self.num_stages)
        if key not in self._plans:
            if self.ec.partition == "lbcp":
                pp = lbcp.plan_partition(
                    self.ec.model, bucket, self.ec.num_chunks, self.num_stages,
                    self.ec.hw, tp=self.ec.tp, mbkr=self.ec.mbkr,
                    compress=self.ec.compress, sa_iters=self.ec.sa_iters)
                self._plans[key] = pp.chunks
            else:
                self._plans[key] = lbcp.uniform_partition(bucket, self.ec.num_chunks)
        return self._plans[key]

    # ---------------------------------------------------------- main loop
    def step(self) -> bool:
        """Admit and run ONE batch. Returns False when the queue is empty.

        The batch's bucket is the one holding the OLDEST eligible request
        (by arrival, then rid) across all buckets — not the first queue
        entry's bucket, which would let one hot bucket starve the others
        (head-of-line blocking). Within the bucket, oldest requests first.
        """
        pending = [r for r in self.queue if r.state == "queued"]
        if not pending:
            return False
        waves = getattr(self.executor, "waves", None)
        wi = len(waves) if waves is not None else None
        with self.spans.span("engine.step", wave=wi) as step:
            with self.spans.span("engine.admit", wave=wi) as admit:
                oldest = min(pending, key=lambda r: (r.arrival, r.rid))
                bucket = oldest.bucket
                batch = sorted((r for r in pending if r.bucket == bucket),
                               key=lambda r: (r.arrival, r.rid)
                               )[: self.ec.max_batch]
                chunks = self._plan_for(bucket)
                now = time.perf_counter()
                for r in batch:
                    r.state = "running"
                    r.t_admit = now
            try:
                makespan, stage_lat = self.executor.run(
                    batch, chunks, self.num_stages, self.ec.tp)
            except StageFailure as e:
                self._handle_failure(e.stage, batch)
                return True
            self.clock += makespan
            self._observe(stage_lat)
            now = time.perf_counter()
            for r in batch:
                r.state = "done"
                r.finish_time = self.clock
                r.t_done = now
                self.queue.remove(r)
                self.done.append(r)
        if waves is not None and len(waves) > wi:
            waves[wi].setdefault("phases", {})["admit"] = admit.dur
            waves[wi]["step"] = step.dur
        return True

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.step():
                return

    def poll(self) -> List[Request]:
        """Requests completed since the last ``poll`` (completion order) —
        the same cell-handle surface ``ContinuousEngine`` exposes."""
        new = self.done[self._polled:]
        self._polled = len(self.done)
        return list(new)

    def configure_obs(self, *, telemetry: Optional[bool] = None,
                      health: Any = None) -> None:
        """See ``ContinuousEngine.configure_obs`` — shared executor seam."""
        _configure_executor(self.executor, telemetry, health)

    def waves(self) -> List[Dict[str, Any]]:
        """See ``ContinuousEngine.waves``."""
        return list(getattr(self.executor, "waves", []))

    # ------------------------------------------------------ fault handling
    def _handle_failure(self, stage: int, batch: Sequence[Request]) -> None:
        """Stage loss: its layer-slice KV for in-flight requests is gone ->
        re-form the pipeline without it and replay the batch from admission."""
        self.failed_stages.append(stage)
        new_n = self.num_stages - 1
        if new_n % 2:
            new_n -= 1  # MBKR pairs stages; keep N even
        self.num_stages = max(new_n, 2)
        self.remeshes += 1
        self._plans.clear()          # plans depend on N — rebuild lazily
        self.ewma = None
        for r in batch:
            r.state = "queued"       # replay from the admission watermark
            r.replays += 1

    # -------------------------------------------------- straggler handling
    def _observe(self, stage_lat: np.ndarray) -> None:
        a = self.ec.ewma_alpha
        if self.ewma is None or len(stage_lat) != len(self.ewma):
            self.ewma = np.asarray(stage_lat, float)
        self.ewma = (1 - a) * self.ewma + a * stage_lat
        med = float(np.median(self.ewma))
        worst = int(np.argmax(self.ewma))
        skew = float(self.ewma[worst] / max(med, 1e-12))
        if skew > self.ec.evict_threshold:
            self._handle_failure(worst, [r for r in self.queue
                                         if r.state == "running"])
        elif skew > self.ec.straggler_threshold:
            self._plans.clear()      # fold new latencies into fresh plans
            self.replans += 1

    # ----------------------------------------------------------- metrics
    def metrics(self) -> Dict[str, float]:
        """TTFT (``t_done - t_submit``) and queue wait (``t_admit -
        t_submit``) as measured on the host clock; ``avg_e2e``/``p99_e2e``
        (``finish_time - arrival``), ``throughput`` and ``makespan`` on the
        engine clock, which sums the executor's wave times (the analytic
        model's under ``SimExecutor``)."""
        timed = [r for r in self.done if math.isfinite(r.t_done - r.t_submit)]
        ttft = [r.t_done - r.t_submit for r in timed]
        wait = [r.t_admit - r.t_submit for r in timed]
        lat = [r.finish_time - r.arrival for r in self.done]
        return {
            "completed": len(self.done),
            "avg_ttft": float(np.mean(ttft)) if ttft else math.nan,
            "p99_ttft": float(np.percentile(ttft, 99)) if ttft else math.nan,
            "avg_queue_wait": float(np.mean(wait)) if wait else math.nan,
            "avg_e2e": float(np.mean(lat)) if lat else math.nan,
            "p99_e2e": float(np.percentile(lat, 99)) if lat else math.nan,
            "throughput": len(self.done) / self.clock if self.clock else 0.0,
            "makespan": self.clock,
            "replans": self.replans,
            "remeshes": self.remeshes,
            "num_stages": self.num_stages,
        }

    # ------------------------------------------------------ observability
    def merged_trace(self) -> obs_trace.TraceRecorder:
        """One Perfetto trace of the run on the host clock (seconds since
        the executor was built): the engine's spans (``engine.*``,
        ``prefill_wave``, ``host.gc``) and waves with any device telemetry
        on the ``engine`` row, one ``r<rid>`` span per finished request
        from submit to done. Pure: a fresh recorder each call."""
        rec = obs_trace.TraceRecorder(enabled=True)
        rec.process_name("engine", "engine (wall clock)")
        for name, t0, t1, ids in self.spans.spans:
            rec.span(name, pid="engine", tid=0, start=t0 - self._epoch,
                     finish=t1 - self._epoch, cat="engine", args=dict(ids))
        _trace_waves(rec, getattr(self.executor, "waves", None) or [])
        for r in self.done:
            if math.isfinite(r.t_done - r.t_submit):
                rec.span(f"r{r.rid}", pid="requests", tid=r.rid,
                         start=r.t_submit - self._epoch,
                         finish=r.t_done - self._epoch, cat="request",
                         args={"seq_len": r.seq_len,
                               "admit": r.t_admit - self._epoch})
        return rec

    def export_obs(self, trace_out: Optional[str] = None,
                   metrics_out: Optional[str] = None,
                   extra: Optional[Dict[str, float]] = None,
                   health=None) -> Dict[str, str]:
        """Export the merged trace and/or the metrics summary with TTFT and
        queue-wait histograms of the measured timestamps; returns {"trace":
        path, "metrics": path} for whichever was asked."""
        from repro.obs.metrics import export_engine_metrics
        from repro.sched.metrics import RequestRecord
        paths: Dict[str, str] = {}
        if health is None:
            health = getattr(self.executor, "health", None)
        if trace_out:
            paths["trace"] = self.merged_trace().export(trace_out)
        if metrics_out:
            records = [RequestRecord(r.rid, r.t_submit, r.seq_len, r.bucket,
                                     admit=r.t_admit, finish=r.t_done)
                       for r in self.done
                       if math.isfinite(r.t_done - r.t_submit)]
            paths["metrics"] = export_engine_metrics(
                metrics_out, self.metrics(), records=records, extra=extra,
                health=health)
        return paths

    # ------------------------------------------------------- checkpointing
    def state_dict(self) -> Dict[str, Any]:
        """JSON-serializable engine state for ``runtime.checkpoint``.

        ROUND-TRIPS: clock, num_stages, failed_stages, ewma, replans,
        remeshes; per QUEUED request (rid, arrival, seq_len, state, replays);
        per DONE request (rid, arrival, seq_len, finish_time).

        INTENTIONALLY DROPPED: ``Request.tokens`` and ``Request.result``
        (host arrays belong to the data plane — the caller re-submits tokens
        after restore), a queued request's ``finish_time`` (always inf until
        completion), and ``bucket`` (recomputed from seq_len on load). A
        running request is restored as queued: execution is not resumable
        mid-batch, so it replays from its admission watermark.
        """
        return {
            "clock": self.clock,
            "num_stages": self.num_stages,
            "failed_stages": list(self.failed_stages),
            "ewma": self.ewma.tolist() if self.ewma is not None else None,
            "replans": self.replans,
            "remeshes": self.remeshes,
            "queue": [(r.rid, r.arrival, r.seq_len, r.state, r.replays)
                      for r in self.queue],
            "done": [(r.rid, r.arrival, r.seq_len, r.finish_time)
                     for r in self.done],
        }

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        self.clock = d["clock"]
        self.num_stages = int(d["num_stages"])
        self.failed_stages = list(d["failed_stages"])
        self.ewma = np.asarray(d["ewma"]) if d["ewma"] is not None else None
        self.replans = int(d["replans"])
        self.remeshes = int(d["remeshes"])
        self.queue = [Request(rid, arr, sl, state="queued", replays=rp)
                      for rid, arr, sl, state, rp in d["queue"]]
        for r in self.queue:
            r.bucket = self._bucket(r.seq_len)
        self.done = [Request(rid, arr, sl, state="done", finish_time=ft)
                     for rid, arr, sl, ft in d["done"]]
        self._plans.clear()


# -------------------------------------------------------- continuous engine

class ContinuousEngine:
    """Continuous-serving engine: drives the executor THROUGH the chunk-level
    scheduler (``sched.ChunkScheduler``) so the pipeline never drains between
    requests — the next request's chunk 0 enters stage 0 the moment the
    previous request's tail chunk vacates it.

    - ``SimExecutor``: makespans come from the scheduler's true overlapped
      schedule (the shared ``sim.engine.schedule_request`` list-scheduling
      core) — NOT the batch-synchronous per-request serialization; the
      executor's per-stage straggler factors fold in via ``stage_scale``.
    - ``JaxExecutor``: requests execute as chunk-interleaved token batches in
      scheduler admission order — consecutive same-bucket admissions are
      stacked (up to ``max_batch``) so every pipeline tick carries one chunk
      from each request in the wave, and a newly arrived request joins the
      next wave instead of waiting for the whole queue to drain.

    Admission is policy-ordered (``EngineConfig.policy``: fcfs | sjf | edf)
    and gated by the ``KVLeaseManager``, whose per-stage budget is the MBKR
    slot pool provisioned for ``EngineConfig.inflight`` concurrent requests
    (clamped to physical KV capacity). ``EngineConfig.slo`` (seconds), when
    set, stamps each submitted request's deadline = arrival + slo; EDF
    orders by it and metrics report attainment.

    The engine IS a ``CellHandle``: the fleet router and serve driver talk
    to it only through that protocol. The legacy ``policy``/``slo``/
    ``inflight``/``trace`` constructor kwargs are DEPRECATED — set the
    same-named ``EngineConfig`` fields instead (cells need declarative,
    config-only construction); passing one still works but warns.
    """

    def __init__(self, ec: EngineConfig, executor, *,
                 policy: Optional[str] = None, slo: Optional[float] = None,
                 inflight: Optional[int] = None,
                 trace: Optional[bool] = None):
        from repro.sched import (ChunkPlan, ChunkScheduler, KVLeaseManager,
                                 TraceRecorder, slot_budget_bytes)
        legacy = {k: v for k, v in dict(policy=policy, slo=slo,
                                        inflight=inflight,
                                        trace=trace).items() if v is not None}
        if legacy:
            warnings.warn(
                f"ContinuousEngine({', '.join(sorted(legacy))}=...) kwargs "
                "are deprecated; set the same-named EngineConfig fields "
                "instead (engines are constructible from config alone)",
                DeprecationWarning, stacklevel=2)
            ec = dc_replace(ec, **legacy)
        self.ec = ec
        self.executor = executor
        self.slo = ec.slo
        self.draining = False
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._polled = 0          # self.done prefix already handed to poll()
        self._consumed = 0        # scheduler.admitted prefix already drained
        self._plan_cls = ChunkPlan
        self._plans: Dict[int, Any] = {}
        self._mplans: Dict[int, Any] = {}      # bucket -> MBKR plan
        self._pplans: Dict[Tuple[int, int], Any] = {}  # (bucket, k) plans
        self._sm = cm.StageModel.build(ec.model, ec.num_stages, ec.tp)

        # MBKR slot budget for `inflight` concurrent requests, <= capacity
        mplan = mbkr.plan(ec.num_chunks, ec.num_stages, mbkr=ec.mbkr)
        cmax = -(-max(ec.buckets) // ec.num_chunks)
        weights = ec.model.param_count() * 2 / (ec.num_stages * max(ec.tp, 1))
        capacity = max(ec.hw.hbm_cap - weights, 0.0) * max(ec.tp, 1)
        budget = slot_budget_bytes(
            max(ec.inflight, 1) * mplan.num_slots,
            max(cm.kv_chunk_bytes(self._sm, cmax), 1.0),
            ec.num_stages, capacity=capacity if capacity > 0 else None)
        self.lease = KVLeaseManager(ec.num_stages, budget)
        self.trace = TraceRecorder(enabled=ec.trace)
        scale = (executor.stage_scale(ec.num_stages)
                 if hasattr(executor, "stage_scale") else None)
        # leases count the page store's STORED bytes (quantized kv_dtype
        # shrinks every resident byte -> more concurrent admissions fit the
        # same physical slot budget)
        from repro.kvstore import quant as kvq
        codec = kvq.get_codec(ec.kv_dtype, ec.model.dtype)
        kv_compress = kvq.kv_compress_factor(
            codec, model_dtype=ec.model.dtype,
            page_tokens=ec.kv_page_tokens or cmax,
            head_dim=ec.model.resolved_head_dim)
        # radix prefix index (kvstore.prefix): page geometry from the
        # LARGEST bucket's chunk — per-bucket plans with smaller chunks
        # clamp their shared-page subtraction in chunk_page_bytes
        self.prefix_cache = None
        if ec.prefix_cache == "on":
            from repro.kvstore.prefix import PrefixPageCache
            pt = ec.kv_page_tokens or cmax
            ppc = max(-(-cmax // pt), 1)
            self.prefix_cache = PrefixPageCache(
                pages_per_chunk=ppc,
                page_bytes=max(cm.kv_chunk_bytes(self._sm, cmax), 1.0)
                * kv_compress / ppc)
            if hasattr(executor, "prefix_enabled"):
                executor.prefix_enabled = True   # arm the device seed cache
        self.scheduler = ChunkScheduler(
            ec.num_stages, self._chunk_plan, policy=ec.policy, lease=self.lease,
            trace=self.trace, compress=ec.compress, kv_compress=kv_compress,
            stage_scale=scale, page_tokens=ec.kv_page_tokens,
            prefix_cache=self.prefix_cache,
            prefix_min_pages=ec.prefix_min_pages,
            plan_for_prefix=self._chunk_plan_prefix)

    # ---------------------------------------------------------- admission
    def submit(self, req: Request) -> None:
        if self.draining:
            raise RuntimeError(
                "cell is draining: admission is closed (route the request "
                "to another cell — the fleet router skips draining cells)")
        req.bucket = bucket_of(self.ec.buckets, req.seq_len)
        req.t_submit = time.perf_counter()
        if self.slo is not None and not math.isfinite(req.deadline):
            req.deadline = req.arrival + self.slo
        if (self.prefix_cache is not None and not req.prefix_hashes
                and req.tokens is not None):
            from repro.kvstore.prefix import chunk_hashes
            req.prefix_hashes = chunk_hashes(
                np.asarray(req.tokens)[: req.seq_len],
                self._chunk_plan(req.bucket).chunks)
        self.queue.append(req)

    def _chunk_plan(self, bucket: int):
        """Per-bucket LBCP chunk plan + analytic cost vectors (cached).
        For the jax executor the analytic costs order/gate admission only —
        execution timing is real."""
        if bucket not in self._plans:
            ec = self.ec
            if ec.partition == "lbcp":
                pp = lbcp.plan_partition(
                    ec.model, bucket, ec.num_chunks, ec.num_stages, ec.hw,
                    tp=ec.tp, mbkr=ec.mbkr, compress=ec.compress,
                    sa_iters=ec.sa_iters)
                chunks, mplan = pp.chunks, pp.mbkr_plan
            else:
                chunks = lbcp.uniform_partition(bucket, ec.num_chunks)
                mplan = (mbkr.plan(ec.num_chunks, ec.num_stages)
                         if ec.mbkr and not ec.model.attn_free else None)
            self._mplans[bucket] = mplan
            self._plans[bucket] = self._plan_cls.build(
                bucket, chunks, self._sm, ec.hw, mbkr_plan=mplan,
                compress=ec.compress)
        return self._plans[bucket]

    def _chunk_plan_prefix(self, bucket: int, k: int):
        """The bucket's plan re-priced for a resident prefix of ``k``
        chunks (``costmodel.chunk_cost_arrays(prefix_hit_chunks=k)``):
        zero compute/wire rows for served chunks, same chunk partition."""
        if k <= 0:
            return self._chunk_plan(bucket)
        key = (bucket, int(k))
        if key not in self._pplans:
            base = self._chunk_plan(bucket)    # populates _mplans[bucket]
            self._pplans[key] = self._plan_cls.build(
                bucket, list(base.chunks), self._sm, self.ec.hw,
                mbkr_plan=self._mplans.get(bucket), compress=self.ec.compress,
                prefix_hit_chunks=int(k))
        return self._pplans[key]

    # ---------------------------------------------------------- main loop
    def run_until_drained(self) -> None:
        from repro.sched import SchedRequest
        for r in self.queue:
            if r.state != "queued":
                continue
            self.scheduler.submit(SchedRequest(
                rid=r.rid, arrival=r.arrival, seq_len=r.seq_len,
                bucket=r.bucket, deadline=r.deadline, payload=r,
                prefix_hashes=tuple(r.prefix_hashes)))
        # scheduler.admitted is cumulative across calls — only drain the new
        # suffix so run_until_drained stays re-entrant (submit/drain cycles)
        order = self.scheduler.run()[self._consumed:]
        self._consumed += len(order)
        for sr in order:
            req: Request = sr.payload
            req.state = "done"
            req.finish_time = sr.finish_time
            self.queue.remove(req)
            self.done.append(req)
        for sr in self.scheduler.requests:
            if sr.state == "rejected" and sr.payload in self.queue:
                sr.payload.state = "rejected"
                self.queue.remove(sr.payload)
        if not isinstance(self.executor, SimExecutor):
            self._execute_real(order)

    # ------------------------------------------------- cell-handle surface
    def poll(self) -> List[Request]:
        """Requests completed since the last ``poll`` (admission order)."""
        new = self.done[self._polled:]
        self._polled = len(self.done)
        return list(new)

    def drain(self) -> List[Request]:
        """Stop admission PERMANENTLY and complete all in-flight work: the
        queue runs dry through the scheduler, committed KV leases expire as
        their requests finish, and any ``submit`` after this raises. Returns
        the requests completed by the drain (the un-polled suffix)."""
        self.draining = True
        self.run_until_drained()
        return self.poll()

    def queue_depth(self) -> int:
        """Requests submitted or admitted but not yet finished at the cell's
        current head-of-pipeline time — the least-loaded router signal."""
        now = float(self.scheduler.stage_free[0])
        live = sum(1 for sr in self.scheduler.admitted
                   if sr.finish_time > now)
        return live + sum(1 for r in self.queue if r.state == "queued")

    def free_lease_bytes(self) -> float:
        """Tightest per-stage KV-lease headroom (``KVLeaseManager.headroom``)
        from the cell's current head time on — bytes a new request's lease
        could still claim on the most-contended stage."""
        now = float(self.scheduler.stage_free[0])
        return float(self.lease.headroom(after=now).min())

    def estimate_admission(self, seq_len: int, arrival: float = 0.0,
                           prefix_hashes: Optional[Sequence[int]] = None
                           ) -> Tuple[float, bool]:
        """(predicted finish time, lease-fits-now) for a hypothetical
        request — ``ChunkScheduler.preview`` against the live frontier with
        this cell's OWN chunk-cost vectors (per-cell calibrated profiles and
        kv_dtype lease pricing both fold in automatically). Pure.
        ``prefix_hashes`` folds the radix index into the quote: a cell
        already holding the prefix quotes an earlier ETA and a smaller
        lease (the fleet's prefix-affinity signal)."""
        bucket = bucket_of(self.ec.buckets, seq_len)
        return self.scheduler.preview(
            bucket, seq_len, release=arrival,
            prefix_hashes=tuple(prefix_hashes or ()))

    def prefix_stats(self) -> Dict[str, Any]:
        """Radix-index counters (``PrefixPageCache.stats``); {} when the
        prefix cache is off."""
        return self.scheduler.prefix_stats()

    def prefix_hit_pages(self, prefix_hashes: Sequence[int]) -> int:
        """Pages of ``prefix_hashes`` already resident in this cell's radix
        index — the router's prefix-affinity tiebreak signal. 0 when off."""
        if self.prefix_cache is None or not prefix_hashes:
            return 0
        return int(self.prefix_cache.hit_pages(tuple(prefix_hashes)))

    def records(self) -> List[Any]:
        """Per-request ``RequestRecord`` rows (sched.metrics) — the fleet
        summary / SLO attainment input."""
        return list(self.scheduler.metrics.records)

    def configure_obs(self, *, telemetry: Optional[bool] = None,
                      health: Any = None) -> None:
        """Arm executor-side observability WITHOUT poking the executor from
        outside (the protocol seam): device telemetry (``return_telemetry``)
        and a health monitor. Flags an executor does not support are
        ignored (SimExecutor IS the analytic model)."""
        _configure_executor(self.executor, telemetry, health)

    def waves(self) -> List[Dict[str, Any]]:
        """The device executor's waves, in run order: wall-clock ``start``
        / ``dur`` (bounded by ``block_until_ready``), ``seq``, ``rids``,
        ``chunks`` (and telemetry when armed). [] for SimExecutor."""
        return list(getattr(self.executor, "waves", []))

    def _execute_real(self, order) -> None:
        """Chunk-interleaved token batches: stack consecutive same-bucket
        admissions up to max_batch and run each wave through the executor."""
        i = 0
        while i < len(order):
            bucket = order[i].bucket
            wave = [order[i]]
            i += 1
            while (i < len(order) and order[i].bucket == bucket
                   and len(wave) < self.ec.max_batch):
                wave.append(order[i])
                i += 1
            chunks = list(self._chunk_plan(bucket).chunks)
            reqs = [sr.payload for sr in wave]
            now = time.perf_counter()
            for r in reqs:
                r.t_admit = now
            self.executor.run(reqs, chunks, self.ec.num_stages, self.ec.tp)
            now = time.perf_counter()
            for r in reqs:
                r.t_done = now

    # -------------------------------------------------------- calibration
    def recalibrate(self, hw: cm.ProfileSpec) -> cm.HardwareProfile:
        """Swap the engine onto a CALIBRATED profile (a ``HardwareProfile``,
        a registered name, or a path written by
        ``obs.calibrate.save_profile``): replaces ``EngineConfig.hw``, drops
        the cached bucket plans, and rebases the scheduler's admission costs
        via ``ChunkScheduler.rebase_costs`` — already-admitted requests keep
        their schedule; only future candidates see measured rates. A
        ``SimExecutor`` also re-prices execution."""
        hw = cm.resolve_profile(hw)
        self.ec = dc_replace(self.ec, hw=hw)
        self._sm = cm.StageModel.build(self.ec.model, self.ec.num_stages,
                                       self.ec.tp)
        self._plans.clear()
        self._mplans.clear()
        self._pplans.clear()
        self.scheduler.rebase_costs(self._chunk_plan)
        if isinstance(self.executor, SimExecutor):
            self.executor.hw = hw
        return hw

    # ----------------------------------------------------------- metrics
    @property
    def clock(self) -> float:
        return self.scheduler.metrics.makespan

    def metrics(self) -> Dict[str, float]:
        return self.scheduler.summary()

    # ------------------------------------------------------ observability
    def merged_trace(self):
        """ONE Perfetto trace merging every surface of this run:

        - scheduler task intervals + request lifecycle marks (pid = stage,
          tid = request; the scheduler's virtual clock),
        - per-stage ``kv_lease_bytes`` counter tracks replayed from the
          lease manager's admission timeline (virtual clock),
        - per-stage ``wire_bytes`` counter tracks: sim runs price each
          spilled chunk (index >= p2) from the bucket plan's KV bytes;
          jax runs with ``executor.collect_telemetry`` price the device
          event counts with the analytic per-event wire bytes,
        - engine wave spans + per-(stage, tick) device spans and
          ``kv_resident_bytes`` tracks from JaxExecutor telemetry waves
          (wall clock since executor construction, pid = "engine"),
        - health-sentinel alerts (``executor.health``) on a ``health``
          process row.

        Pure: builds a fresh recorder each call; safe to export repeatedly.
        """
        from repro.obs.trace import TraceRecorder
        rec = TraceRecorder(enabled=True)
        rec.tasks = list(self.trace.tasks)
        rec.marks = list(self.trace.marks)
        # lease residency per stage (virtual clock)
        for s, timeline in enumerate(self.lease._timeline):
            level = 0.0
            for t, delta in sorted(timeline):
                level += delta
                rec.counter("kv_lease_bytes", pid=s, time=t,
                            values={"bytes": level})
        # sim wire model: a chunk with index >= p2 was spilled at creation
        buckets = {sr.rid: sr.bucket for sr in self.scheduler.requests}
        wire_acc: Dict[int, float] = {}
        for ev in sorted(self.trace.tasks, key=lambda e: e.finish):
            plan = self._chunk_plan(buckets.get(ev.rid, max(self.ec.buckets)))
            if ev.chunk >= plan.p2:
                lvl = wire_acc.get(ev.stage, 0.0) + float(plan.kvb[ev.chunk])
                wire_acc[ev.stage] = lvl
                rec.counter("wire_bytes", pid=ev.stage, time=ev.finish,
                            values={"bytes": lvl})
        # engine waves (wall clock) + device telemetry
        _trace_waves(rec, getattr(self.executor, "waves", None) or [])
        health = getattr(self.executor, "health", None)
        if health is not None:
            health.to_trace(rec)
        return rec

    def export_obs(self, trace_out: Optional[str] = None,
                   metrics_out: Optional[str] = None,
                   extra: Optional[Dict[str, float]] = None,
                   health=None) -> Dict[str, str]:
        """Export the merged trace and/or the metrics summary (both atomic);
        returns {"trace": path, "metrics": path} for whichever was asked.
        ``health`` (default: the executor's attached monitor) adds the
        per-kind alert counters and burn-rate gauge to the metrics."""
        paths: Dict[str, str] = {}
        if health is None:
            health = getattr(self.executor, "health", None)
        if trace_out:
            paths["trace"] = self.merged_trace().export(trace_out)
        if metrics_out:
            from repro.obs.metrics import export_engine_metrics
            paths["metrics"] = export_engine_metrics(
                metrics_out, self.metrics(),
                records=self.scheduler.metrics.records, extra=extra,
                health=health)
        return paths
