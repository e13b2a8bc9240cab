"""One benchmark run of one cell: build the served engine with weights made
from the seed, warm every bucket the traffic uses, drive the traffic for the
window, read the trace, check the served logits against the plain
reference, and return the result line.

The cell names a configuration file (``bench/configs/<config>.json``) and a
traffic file (``bench/traffic/<mix>.json``); the configuration names its
layout (``bench/layouts/<layout>.py``: parameter tree, weights, counts) and
its plain reference (``bench/reference/<reference>.py``); per-layer metrics
are readers in ``bench/metrics/<metric>.py``. Nothing here names a cell or
an architecture. ``root`` points those lookups at another directory (the
tests' fixtures).
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import statistics
import sys
import time
import types

import numpy as np

import flops
import lookup
import traffic as traffic_mod
import weights

BENCH = lookup.BENCH
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, "bench_out")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, name: str):
        bm = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        found = [w for w in bm["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        spec = found[0]
        self.name = name
        conf = [c for c in bm["configs"] if c["name"] == spec["config"]][0]
        self.config = load_json(os.path.join(ROOT, conf["file"]))
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", spec["traffic"] + ".json"))
        self.chips = int(spec["chips"])
        applies = lambda m: name in m.get("workloads", [name])
        self.end_to_end = [m for m in bm["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bm["per_layer"] if applies(m)]


# ------------------------------------------------------------------ system

def tpu_devices(cell: Cell):
    """The cell's chips, brought up through the system's own set-up (which
    fixes its compile cache), with every program written to that cache;
    None, with a message, where JAX finds no TPU or fewer chips than the
    cell asks for: a measurement never falls back to another device."""
    from repro.launch import serve
    devices = serve.jax_devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices[:cell.chips]


def model_config(c: dict, root: str = BENCH):
    """The system's ModelConfig for configuration file ``c``, as its
    layout builds it."""
    return lookup.layout(c, root).model_config(c)


def build_engine(c: dict, t: dict, devices, seed: int, root: str = BENCH):
    """The served engine, built the way ``launch.serve`` builds its jax
    executor (batch scheduler, uniform chunks), on ``devices`` as
    ``stages`` x ``tp``, holding weights made from ``seed``."""
    from jax.sharding import Mesh
    from repro.configs.base import RunConfig
    from repro.core import costmodel as cm
    from repro.models.topology import Topology
    from repro.runtime.engine import EngineConfig, JaxExecutor, PrefillEngine

    s = c["serve"]
    stages, tp = s["stages"], s["tp"]
    mesh = Mesh(np.asarray(devices[:stages * tp]).reshape(stages, tp),
                ("data", "model"))
    cfg = model_config(c, root)
    run = RunConfig(num_chunks=t["num_chunks"], num_stages=stages,
                    attn_backend=s["attn_backend"],
                    pool_backend=s["pool_backend"], kv_dtype=s["kv_dtype"])
    ec = EngineConfig(model=cfg, hw=cm.device_profile(devices[0]),
                      num_stages=stages, tp=tp, num_chunks=t["num_chunks"],
                      max_batch=t["max_batch"],
                      buckets=tuple(sorted(t["buckets"])),
                      partition="uniform", kv_dtype=s["kv_dtype"])
    eng = PrefillEngine(ec, JaxExecutor(cfg, None, Topology(mesh=mesh), run))
    load_weights(eng, c, seed, root)
    return eng


def load_weights(eng, c: dict, seed: int, root: str = BENCH) -> None:
    """Give the engine's executor the weights made from ``seed``: one
    jitted call that makes the layout's flat tree and restacks it into the
    system's per-stage layout, sharded as the system shards it."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core import pipeline as pp
    from repro.models.api import build_model
    ex = eng.executor
    cfg, topo = ex.cfg, ex.topo
    plan = pp.build_plan(cfg, topo.num_stages, max(eng.ec.buckets), ex.run_cfg)
    key = weights.base_key(seed)
    layout = lookup.layout(c, root)
    weights.check_layout(
        jax.eval_shape(lambda k: layout.flat_params(k, c), key),
        jax.eval_shape(build_model(cfg).init, jax.random.key(0)))
    shardings = jax.tree.map(
        lambda p: NamedSharding(topo.mesh, p),
        pp.stage_param_specs(cfg, plan, topo),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    ex.staged = jax.jit(
        lambda k: pp.stage_params(cfg, layout.flat_params(k, c), plan),
        out_shardings=shardings)(key)


class CompileCounter:
    """Counts the compilations JAX reports (tracing and backend compiles)
    and the persistent-cache hits, so that a window can show it compiled
    nothing."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event.endswith("backend_compile_duration"):
                self.compiles += 1

        def on_event(event, **kw):
            if event.endswith("cache_hits"):
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ------------------------------------------------------------------ traffic

def warm_up(eng, t: dict, vocab: int) -> None:
    """One request per bucket: every program the window will run."""
    from repro.runtime.engine import Request
    for i, b in enumerate(sorted(t["buckets"])):
        eng.submit(Request(rid=-1 - i, arrival=0.0, seq_len=b,
                           tokens=traffic_mod.warm_tokens(b, vocab)))
        eng.run_until_drained()
    eng.poll()


def drive(eng, t: dict, seed: int, seconds: float, vocab: int) -> dict:
    """Run the traffic for ``seconds`` and drain what it sent. Times are
    host ``perf_counter`` seconds; a request's TTFT runs from when it was
    due to the return of the ``step()`` that finished it."""
    import jax
    from repro.runtime.engine import Request
    annotate = jax.profiler.TraceAnnotation
    sched = traffic_mod.Schedule(t, seed, seconds)
    reqs, pending, late = {}, set(), []
    clock = time.perf_counter

    def send(i, due):
        n = sched.length(i)
        with annotate("bench_submit"):
            eng.submit(Request(rid=i, arrival=due - t0, seq_len=n,
                               tokens=traffic_mod.tokens(seed, i, n, vocab)))
        reqs[i] = {"rid": i, "seq": n, "due": due}
        pending.add(i)

    def step():
        s = clock()
        with annotate("bench_step"):
            eng.step()
        d = clock()
        for r in eng.poll():
            reqs[r.rid].update(start=s, done=d, result=r.result)
            pending.discard(r.rid)
        return d

    with annotate("bench_window"):
        t0 = clock()
        end, cap = t0 + seconds, t0 + seconds + t["drain_cap_s"]
        if sched.loop == "closed":
            sent = 0
            for _ in range(t.get("clients", 1)):
                send(sent, t0)
                sent += 1
            while pending and clock() < cap:
                before = set(pending)
                d = step()
                for _ in before - pending:
                    if d < end:
                        send(sent, d)
                        sent += 1
        else:
            arr, i, waited = sched.arrivals, 0, False
            while (i < len(arr) or pending) and clock() < cap:
                now = clock()
                while i < len(arr) and t0 + arr[i] <= now:
                    if waited:
                        late.append(now - (t0 + arr[i]))
                    send(i, t0 + arr[i])
                    i += 1
                waited = False
                if pending:
                    step()
                elif i < len(arr):
                    with annotate("bench_wait"):
                        time.sleep(max(0.0, t0 + arr[i] - clock()))
                    waited = True
        t_end = clock()
    done = [r for r in reqs.values() if "done" in r]
    return {"t0": t0, "t_end": t_end, "requests": reqs, "done": done,
            "failed": len(reqs) - len(done), "late": late}


# ---------------------------------------------------------------- metrics

def end_to_end(w: dict, setup_s: float) -> dict:
    ttft = [r["done"] - r["due"] for r in w["done"]]
    last = max((r["done"] for r in w["done"]), default=w["t0"])
    out = {"setup_s": setup_s}
    if ttft:
        out["ttft_p50_s"] = statistics.median(ttft)
        out["ttft_p90_s"] = float(np.percentile(ttft, 90))
        out["prefill_tokens_per_s"] = (sum(r["seq"] for r in w["done"])
                                       / (last - w["t0"]))
    return out


def metric_reader(name: str):
    """``read`` of the per-layer metric's file, ``bench/metrics/<name>.py``."""
    return lookup.module("metrics", name).read


def read_per_layer(cell: Cell, view) -> dict:
    """Each per-layer metric's reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------- check

def pick_sample(done: list, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 2])
    order = sorted(done, key=lambda r: r["rid"])
    longest = max(order, key=lambda r: (r["seq"], -r["rid"]))
    rest = [r for r in order if r is not longest]
    k = min(n - 1, len(rest))
    picked = [rest[j] for j in sorted(rng.choice(len(rest), k, replace=False))]
    return [longest] + picked


def compare(served, ref) -> dict:
    """The numbers compared for one request's next-token logits: relative
    L2 error of the row, and how far the served argmax's reference logit
    lies below the reference's best."""
    s = np.asarray(served, np.float64)[: len(ref)]
    r = np.asarray(ref, np.float64)
    if not np.isfinite(s).all():
        return {"logit_rel_err": float("inf"), "top1_gap": float("inf")}
    return {"logit_rel_err": float(np.linalg.norm(s - r) / np.linalg.norm(r)),
            "top1_gap": float(r.max() - r[int(np.argmax(s))])}


def reference_logits(c: dict, seed: int, prompts: list, modes=("f32",),
                     device=None, root: str = BENCH) -> list:
    """The plain reference's next-token logits of each prompt, per mode,
    with the weights made anew from the seed by the layout, one layer at a
    time."""
    import jax
    ref = lookup.module("reference", c["reference"], root)
    layout = lookup.layout(c, root)
    key = weights.base_key(seed)
    with jax.default_device(device):
        layer_fn = jax.jit(lambda k, i: layout.layer(k, c, i))
        g = jax.jit(lambda k: layout.globals_(k, c))(key)
        return ref.last_logits(c, g, lambda i: layer_fn(key, i), prompts,
                               modes)


def check(c: dict, seed: int, done: list, vocab: int, device,
          root: str = BENCH) -> dict:
    """Every compared number of the run, each with its limit."""
    chk = c["check"]
    sample = pick_sample(done, chk["sample"], seed)
    prompts = [traffic_mod.tokens(seed, r["rid"], r["seq"], vocab)
               for r in sample]
    t = time.perf_counter()
    refs = reference_logits(c, seed, prompts, device=device, root=root)
    worst = {}
    for r, ref in zip(sample, refs):
        for k, v in compare(r["result"], ref["f32"]).items():
            worst[k] = max(worst.get(k, 0.0), v)
        s = np.asarray(r["result"], np.float64)[: len(ref["f32"])]
        log(f"request {r['rid']}: largest logit error "
            f"{np.abs(s - ref['f32']).max()!r}, reference logit std "
            f"{ref['f32'].std()!r}")
    log(f"reference over {len(sample)} requests "
        f"({[r['seq'] for r in sample]} tokens): "
        f"{time.perf_counter() - t:.3f} s")
    for k in sorted(set(worst) - set(chk["limits"])):
        log(f"not compared: {k} {worst[k]!r}")
    return {k: {"value": worst.get(k, float("inf")), "limit": lim}
            for k, lim in chk["limits"].items()}


# --------------------------------------------------------------------- run

def free_weights(eng) -> None:
    """Drop the system's weights from the device."""
    import jax
    for a in jax.tree.leaves(eng.executor.staged):
        a.delete()
    eng.executor.staged = None
    gc.collect()


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, keep_trace: str = None, root: str = BENCH) -> dict:
    import jax
    c, t = cell.config, cell.traffic
    counter = CompileCounter()
    eng = build_engine(c, t, devices, seed, root)
    vocab = c["vocab_size"]
    warm_up(eng, t, vocab)
    n_warm = len(eng.waves())
    trace_dir = os.path.join(OUT, "trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    compiles0 = counter.compiles
    w = drive(eng, t, seed, seconds, vocab)
    in_window = counter.compiles - compiles0
    log(f"compiles in the window: {in_window} (setup: {compiles0} compiled,"
        f" {counter.cache_hits} from the persistent cache)")
    if w["late"]:
        log(f"generator lateness after a wait: max "
            f"{max(w['late']) * 1e3:.3f} ms over {len(w['late'])} requests")
    dev = devices[0]
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    waves = eng.waves()[n_warm:]
    tr = breakdown = None
    if trace:
        jax.profiler.stop_trace()
        from tracefile import Trace
        files = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        tr = Trace.load(files[0])
        busy = tr.busy_s()
        device["busy_s"] = sum(busy.values()) / len(busy)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        if keep_trace:
            shutil.copy(files[0], keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    view = types.SimpleNamespace(
        trace=tr, requests=w["done"], waves=waves, config=c, traffic=t,
        chips=cell.chips, window_s=w["t_end"] - w["t0"], t0=w["t0"],
        peak=flops.peaks(dev.device_kind) if dev.platform == "tpu" else None,
        flops=flops)
    if trace:
        metrics = read_per_layer(cell, view)
    else:
        e2e = end_to_end(w, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    free_weights(eng)
    del eng
    numbers = check(c, seed, w["done"], vocab, dev, root)
    # a request that never finished is as wrong as a wrong answer
    correct = bool(w["done"]) and w["failed"] == 0 and all(
        v["value"] <= v["limit"] for v in numbers.values())
    result = {"correct": correct, "attempted": len(w["requests"]),
              "failed": w["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result
