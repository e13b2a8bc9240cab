"""Declarative serving options: ONE serializable dataclass for the whole
serve surface.

``ServeOptions`` replaces serve.py's loose ~25-flag argparse namespace:

- every flag maps to a same-named field (dashes -> underscores), so a run
  is reproducible from a JSON file (``--options-out`` writes it,
  ``--options-in`` replays it);
- CLI flags are OVERRIDES: the parser registers every flag with
  ``argparse.SUPPRESS`` defaults, so only flags the user actually typed
  land in the namespace — merge order is dataclass defaults <-
  ``--options-in`` JSON <- explicit flags;
- a FLEET SPEC is a list of per-cell ServeOptions override dicts plus a
  router policy: ``{"router": "jsf", "cells": [{"kv_dtype": "int8"}, {}]}``
  — each cell's EngineConfig derives from the base options with that
  cell's overrides applied (heterogeneous cells by construction).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass, field, fields, replace as dc_replace
from typing import Any, Dict, List, Optional, Tuple


@dataclass(frozen=True)
class ServeOptions:
    # model / scale
    arch: str = "qwen3-8b"
    preset: str = "smoke"              # smoke | full (jax executor)
    executor: str = "jax"              # jax | sim
    requests: int = 12
    seq: int = 256
    num_chunks: int = 8
    max_batch: int = 4
    seed: int = 0
    buckets: Optional[Tuple[int, ...]] = None   # None = executor default
    # kernel / transport backends
    attn_backend: str = "jnp"
    pool_backend: str = "auto"
    ssm_backend: str = "auto"
    tp_lowering: str = "auto"
    transport: str = "jax"
    fetch_batch: str = "auto"
    # KV page store
    kv_dtype: str = "auto"
    kv_page_tokens: int = 0
    kv_offload: bool = False
    # cross-request prefix KV reuse (repro.kvstore.prefix)
    prefix_cache: str = "off"          # off | on
    prefix_min_pages: int = 1
    # scheduling
    scheduler: str = "batch"           # batch | continuous
    policy: str = "fcfs"               # fcfs | sjf | edf
    arrival_rate: float = 0.0
    slo_ms: Optional[float] = None
    # fleet (multi-cell)
    cells: int = 1
    router: str = "jsf"                # jsf | rr | least-loaded
    fleet_spec: Optional[str] = None   # path to the fleet-spec JSON
    # observability
    trace_out: Optional[str] = None
    metrics_out: Optional[str] = None
    profile_dir: Optional[str] = None
    calibrated_profile: Optional[str] = None
    health: bool = False

    # ------------------------------------------------------------ round-trip
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        if d["buckets"] is not None:
            d["buckets"] = list(d["buckets"])
        return d

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ServeOptions":
        known = {f.name for f in fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(f"unknown ServeOptions keys: {unknown} "
                             f"(expected a subset of {sorted(known)})")
        d = dict(d)
        if d.get("buckets") is not None:
            d["buckets"] = tuple(int(b) for b in d["buckets"])
        return cls(**d)

    @classmethod
    def from_json(cls, text: str) -> "ServeOptions":
        return cls.from_dict(json.loads(text))

    def override(self, **kw) -> "ServeOptions":
        """Same validation as from_dict, replace() semantics."""
        if kw.get("buckets") is not None:
            kw["buckets"] = tuple(int(b) for b in kw["buckets"])
        known = {f.name for f in fields(type(self))}
        unknown = sorted(set(kw) - known)
        if unknown:
            raise ValueError(f"unknown ServeOptions keys: {unknown}")
        return dc_replace(self, **kw)


# ------------------------------------------------------------------- parser

def _csv_ints(text: str) -> Tuple[int, ...]:
    return tuple(int(t) for t in text.split(",") if t.strip())


def add_serve_args(ap: argparse.ArgumentParser) -> None:
    """Register every ServeOptions field as a flag with SUPPRESS defaults —
    the namespace carries ONLY flags the user explicitly typed, so they
    overlay cleanly on ``--options-in`` / fleet-spec values."""
    S = argparse.SUPPRESS
    ap.add_argument("--arch", default=S)
    ap.add_argument("--preset", default=S, choices=("smoke", "full"))
    ap.add_argument("--executor", default=S, choices=("jax", "sim"))
    ap.add_argument("--requests", type=int, default=S)
    ap.add_argument("--seq", type=int, default=S)
    ap.add_argument("--num-chunks", type=int, default=S)
    ap.add_argument("--max-batch", type=int, default=S)
    ap.add_argument("--seed", type=int, default=S)
    ap.add_argument("--buckets", type=_csv_ints, default=S,
                    help="comma-separated bucket boundaries (tokens); "
                         "default: executor-specific")
    ap.add_argument("--attn-backend", default=S, choices=("jnp", "pallas"),
                    help="attention inner-loop backend (core.attention): "
                         "jnp = pure-jnp reference, pallas = the flash "
                         "kernel (interpret mode off-TPU)")
    ap.add_argument("--pool-backend", default=S,
                    choices=("auto", "jnp", "pallas", "paged"),
                    help="backend for POOL-sourced partials (own-pool scan "
                         "+ fetch/qship) — mixable with --attn-backend; "
                         "paged = one RAGGED launch straight off the page "
                         "store (DESIGN.md §3.7)")
    ap.add_argument("--ssm-backend", default=S,
                    choices=("auto", "jnp", "pallas"),
                    help="SSD inner loop for ssm/hybrid archs; auto = the "
                         "platform's default (models.ssm.ssd_impl)")
    ap.add_argument("--tp-lowering", default=S, choices=("auto", "manual"),
                    help="TP lowering (core.transport, DESIGN.md §3.6)")
    ap.add_argument("--transport", default=S,
                    help="transport registry entry for cross-stage/"
                         "cross-rank collectives (core.transport)")
    ap.add_argument("--fetch-batch", default=S, choices=("auto", "on", "off"),
                    help="batched fetch: land remote chunk-layers in a "
                         "staging buffer + ONE pool_attention launch")
    ap.add_argument("--kv-dtype", default=S,
                    choices=("auto", "bfloat16", "int8", "fp8"),
                    help="KV page-store codec (repro.kvstore): int8/fp8 "
                         "store+ship quantized pages; leases count "
                         "quantized bytes (~2x admission capacity)")
    ap.add_argument("--kv-page-tokens", type=int, default=S,
                    help="tokens per KV page (0 = one page per chunk)")
    ap.add_argument("--kv-offload", action="store_true", default=S,
                    help="plan the cold KV tier (kvstore.tiers) and print "
                         "the tier summary")
    ap.add_argument("--prefix-cache", default=S, choices=("off", "on"),
                    help="radix prefix KV index (kvstore.prefix): admitted "
                         "requests whose leading chunks are already "
                         "resident lease only their novel suffix "
                         "(continuous scheduler); off = bit-identical to a "
                         "build without the feature")
    ap.add_argument("--prefix-min-pages", type=int, default=S,
                    help="ignore prefix hits smaller than this many pages")
    ap.add_argument("--scheduler", default=S, choices=("batch", "continuous"),
                    help="batch = batch-synchronous PrefillEngine; "
                         "continuous = cross-request chunk pipelining")
    ap.add_argument("--policy", default=S, choices=("fcfs", "sjf", "edf"),
                    help="continuous-mode admission policy")
    ap.add_argument("--arrival-rate", type=float, default=S,
                    help="open-loop Poisson arrivals (req/s); 0 = closed loop")
    ap.add_argument("--slo-ms", type=float, default=S,
                    help="per-request SLO (deadline = arrival + slo)")
    ap.add_argument("--cells", type=int, default=S,
                    help="fleet mode: run N serving cells behind the fleet "
                         "router (repro.fleet); implies --scheduler "
                         "continuous")
    ap.add_argument("--router", default=S, choices=("jsf", "rr", "least-loaded"),
                    help="fleet placement policy: jsf = join-shortest-"
                         "finish (lease/cost-aware ETA), rr = round-robin, "
                         "least-loaded = smallest queue depth")
    ap.add_argument("--fleet-spec", default=S,
                    help="fleet-spec JSON: {\"router\": ..., \"cells\": "
                         "[per-cell ServeOptions overrides, ...]} — "
                         "heterogeneous cells (kv_dtype, buckets, "
                         "calibrated_profile, ...)")
    ap.add_argument("--trace-out", default=S,
                    help="write ONE merged Chrome/Perfetto trace here; in "
                         "fleet mode each cell gets its own process rows")
    ap.add_argument("--metrics-out", default=S,
                    help="export serving metrics here (repro.obs.metrics): "
                         ".prom = Prometheus textfile, else JSON lines")
    ap.add_argument("--profile-dir", default=S,
                    help="wrap the run in jax.profiler.trace(dir) "
                         "(jax executor only)")
    ap.add_argument("--calibrated-profile", default=S,
                    help="HardwareProfile for planning/admission costs: a "
                         "registered name or a calibrated-profile JSON "
                         "(obs.calibrate)")
    ap.add_argument("--health", action="store_true", default=S,
                    help="arm the runtime health sentinels (obs.health)")


def options_from_args(ns: argparse.Namespace,
                      base: Optional[ServeOptions] = None) -> ServeOptions:
    """Overlay the explicitly-typed flags (SUPPRESS leaves the rest out of
    the namespace) onto ``base`` (defaults or ``--options-in``)."""
    base = base or ServeOptions()
    known = {f.name for f in fields(ServeOptions)}
    explicit = {k: v for k, v in vars(ns).items() if k in known}
    return base.override(**explicit)


# --------------------------------------------------------------- fleet spec

@dataclass(frozen=True)
class FleetSpec:
    """Router policy + per-cell option overrides. ``cell_options(base)``
    materializes the per-cell ServeOptions list: base <- overrides[i]."""
    router: str = "jsf"
    cells: Tuple[Dict[str, Any], ...] = field(default_factory=tuple)

    @classmethod
    def from_json(cls, text: str) -> "FleetSpec":
        d = json.loads(text)
        unknown = sorted(set(d) - {"router", "cells"})
        if unknown:
            raise ValueError(f"unknown fleet-spec keys: {unknown}")
        return cls(router=d.get("router", "jsf"),
                   cells=tuple(d.get("cells", ())))

    def cell_options(self, base: ServeOptions) -> List[ServeOptions]:
        return [base.override(**dict(ov)) for ov in self.cells]


def resolve_fleet(opts: ServeOptions) -> Tuple[str, List[ServeOptions]]:
    """(router policy, per-cell options) from ``--fleet-spec`` (wins) or
    ``--cells N`` homogeneous replication."""
    if opts.fleet_spec:
        with open(opts.fleet_spec) as f:
            spec = FleetSpec.from_json(f.read())
        router = opts.router if opts.router != "jsf" else spec.router
        cells = spec.cell_options(opts)
        if not cells:
            raise ValueError(f"fleet spec {opts.fleet_spec} lists no cells")
        return router, cells
    return opts.router, [opts] * max(opts.cells, 1)
