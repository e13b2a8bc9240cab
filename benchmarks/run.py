"""Benchmark driver: one module per paper table/figure + the roofline report.

  PYTHONPATH=src python -m benchmarks.run [--quick]

Jobs whose backend prerequisites are unavailable are SKIPPED, not crashed,
and the SKIP line names the gating predicate so a log says exactly WHY a
job was gated. Job modules are imported lazily, exactly once, and only
for the jobs actually selected — ``--only kvstore`` no longer pays the
import cost (jax tracing setup included) of every other benchmark module.
"""
from __future__ import annotations

import argparse
import sys
import time
from importlib import import_module

# job name -> (module under benchmarks/, entrypoint attr, description,
#              gating predicate dotted name or None)
JOBS = {
    "sched": ("sched_throughput", "main",
              "Continuous chunk-level scheduling vs batch-synchronous", None),
    "attn_backend": ("attn_backend", "run",
                     "jnp vs pallas attention-backend comparison", None),
    "kvstore": ("kvstore", "run",
                "KV page store: max seq len vs kv_dtype + tier headroom",
                None),
    "kvstore_pipeline": ("kvstore", "pipeline_leg",
                         "Real-pipeline paged-pool bytes + wall time "
                         "(TP-sharded pool)", None),
    "fig6a": ("fig6a", "main",
              "Fig 6(a): E2E latency/throughput vs GPipe & Terapipe", None),
    "fig6b": ("fig6b", "main",
              "Fig 6(b): max sequence length vs Terapipe x #chunks", None),
    "fig1c": ("fig1c", "main",
              "Fig 1(c): WSC vs GPU-system communication advantage", None),
    "lbcp_ablation": ("lbcp_ablation", "main",
                      "LBCP ablation + stagger-collapse study", None),
    "kernels": ("kernels", "main",
                "Pallas kernel correctness + analytic TPU timing", None),
    "roofline": ("roofline_report", "main",
                 "Roofline report from the dry-run artifacts", None),
    "obs": ("obs_export", "run",
            "Merged Perfetto trace + metrics exporter sample artifacts",
            None),
    "calibration": ("calibration", "run",
                    "Cost-model calibration MAPE + health-sentinel overhead "
                    "(sim-backed, deterministic)", None),
}

_QUICK_AWARE = {"sched", "attn_backend", "kvstore", "kvstore_pipeline",
                "obs", "calibration"}


def _gate(predicate: str) -> bool:
    """Evaluate a dotted gating predicate from ``repro.compat``."""
    from repro import compat
    assert predicate.startswith("compat."), predicate
    return bool(getattr(compat, predicate.split(".", 1)[1])())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller SA budgets / fewer probes")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: " + ",".join(JOBS))
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(JOBS)
        if unknown:
            # a typo'd job name must not ride the "0 ran" path with the
            # all-SKIPPED message — name the bad names and the valid set
            print(f"ERROR: unknown job name(s) {sorted(unknown)}; "
                  f"valid: {','.join(JOBS)}")
            return 2

    rc = 0
    ran = skipped = 0
    modules = {}  # one import pass: each selected module imported ONCE
    for name, (mod_name, attr, desc, predicate) in JOBS.items():
        if only and name not in only:
            continue
        print(f"\n================ {name}: {desc} ================",
              flush=True)
        if predicate is not None and not _gate(predicate):
            skipped += 1
            print(f"[{name} SKIP: gated on {predicate}() == False — "
                  "installed jaxlib cannot partition partial-auto shard_map "
                  "(PartitionId); rerun on jax >= the jax.shard_map release]")
            continue
        if mod_name not in modules:
            modules[mod_name] = import_module(f"benchmarks.{mod_name}")
        fn = getattr(modules[mod_name], attr)
        ran += 1
        t0 = time.time()
        try:
            if name in _QUICK_AWARE:
                fn(quick=args.quick)
            else:
                fn()
            print(f"[{name} done in {time.time()-t0:.1f}s]")
        except Exception as e:  # noqa: BLE001
            rc = 1
            import traceback
            traceback.print_exc()
            print(f"[{name} FAILED: {e}]")
    if ran == 0:
        # every selected job was gated away (or --only matched nothing):
        # an empty artifact set must FAIL the caller, not ride a green exit
        # to the upload step
        print(f"\nERROR: 0 of {skipped} selected job(s) ran "
              f"({'all SKIPPED' if skipped else '--only matched no jobs'})",
              flush=True)
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
