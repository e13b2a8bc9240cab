import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
#   512 placeholder host devices back the 16x16 pod and 2x16x16 multi-pod
#   meshes for lower()+compile() — no arrays are ever materialized.
"""Multi-pod dry-run: lower + compile EVERY (arch x shape x mesh) cell and
record memory_analysis / cost_analysis / roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape prefill_32k
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
  python -m repro.launch.dryrun --all --jobs 4        # subprocess per cell

Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>__<mode>.json — consumed by
EXPERIMENTS.md §Dry-run / §Roofline and benchmarks/roofline_report.py.

A CPU-only tool: it fakes 512 host devices at import and ``--jobs`` spawns
one child process per cell, so never run it on a machine with a chip (a
chip belongs to one process; ``chip_smoke.py`` and ``serve`` are the chip
paths). Run it with ``JAX_PLATFORMS=cpu``.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import List, Optional, Tuple

ARCHS = (
    "whisper-small", "qwen3-8b", "stablelm-3b", "granite-3-2b", "qwen3-14b",
    "granite-moe-3b-a800m", "qwen2-moe-a2.7b", "llava-next-34b",
    "zamba2-7b", "mamba2-130m",
)
SHAPE_NAMES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")


def run_cell(arch: str, shape_name: str, mesh_kind: str, mode: str,
             out_dir: str, attn_backend: str = "jnp",
             kv_dtype: str = "auto", kv_page_tokens: int = 0,
             pool_backend: str = "auto", tp_lowering: str = "auto",
             calibrated_profile: Optional[str] = None) -> dict:
    from repro import compat
    from repro.configs.base import SHAPES, get_config
    from repro.launch.cells import SkipCell, build_cell
    from repro.launch.mesh import make_topology
    from repro.roofline.analysis import analyze_lowered

    from repro.configs.base import RunConfig

    topo = make_topology(multi_pod=(mesh_kind == "multipod"))
    chips = topo.mesh.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "mode": mode,
           "chips": chips, "attn_backend": attn_backend,
           "pool_backend": pool_backend, "kv_dtype": kv_dtype, "ok": False}
    t0 = time.time()
    try:
        if mode == "mocap_opt":
            # the beyond-paper optimized lowering (§Perf): kv_split attention
            # + sequence-parallel residual + EP for MoE + compact host scan
            run = RunConfig(num_stages=topo.num_stages,
                            attn_sharding="kv_split",
                            attn_backend=attn_backend,
                            pool_backend=pool_backend, kv_dtype=kv_dtype,
                            kv_page_tokens=kv_page_tokens,
                            tp_lowering=tp_lowering)
            cell = build_cell(arch, shape_name, topo, mode="mocap", run=run)
        else:
            run = RunConfig(num_stages=topo.num_stages,
                            attn_backend=attn_backend,
                            pool_backend=pool_backend, kv_dtype=kv_dtype,
                            kv_page_tokens=kv_page_tokens,
                            tp_lowering=tp_lowering)
            cell = build_cell(arch, shape_name, topo, mode=mode, run=run)
    except SkipCell as e:
        rec.update(ok=True, skipped=True, reason=str(e))
        return rec
    if cell.meta.get("wire_model"):
        # §3.4 analytic per-run wire bytes (core.transport.analytic_wire_
        # bytes) — the runtime CollectiveLedger is pinned to this model
        # within 1% by tests/test_transport.py
        rec["wire_model"] = cell.meta["wire_model"]
        rec["tp_lowering"] = cell.meta["plan"].tp_lowering
        # tick x stage slot-occupancy profile off the same plan — the
        # device StageTelemetry counters are pinned to this analytic twin
        # by tests/test_obs.py
        from repro.obs.telemetry import occupancy_model
        rec["occupancy_model"] = occupancy_model(cell.meta["plan"])
        if calibrated_profile:
            # per-(chunk, stage) calibration residuals + how far the
            # measured profile moved this cell's predicted chunk costs —
            # recorded NEXT TO wire_model / occupancy_model (obs.calibrate)
            from repro.core import costmodel as _cm
            from repro.core import mbkr as _mb
            from repro.obs import calibrate as _cal
            plan = cell.meta["plan"]
            sm = _cm.StageModel.build(get_config(arch), plan.num_stages, 1)
            mplan = (_mb.plan(plan.num_chunks, plan.num_stages)
                     if plan.mode == "mocap" else None)
            rec["calibration"] = _cal.calibration_record(
                sm, [plan.chunk_len] * plan.num_chunks, _cm.WSC_PAPER,
                calibrated_profile, mbkr_plan=mplan)
    try:
        with compat.set_mesh(cell.meta.get("mesh", topo.mesh)):
            lowered = cell.lower()
            rec["lower_s"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = time.time() - t1
            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                             + mem.temp_size_in_bytes
                                             + mem.output_size_in_bytes),
            }
            cfg = get_config(arch)
            terms = analyze_lowered(lowered, compiled, cfg,
                                    SHAPES[shape_name], chips)
            rec["roofline"] = terms.to_dict()
            rec["ok"] = True
            rec["summary"] = terms.summary()
    except Exception as e:  # noqa: BLE001 — a failed cell is a data point
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def cell_modes(shape_name: str) -> Tuple[str, ...]:
    # prefill lowers the paper technique (faithful + optimized) AND the
    # conventional baseline as first-class modes
    if shape_name == "prefill_32k":
        return ("mocap", "baseline_tp", "mocap_opt")
    return ("auto",)


def save(rec: dict, out_dir: str) -> str:
    os.makedirs(os.path.join(out_dir, rec["mesh"]), exist_ok=True)
    path = os.path.join(out_dir, rec["mesh"],
                        f"{rec['arch']}__{rec['shape']}__{rec['mode']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    return path


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=("pod", "multipod", "both"))
    ap.add_argument("--mode", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1,
                    help="run cells in parallel subprocesses")
    ap.add_argument("--attn-backend", default="jnp",
                    choices=("jnp", "pallas"),
                    help="attention backend for pipeline modes "
                         "(core.attention registry)")
    ap.add_argument("--pool-backend", default="auto",
                    choices=("auto", "jnp", "pallas", "paged"),
                    help="backend for pool-sourced partials (own-pool scan "
                         "+ fetch/qship); auto follows --attn-backend; "
                         "paged = gather-free ragged pool kernel")
    ap.add_argument("--tp-lowering", default="auto",
                    choices=("auto", "manual"),
                    help="TP lowering for pipeline modes (core.transport): "
                         "auto = GSPMD partial-auto (manual fallback on old "
                         "jaxlib); manual = explicit transport psums")
    ap.add_argument("--kv-dtype", default="auto",
                    choices=("auto", "bfloat16", "int8", "fp8"),
                    help="KV page-store codec for pipeline modes "
                         "(repro.kvstore; changes lowered pool bytes)")
    ap.add_argument("--kv-page-tokens", type=int, default=0,
                    help="tokens per KV page (0 = one page per chunk)")
    ap.add_argument("--calibrated-profile", default=None,
                    help="calibrated-profile JSON (obs.calibrate): records per-(chunk, stage) fit "
                         "residuals and the nominal-vs-calibrated predicted "
                         "chunk costs next to wire_model/occupancy_model")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args(argv)

    meshes = ("pod", "multipod") if args.mesh == "both" else (args.mesh,)
    cells: List[Tuple[str, str, str, str]] = []
    archs = ARCHS if (args.all or not args.arch) else (args.arch,)
    shapes = SHAPE_NAMES if (args.all or not args.shape) else (args.shape,)
    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                for mode in (cell_modes(shape) if args.mode is None
                             else (args.mode,)):
                    cells.append((arch, shape, mesh, mode))

    if args.jobs > 1:
        return _run_parallel(cells, args.out, args.jobs, args.attn_backend,
                             args.kv_dtype, args.kv_page_tokens,
                             args.pool_backend, args.tp_lowering,
                             args.calibrated_profile)

    failures = 0
    for arch, shape, mesh, mode in cells:
        rec = run_cell(arch, shape, mesh, mode, args.out, args.attn_backend,
                       args.kv_dtype, args.kv_page_tokens, args.pool_backend,
                       args.tp_lowering, args.calibrated_profile)
        path = save(rec, args.out)
        status = ("SKIP" if rec.get("skipped") else
                  "OK" if rec["ok"] else "FAIL")
        extra = rec.get("summary", rec.get("reason", rec.get("error", "")))
        print(f"[{status:4}] {mesh:8} {arch:22} {shape:12} {mode:12} "
              f"{extra}", flush=True)
        failures += 0 if rec["ok"] else 1
    return 1 if failures else 0


def _run_parallel(cells, out_dir: str, jobs: int,
                  attn_backend: str = "jnp", kv_dtype: str = "auto",
                  kv_page_tokens: int = 0, pool_backend: str = "auto",
                  tp_lowering: str = "auto",
                  calibrated_profile: Optional[str] = None) -> int:
    procs: List[Tuple[subprocess.Popen, tuple]] = []
    pending = list(cells)
    failures = 0

    def launch(cell):
        arch, shape, mesh, mode = cell
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--mesh", mesh, "--mode", mode,
               "--attn-backend", attn_backend, "--pool-backend", pool_backend,
               "--kv-dtype", kv_dtype, "--tp-lowering", tp_lowering,
               "--kv-page-tokens", str(kv_page_tokens), "--out", out_dir]
        if calibrated_profile:
            cmd += ["--calibrated-profile", calibrated_profile]
        return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    while pending or procs:
        while pending and len(procs) < jobs:
            cell = pending.pop(0)
            procs.append((launch(cell), cell))
        done = [i for i, (p, _) in enumerate(procs) if p.poll() is not None]
        for i in sorted(done, reverse=True):
            p, cell = procs.pop(i)
            out = p.stdout.read() if p.stdout else ""
            print(out.strip(), flush=True)
            failures += 1 if p.returncode else 0
        time.sleep(0.3)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
