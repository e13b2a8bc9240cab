"""Readings that set a cell's correctness limits, on the chip, in one
process: for each seed, the served path's compared numbers over a short
window at the cell's own load (as a run computes them), and the control's:
the plain reference with every matrix product in float8_e4m3fn (the
precision below the configuration's bfloat16) put in the system's place,
compared with the float32 reference on the same prompts.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 8

The control is read on the first ``--controls`` seeds (3 by default).
Prints one line per seed and writes ``bench_out/control-<cell>.json``. The
benchmark's own runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def readings(cell, seeds, seconds, devices, controls=3) -> list:
    """[{seed, program: {number: value}, control: {...}}, ...]; the
    control is read on the first ``controls`` seeds."""
    import harness
    import traffic as traffic_mod
    c, t = cell.config, cell.traffic
    vocab = c["vocab_size"]
    eng = None
    out = []
    for i, seed in enumerate(seeds):
        if eng is None:
            eng = harness.build_engine(c, t, devices, seed)
            harness.warm_up(eng, t, vocab)
        else:
            harness.load_weights(eng, c, seed)
        w = harness.drive(eng, t, seed, seconds, vocab)
        harness.free_weights(eng)
        sample = harness.pick_sample(w["done"], c["check"]["sample"], seed)
        prompts = [traffic_mod.tokens(seed, r["rid"], r["seq"], vocab)
                   for r in sample]
        modes = ("f32", "fp8") if i < controls else ("f32",)
        t0 = time.perf_counter()
        refs = harness.reference_logits(c, seed, prompts, modes, devices[0])
        row = {"seed": seed, "requests": len(w["done"]),
               "failed": w["failed"], "sample": [r["seq"] for r in sample],
               "reference_s": time.perf_counter() - t0,
               "program": {}, "control": {}}
        for r, ref in zip(sample, refs):
            for side, got in (("program", r["result"]),
                              ("control", ref.get("fp8"))):
                if got is None:
                    continue
                for k, v in harness.compare(got, ref["f32"]).items():
                    row[side][k] = max(row[side].get(k, 0.0), v)
        harness.log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", type=int, default=3,
                    help="read the control on this many of the first seeds")
    args = ap.parse_args(argv)
    import harness
    cell = harness.Cell(args.workload)
    devices = harness.tpu_devices(cell)
    if devices is None:
        return 3
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = readings(cell, seeds, args.seconds, devices,
                    controls=args.controls)
    os.makedirs(harness.OUT, exist_ok=True)
    path = os.path.join(harness.OUT, f"control-{args.workload}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    for side in ("program", "control"):
        for k in ("logit_rel_err", "top1_gap"):
            vals = [r[side][k] for r in rows if k in r[side]]
            if vals:
                print(f"{side} {k}: min {min(vals)!r} max {max(vals)!r} "
                      f"over {len(vals)} seeds")
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
