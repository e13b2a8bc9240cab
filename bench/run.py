"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the accelerator of the machine it is started on, with one process
for all the chips the cell asks for. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result. The last
line of standard output is the result as one JSON object; with ``--trace
0`` its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window. The compared
numbers of the correctness check, each with its limit, are the result's
last key and the last lines of standard error.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the .xplane.pb of a traced run to this path")
    args = ap.parse_args(argv)

    import harness
    cell = harness.Cell(args.workload)
    devices = harness.tpu_devices(cell)
    if devices is None:
        return 3
    harness.log(f"{args.workload}: {devices[0].device_kind} x {cell.chips}, "
                f"seed {args.seed}, {args.seconds:g} s, trace {args.trace}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T_START, args.keep_trace)
    for name, v in result["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
