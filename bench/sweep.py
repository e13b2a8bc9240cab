"""Rate sweep of an open-loop cell, on the chip, in one process: the served
engine is built and warmed once, then the cell's mix is offered at each
rate for ``--seconds``. For each rate it prints the TTFT median and 90th
percentile, the completed tokens per second, and whether the backlog grew:
how many requests were still unfinished when the window closed, and the
median queue wait of the last quarter of arrivals against the first.

    python3 bench/sweep.py --workload <cell> --rates 1.6,2.0,2.4 --seconds 30

The knee is the highest rate whose backlog does not grow over the window;
an open-loop cell's traffic file fixes its rate below it. The benchmark's
own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import harness
    cell = harness.Cell(args.workload)
    devices = harness.tpu_devices(cell)
    if devices is None:
        return 3
    c, t = cell.config, cell.traffic
    eng = harness.build_engine(c, t, devices, args.seed)
    harness.warm_up(eng, t, c["vocab_size"])
    rows = sweep(eng, c, t, [float(r) for r in args.rates.split(",")],
                 args.seconds, args.seed)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


def sweep(eng, c: dict, t: dict, rates, seconds: float, seed: int) -> list:
    import harness
    rows = []
    for k, rate in enumerate(rates):
        w = harness.drive(eng, dict(t, rate_per_s=rate), seed + k, seconds,
                          c["vocab_size"])
        done = sorted(w["done"], key=lambda r: r["due"])
        ttft = [r["done"] - r["due"] for r in done]
        e2e = harness.end_to_end(w, 0.0)
        q = max(1, len(done) // 4)
        wait = lambda rs: statistics.median(r["start"] - r["due"] for r in rs)
        end = w["t0"] + seconds
        row = {"rate": rate, "sent": len(w["requests"]),
               "unfinished_at_close": sum(
                   1 for r in w["requests"].values()
                   if r["due"] < end and r.get("done", 1e30) > end),
               "wait_first_q_s": wait(done[:q]),
               "wait_last_q_s": wait(done[-q:]),
               "ttft_p50_s": e2e.get("ttft_p50_s"),
               "ttft_p90_s": e2e.get("ttft_p90_s"),
               "tokens_per_s": e2e.get("prefill_tokens_per_s"),
               "offered_tokens_per_s": rate * statistics.mean(
                   r["seq"] for r in w["requests"].values()),
               "max_ttft_s": max(ttft), "failed": w["failed"]}
        harness.log(json.dumps(row))
        rows.append(row)
    return rows


if __name__ == "__main__":
    sys.exit(main())
