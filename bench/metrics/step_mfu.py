"""The whole step's share of the chips' bf16 peak, in percent: the useful
operations of every request finished in the window (linear layers,
causal attention, the one output row; ``bench/flops.py``) over the window
(host clock, first due to last return) times chips times peak."""


def read(run):
    if not run.requests or run.peak is None:
        return None
    work = sum(run.flops.model_flops(run.config, r["seq"])
               for r in run.requests)
    span = max(r["done"] for r in run.requests) - run.t0
    least = work / run.peak["bf16_flops_per_s"]
    return 100.0 * run.flops.share(least, span * run.chips)
