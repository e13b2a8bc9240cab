"""The paged pool kernel's share of its roofline, in percent: the least
time of every pool call that has earlier chunks to read (the larger of
operations over peak and bytes over HBM bandwidth, per call;
``bench/flops.py``) over the kernel's summed device time in the trace."""

KERNEL = "pool_attention_paged"   # the HLO custom call of the kernels/ops.py wrapper


def read(run):
    if run.trace is None or run.peak is None or not run.requests:
        return None
    spent = run.trace.op_seconds(lambda name: name == KERNEL)
    if spent <= 0:
        return None
    least = sum(run.flops.kernel_min_seconds(
        run.config, r["seq"], run.traffic["num_chunks"], run.peak)["pool"]
        for r in run.requests)
    return 100.0 * run.flops.share(least, spent)
