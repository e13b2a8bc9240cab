"""The SSD scan's share of its roofline, in percent: the least time of the
window's scans (per Mamba layer and chunk, the larger of operations over
peak and bytes over HBM bandwidth; the layout's ``ssd_kernel``) over the
device self time of the ops under the ``layer.ssd`` scope, whatever
implements the scan. None where the layout counts no scan or the program
names no such scope."""
import lookup
import scopes

SCOPE = "layer.ssd"


def read(run):
    if run.trace is None or run.peak is None or not run.requests:
        return None
    layout = lookup.layout(run.config)
    by_scope = scopes.scope_seconds(run)
    if not hasattr(layout, "ssd_kernel") or by_scope is None:
        return None
    spent = sum(s.get(SCOPE, 0.0) for s in by_scope.values())
    if spent <= 0:
        return None
    m = run.traffic["num_chunks"]
    layers = layout.kinds(run.config).count("mamba")
    least = sum(layers * m * layout.least_seconds(
        *layout.ssd_kernel(run.config, r["seq"] // m), run.peak)
        for r in run.requests)
    return 100.0 * run.flops.share(least, spent)
