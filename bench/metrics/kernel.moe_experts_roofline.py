"""The held experts' grouped matmuls' share of their roofline, in percent:
the least time of the window's expert work (per layer and chunk, the
larger of operations over peak and bytes over HBM bandwidth; the layout's
``moe_experts_kernel``, 6 d f per held (token, pick) pair, the held
experts' weights read once) over the device self time of the grouped
matmuls: the ops under the ``layer.moe_experts`` scope, and the ops of
XLA's TPU ``ragged_dot`` (named ``ragged-dot...``), which carry no scope.
The pairs come from each wave's ``moe_held_picks`` counter (per layer,
summed over the wave's chunks) and are taken as spread evenly over the
chunks, which gives no more than the least time of any other spread. None
where the waves carry no counter or the program names no such op."""
import lookup
import scopes

SCOPE = "layer.moe_experts"
UNSCOPED_OPS = "ragged-dot"


def expert_seconds(run):
    """Device self seconds of the grouped matmuls; None without scopes."""
    table = scopes.op_table(run)
    if table is None:
        return None
    return sum(own * 1e-9 for rows in table.values()
               for name, _, _, own, scope, _ in rows
               if scope == SCOPE or (scope == scopes.UNSCOPED
                                     and name.startswith(UNSCOPED_OPS)))


def read(run):
    if run.trace is None or run.peak is None or not run.waves:
        return None
    layout = lookup.layout(run.config)
    if (not hasattr(layout, "moe_experts_kernel")
            or any("moe_held_picks" not in w for w in run.waves)):
        return None
    spent = expert_seconds(run)
    if not spent:
        return None
    least = 0.0
    for w in run.waves:
        m = len(w["chunks"])
        for picks in w["moe_held_picks"]:
            least += m * layout.least_seconds(
                *layout.moe_experts_kernel(run.config, picks / m), run.peak)
    return 100.0 * run.flops.share(least, spent)
