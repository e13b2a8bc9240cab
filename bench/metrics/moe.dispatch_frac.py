"""Share of the expert layers' device self time spent routing and
combining: the self time under ``layer.moe_route`` (router, top-k, sort,
gather) plus ``layer.moe_combine`` (un-sort, gate weighting, residual add)
over that and the grouped matmuls' (as ``kernel.moe_experts_roofline``
counts them) and the shared expert's (``layer.moe_shared``). None where
the program names none of them."""
import lookup
import scopes

DISPATCH = ("layer.moe_route", "layer.moe_combine")
SHARED = "layer.moe_shared"


def read(run):
    by_scope = scopes.scope_seconds(run)
    if by_scope is None:
        return None
    experts = lookup.module("metrics", "kernel.moe_experts_roofline")
    part = sum(s.get(k, 0.0) for s in by_scope.values() for k in DISPATCH)
    total = (part + sum(s.get(SHARED, 0.0) for s in by_scope.values())
             + experts.expert_seconds(run))
    return part / total if total > 0 else None
