"""Imbalance of the stages' work per pipeline tick: sum over ticks of (the
slowest chip's non-collective self time minus the chips' mean) over the
sum of the slowest chip's; the share of the lockstep tick the other stages
wait through."""
import scopes


def read(run):
    return scopes.tick_imbalance_frac(run)
