"""Share of the pipeline's tick compute spent on fill and drain ticks: per
chip and tick (the interval between the ends of consecutive
``transport.ring_shift`` collectives), non-collective device self time on
ticks whose chunk index ``t - stage`` lies outside ``[0, M)``, over all
tick compute of all chips."""
import scopes


def read(run):
    return scopes.bubble_frac(run)
