"""Median wait of a request in the engine's queue: from when it was due to
the start of the ``step()`` that ran it (host clock)."""
import statistics


def read(run):
    waits = [r["start"] - r["due"] for r in run.requests]
    return statistics.median(waits) if waits else None
