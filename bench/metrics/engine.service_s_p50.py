"""Median service time of a wave: ``JaxExecutor.run``'s own duration,
bounded by ``block_until_ready`` (host clock, ``eng.waves()``)."""
import statistics


def read(run):
    durs = [w["dur"] for w in run.waves]
    return statistics.median(durs) if durs else None
