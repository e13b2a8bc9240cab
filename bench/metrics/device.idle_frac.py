"""Share of the traced window in which no operation ran on a chip (the
idlest chip of the cell): 1 - union of the device's op intervals / window."""


def read(run):
    if run.trace is None:
        return None
    return 1.0 - min(run.trace.busy_s().values()) / run.trace.window_s
