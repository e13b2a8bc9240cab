"""Share of the traced window in which a chip ran a collective and nothing
else (the most exposed chip): inter-chip transfers the pipeline does not
hide behind compute."""


def read(run):
    if run.trace is None:
        return None
    exposed = run.trace.exposed_collective_s()
    return max(exposed.values()) / run.trace.window_s
