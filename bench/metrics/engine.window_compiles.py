"""Waves of the window in which the engine compiled, traced or loaded a
program: a jit-cache miss of the executor, or a JAX tracing, backend
compile or persistent-cache load event while the wave ran (the wave
record's ``compiled``)."""


def read(run):
    marked = [w["compiled"] for w in run.waves if "compiled" in w]
    return sum(marked) if marked else None
