"""Share of the window the engine's host code holds the step: the sum over
the window's waves of the ``engine.step`` span minus its
``engine.device_wait`` (``block_until_ready``) span, over the window
(program spans, ``perf_counter`` clock, from the wave records)."""


def read(run):
    waves = [w for w in run.waves if "step" in w]
    if not waves or not run.window_s:
        return None
    host = sum(w["step"] - w["phases"]["device_wait"] for w in waves)
    return host / run.window_s
