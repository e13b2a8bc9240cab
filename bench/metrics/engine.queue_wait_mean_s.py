"""Mean time a request of the window waits before the engine admits it:
from when it was due (the harness's arrival, ``perf_counter`` clock) to the
``engine.admit`` that picks it into a batch (the request's ``t_admit``, a
program timestamp carried by the wave records). An open-loop arrival is
submitted only once the running ``step()`` returns, so the engine's own
``t_admit - t_submit`` would leave out the wait behind the running wave;
from ``due`` it is in."""


def read(run):
    admit = {}
    for w in run.waves:
        for rid, t in zip(w["rids"], w.get("t_admit", ())):
            admit[rid] = t
    found = [admit[r["rid"]] - r["due"] for r in run.requests
             if r["rid"] in admit]
    return sum(found) / len(found) if found else None
