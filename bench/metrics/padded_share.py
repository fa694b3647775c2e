"""padded_share: share of the token columns the window's steps computed
that fed no token: 100 * (1 - tokens fed / sum of B * C) over the plain
(non-speculative) steps of the ticks that ended inside the window (%).

A step of B slots at chunk width C computes B * C columns whatever it feeds;
a decode row feeds one and a prefill row up to C.  Read from the engine's
ring of tick records (``engine.tracer``, ``src/repro/serve/tracing.py``); a
program without the ring reports nothing."""

import numpy as np


def read(run):
    tracer = getattr(getattr(run.rec, "engine", None), "tracer", None)
    if tracer is None:
        return None
    if not tracer.reaches(run.win.open):
        run.notes.append("padded_share: the tick ring no longer reaches the "
                         "window's open")
        return None
    r = tracer.records(run.win.open, run.win.close)
    r = r[~r["spec"]]
    cols = int(np.sum(r["B"].astype(np.int64) * r["C"]))
    if cols <= 0:
        return None
    fed = int(np.sum(r["prompt"].astype(np.int64) + r["decode"]))
    return 100.0 * (1.0 - fed / cols)
