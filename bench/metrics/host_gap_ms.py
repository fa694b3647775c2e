"""host_gap_ms: median over the window's decode-only ticks (no prompt token
fed) of the engine's own tick span, ``engine.tick``, less its ``engine.wait``
span, the block on the step's logits: the host's part of a decode tick.

Read from the engine's ring of tick records (``engine.tracer``,
``src/repro/serve/tracing.py``), keeping the ticks that ended inside the
window; a program without the ring reports nothing."""

import statistics


def read(run):
    tracer = getattr(getattr(run.rec, "engine", None), "tracer", None)
    if tracer is None:
        return None
    if not tracer.reaches(run.win.open):
        run.notes.append("host_gap_ms: the tick ring no longer reaches the "
                         "window's open")
        return None
    r = tracer.records(run.win.open, run.win.close)
    r = r[(r["prompt"] == 0) & (r["decode"] > 0)]
    if not len(r):
        return None
    return 1e3 * statistics.median((r["end"] - r["start"] - r["wait"]).tolist())
