"""occupancy: mean share of the engine's slots holding a request, read from
``engine.slots`` after each tick of the window (%)."""


def read(run):
    ticks = [t for t in run.rec.ticks if run.win.open < t.end <= run.win.close]
    if not ticks:
        return None
    slots = run.cell.mix["engine"]["slots"]
    return 100.0 * sum(t.live for t in ticks) / (len(ticks) * slots)
