"""queue_wait_p50_ms: median (nearest rank) over the requests due in the
window of the time from when each was due to the end of the first tick
after which it held a slot.  A request never admitted counts the whole wait
until the run stopped waiting."""


def read(run):
    recs = run.due_in_window()
    if not recs:
        return None
    waits = [((r.admit if r.admit is not None else run.win.stop) - r.due) * 1e3
             for r in recs]
    return run.quantile(waits, 0.50)
