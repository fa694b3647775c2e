"""admit_wait_p50_ms: median (nearest rank) over the requests due in the
window of the time from when each was due to when the engine first gave it
a slot (``Request.t_admit``, stamped in ``Engine._admit``).  Unlike
``queue_wait_p50_ms`` it leaves out the tick that follows admission.  A
request never admitted counts the whole wait until the run stopped
waiting; a program that does not stamp admission reports nothing."""


def read(run):
    recs = run.due_in_window()
    if not recs or not all(hasattr(r.req, "t_admit") for r in recs):
        return None
    waits = [((r.req.t_admit if r.req.t_admit is not None else run.win.stop)
              - r.due) * 1e3 for r in recs]
    return run.quantile(waits, 0.50)
