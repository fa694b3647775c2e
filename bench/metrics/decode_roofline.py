"""decode_roofline: the least time of the traced decode-only steps over the
device time of the serving step's program in them (%).

A step's least time is the larger of its FLOPs over the peak FLOP/s and its
needed bytes over the peak bytes/s, as the configuration's family counts
them (``decode_step`` of ``bench/families/<family>.py``; for ``dense_gqa``
every weight once, the keys and values of each live row's context, and
those written for the new tokens).  The device time is that of the step
program's runs inside the benchmark's ``tick`` span (``bench/trace.py``)."""


def read(run):
    if run.trace is None or run.peak is None:
        return None
    step = run.trace["decode_step_s"]
    ctx = run.trace["decode_contexts"]
    least = device = 0.0
    bounds = set()
    for i, t in step.items():
        c = run.family.decode_step(run.cfg, ctx[i], run.peak)
        least += c["least_s"]
        device += t
        bounds.add(c["bound"])
    if device <= 0:
        return None
    run.notes.append(f"decode_roofline: {len(step)} steps, bound by "
                     f"{'/'.join(sorted(bounds))}")
    return 100.0 * least / device
