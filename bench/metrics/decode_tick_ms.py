"""decode_tick_ms: median host-clock duration of the window's ticks that fed
no prompt token (every live row decoded one token).  A tick ends in
``block_until_ready`` on the step's logits, so its time includes the
device's."""

import statistics


def read(run):
    d = [t.end - t.start for t in run.rec.ticks
         if run.win.open < t.end <= run.win.close and not t.prefill]
    return 1e3 * statistics.median(d) if d else None
