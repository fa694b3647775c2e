"""prefill_tick_ms: median host-clock duration of the window's ticks that fed
prompt tokens (a prefill chunk, possibly beside decoding rows)."""

import statistics


def read(run):
    d = [t.end - t.start for t in run.rec.ticks
         if run.win.open < t.end <= run.win.close and t.prefill]
    return 1e3 * statistics.median(d) if d else None
