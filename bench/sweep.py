#!/usr/bin/env python3
"""Knee sweep of an open-loop mix: one engine, one window per offered rate.

    python3 bench/sweep.py --workload granite-3-2b-blast50.chat \
        --rates 0.4,0.5,0.6,0.7 --seconds 51 --seed 7

For each rate it prints the requests due in the window, those completed
per second in it against the rate offered, the TTFT of the requests due in
the window's first and second halves, and the queue at its close.  The
knee is the highest rate whose completions keep up with the rate offered
and whose TTFT does not drift up across the window; a mix's file holds 0.8
of it.  Used once when a mix is defined, not by benchmark runs.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    cell = R.load_cell(args.workload)
    R.find_chips(cell.chips)
    R.enable_compile_cache()
    compiles = R.CompileCount()
    arch, cfg = R.program_config(cell.cfg)
    engine = R.build_engine(arch, cell.mix, R.seed_key(args.seed), None)
    R.warm_up(engine)
    for rate in (float(r) for r in args.rates.split(",")):
        rec = R.Recorder(engine, cfg)
        win = R.drive(engine, rec, cell.mix, args.seed, args.seconds, False,
                      compiles, rate=rate, drain_s=0.0)
        run = R.Run(cell, cfg, rec, win, [])
        e2e = R.end_to_end(run)
        done = sum(1 for r in rec.reqs.values() if r.stamps
                   and r.req.done and run.in_window(r.stamps[-1]))
        due = run.due_in_window()
        half = win.open + win.seconds / 2
        ttft = [[1e3 * ((r.stamps[0] if r.stamps else win.stop) - r.due)
                 for r in due if (r.due < half) == first]
                for first in (True, False)]
        per_s = done / (win.close - win.open)
        print(json.dumps({"rate": rate, "due": len(due),
                          "done_per_s": per_s, "kept_up": per_s / rate,
                          "ttft_p50_ms_halves": [
                              R.nearest_rank(t, 0.5) if t else None
                              for t in ttft],
                          "queue_close": win.queue_at_close,
                          **{k: v for k, v in e2e.items()
                             if k != "setup_s"}}), flush=True)
        rec.close()
        for r in [s.req for s in engine.slots if s.req] + [
                q[2] for q in engine.queue]:
            engine.cancel(r.uid)
    return 0


if __name__ == "__main__":
    sys.exit(main())
