"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps the
events the reduction needs, in a plain form that can be saved as JSON:

    {"host":    [[name, start_ns, dur_ns, {stat: value}], ...],
     "devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}}}

Host events are the benchmark's own annotations (``HOST_SPANS``); device
events are those of the accelerator planes: one per operation ("XLA Ops")
and one per run of a compiled program ("XLA Modules").

``reduce`` computes, inside the host span named ``window``:
  * busy seconds: the union of the device's operation intervals, averaged
    over the chips, and the window's length;
  * the operations that took most device time (self time: a loop's less
    that of the operations inside it), by name, for those starting inside;
  * the idle gaps (window minus the union), each named by the innermost host
    span that covers its middle, summed by name;
  * for each host span ``tick`` whose index is in ``decode_ticks``, the
    device time of the serving step's program runs that fall inside it.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from collections import defaultdict

HOST_SPANS = {"window", "tick", "admit", "schedule", "step", "guardrail",
              "reset_row"}
STEP_PROGRAM = "prefill_chunk"      # the engine's jitted step
TOP = 10


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    host, devices, layout = [], {}, {}
    for plane in pd.planes:
        layout[plane.name] = {line.name: sum(1 for _ in line.events)
                              for line in plane.lines}
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append([ev.name, ev.start_ns, ev.duration_ns,
                                     {k: v for k, v in ev.stats}])
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if kind:
                    d[kind] = [[short(ev.name), ev.start_ns, ev.duration_ns]
                               for ev in line.events]
            if d["ops"] or d["modules"]:
                devices[plane.name] = d
    return {"host": host, "devices": devices, "layout": layout}


def short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12 = bf16[..]
    fusion(..)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def self_times(ops) -> list[tuple[str, float, float]]:
    """(name, start, self time) of nested operations: a loop's time less
    that of the operations inside it."""
    out, stack = [], []          # stack: [end, index into out]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= d
        out.append([name, s, d])
        stack.append([s + d, len(out) - 1])
    return [tuple(o) for o in out]


def dump(events: dict, path: str, ticks: int = 12) -> None:
    """Write the events of the window's first ``ticks`` ticks as JSON (a
    small recorded trace for the reduction's tests)."""
    host = events["host"]
    win = next(h for h in host if h[0] == "window")
    starts = sorted(h[1] + h[2] for h in host if h[0] == "tick")
    cut = starts[min(ticks, len(starts)) - 1] if starts else win[1] + win[2]
    keep = lambda evs: [e for e in evs if e[1] < cut]  # noqa: E731
    small = {"host": [[*win[:2], cut - win[1], win[3]]]
             + [h for h in keep(host) if h[0] != "window"],
             "devices": {p: {k: keep(v) for k, v in d.items()}
                         for p, d in events["devices"].items()},
             "layout": events["layout"]}
    with open(path, "w") as f:
        json.dump(small, f)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _Spans:
    """Host spans, searchable for the innermost one covering a time."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda h: h[1])
        self.starts = [h[1] for h in self.spans]
        self.longest = max((h[2] for h in self.spans), default=0)

    def innermost(self, t: float) -> str:
        best = None
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.starts[i] >= t - self.longest:
            name, s, d, _ = self.spans[i]
            if t < s + d and (best is None or d < best[1]):
                best = (name, d)
            i -= 1
        return best[0] if best else "outside any span"


def reduce(events: dict, decode_ticks: set) -> dict:
    host = events["host"]
    windows = [h for h in host if h[0] == "window"]
    if not windows:
        raise RuntimeError("the trace holds no 'window' span")
    _, w0, wd, _ = windows[0]
    w1 = w0 + wd
    spans = [h for h in host if h[0] != "window" and h[1] < w1
             and h[1] + h[2] > w0]
    index = _Spans(spans)
    busy, op_time, gaps = [], defaultdict(float), defaultdict(float)
    for dev in events["devices"].values():
        ops = dev["ops"] or dev["modules"]
        merged = union(((s, s + d) for _, s, d in ops), w0, w1)
        busy.append(sum(e - s for s, e in merged))
        for name, s, d in self_times(ops):
            if w0 <= s < w1:
                op_time[name] += d
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[index.innermost((s + e) / 2)] += e - s
    n = max(1, len(events["devices"]))
    ticks = {h[3].get("i"): (h[1], h[1] + h[2]) for h in spans
             if h[0] == "tick"}
    step = {}
    for i, (s, e) in ticks.items():
        if i not in decode_ticks:
            continue
        t = 0.0
        for dev in events["devices"].values():
            for name, ms, md in dev["modules"]:
                if STEP_PROGRAM in name and s <= ms + md / 2 < e:
                    t += md
        if t > 0:
            step[i] = t / n * 1e-9
    ns = 1e-9
    return {
        "window_s": wd * ns,
        "busy_s": sum(busy) / n * ns,
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v / n * ns] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:TOP]],
        "decode_step_s": step,
    }
