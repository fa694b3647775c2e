"""Host spans and per-tick counters of the serving engine.

Each ``Engine`` owns one ``Tracer`` (``engine.tracer``).  A tick opens the
span ``engine.tick``, and inside it the engine's phases open theirs:

  engine.admit      deadlines, shedding, admission (incl. row resets)
  engine.schedule   mode pick, token budget, page allocation, host operands
  engine.dispatch   the jitted step call(s) and their operand uploads
  engine.wait       blocking on the step's outputs
  engine.guardrail  the per-row logit health check
  engine.sample     key split, argmax, temperature draws, copies to host
  engine.emit       the per-row loop: positions, registration, emission,
                    finishing

Every span does two things.  It enters a ``jax.profiler.TraceAnnotation``
under its name (the tick a ``StepTraceAnnotation`` numbered by the engine's
step counter), so a profiler trace shows it on the profiler's clock beside
the device's operations; with no profiler recording this costs under a
microsecond.  And it adds its duration, read with ``time.perf_counter`` (the
clock of ``Request.t_*``), to the tick's record.  Phase times are exclusive:
a span opened inside another (a temperature draw inside the emit loop)
pauses its parent.

A tick that runs a step appends one record to a fixed-size ring (``RING``
records, O(1) a tick).  A record holds the tick's start and end, the seconds
of each phase, the step's shape ``B`` x ``C``, the prompt and decode tokens
it fed, whether it was a speculative round, the rows still live after the
tick, the queue depth after admission, and the programs JAX compiled or
fetched from its persistent cache during the tick with their compile
seconds.  ``records(since, until)`` and ``reaches(t)`` read it.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

PHASES = ("admit", "schedule", "dispatch", "wait", "guardrail", "sample",
          "emit")
RING = 32_768
RECORD = np.dtype(
    [("start", "f8"), ("end", "f8")] + [(p, "f8") for p in PHASES]
    + [("B", "i4"), ("C", "i4"), ("prompt", "i4"), ("decode", "i4"),
       ("spec", "?"), ("live", "i4"), ("queue", "i4"), ("compiles", "i4"),
       ("compile_s", "f8")])

_PHASE_INDEX = {p: i for i, p in enumerate(PHASES)}
_clock = time.perf_counter

# programs compiled or fetched from the persistent cache: JAX's monitoring
# listeners are process-wide, so the count is too (registered once, by the
# first tracer; compiles may run on any thread)
_COMPILES = [0, 0.0]
_compiles_lock = threading.Lock()
_listening = False


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        with _compiles_lock:
            _COMPILES[0] += 1
            _COMPILES[1] += secs


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        with _compiles_lock:
            _COMPILES[0] += 1


def _listen() -> None:
    global _listening
    with _compiles_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


class _Span:
    __slots__ = ("tracer", "i", "ann", "parent", "t0")

    def __init__(self, tracer: Tracer, i: int, name: str):
        self.tracer, self.i = tracer, i
        self.ann = TraceAnnotation(name)

    def __enter__(self):
        tr = self.tracer
        self.parent, tr._open = tr._open, self
        self.ann.__enter__()
        self.t0 = _clock()

    def __exit__(self, *exc):
        d = _clock() - self.t0
        tr = self.tracer
        tr._phase[self.i] += d
        if self.parent is not None:
            self.parent.t0 += d          # the parent's time stays exclusive
        tr._open = self.parent
        self.ann.__exit__(*exc)
        return False


class _Tick:
    __slots__ = ("tracer", "slots", "ann", "t0", "c0", "s0")

    def __init__(self, tracer: Tracer, step_num: int, slots):
        self.tracer, self.slots = tracer, slots
        self.ann = StepTraceAnnotation("engine.tick", step_num=step_num)

    def __enter__(self):
        tr = self.tracer
        tr._phase = [0.0] * len(PHASES)
        tr._step = None
        tr._queue = 0
        self.ann.__enter__()
        self.c0, self.s0 = _COMPILES
        self.t0 = _clock()

    def __exit__(self, *exc):
        t1 = _clock()
        tr = self.tracer
        if tr._step is not None:
            live = sum(1 for s in self.slots if s.req is not None)
            tr._append((self.t0, t1, *tr._phase, *tr._step, live, tr._queue,
                        _COMPILES[0] - self.c0, _COMPILES[1] - self.s0))
        self.ann.__exit__(*exc)
        return False


class Tracer:
    """The engine's spans and its ring of per-tick records."""

    def __init__(self, size: int = RING):
        _listen()
        self.size = size
        self._ring = np.zeros(size, RECORD)
        self.n = 0                      # records ever appended
        self._phase = [0.0] * len(PHASES)
        self._open: _Span | None = None
        self._step = None
        self._queue = 0

    # -- writing (the engine) ---------------------------------------------

    def tick(self, step_num: int, slots) -> _Tick:
        """The span of one tick; ``slots`` are counted live at its end."""
        return _Tick(self, step_num, slots)

    def span(self, phase: str) -> _Span:
        return _Span(self, _PHASE_INDEX[phase], "engine." + phase)

    def queued(self, depth: int) -> None:
        """The queue's depth once admission is done."""
        self._queue = depth

    def step(self, B: int, C: int, prompt: int, decode: int,
             spec: bool = False) -> None:
        """The tick runs a step of shape (B, C) feeding ``prompt`` prompt
        and ``decode`` decode tokens: the tick leaves a record."""
        self._step = (B, C, prompt, decode, spec)

    def _append(self, rec: tuple) -> None:
        self._ring[self.n % self.size] = rec
        self.n += 1

    # -- reading ------------------------------------------------------------

    def records(self, since: float | None = None,
                until: float | None = None) -> np.ndarray:
        """The records still in the ring, oldest first, whose tick ended in
        (``since``, ``until``] (either bound may be left open)."""
        if self.n <= self.size:
            out = self._ring[:self.n].copy()
        else:
            i = self.n % self.size
            out = np.concatenate((self._ring[i:], self._ring[:i]))
        keep = np.ones(len(out), bool)
        if since is not None:
            keep &= out["end"] > since
        if until is not None:
            keep &= out["end"] <= until
        return out[keep]

    def reaches(self, t: float) -> bool:
        """Whether every tick that ended after ``t`` is still in the ring."""
        return (self.n <= self.size
                or self._ring[self.n % self.size]["end"] <= t)

    def summary(self, last: int = 1000) -> dict:
        """Medians over the last ``last`` records (ms), the share of the
        computed token columns that were padding (%, speculative rounds
        left out), and the programs compiled or fetched."""
        r = self.records()[-last:]
        if not len(r):
            return {"ticks": 0}
        plain = r[~r["spec"]]
        cols = float(np.sum(plain["B"].astype(np.int64) * plain["C"]))
        fed = float(np.sum(plain["prompt"] + plain["decode"]))
        tick = r["end"] - r["start"]
        return {"ticks": int(len(r)),
                "tick_ms": 1e3 * float(np.median(tick)),
                "wait_ms": 1e3 * float(np.median(r["wait"])),
                "host_gap_ms": 1e3 * float(np.median(tick - r["wait"])),
                "padded_share": (100.0 * (1.0 - fed / cols) if cols
                                 else None),
                "compiles": int(r["compiles"].sum()),
                "compile_s": float(r["compile_s"].sum())}
