#!/usr/bin/env python3
"""Serving benchmark: runs one cell of BENCHMARK.json on the chip it is
started on, and prints one JSON result line.

    python3 bench/run.py --workload <config>.<mix> --seed N --seconds S \
        --trace 0|1

A cell is a configuration (``bench/configs/<name>.json``) under a traffic
mix (``bench/traffic/<name>.json``); both are found by the names the cell's
entry in BENCHMARK.json gives.  The run:

1. finds the chips (a CPU, or fewer chips than the cell asks for, ends the
   run with exit code 3 and no result), and keeps JAX's persistent
   compilation cache at ``$JAX_COMPILATION_CACHE_DIR`` or else at
   ``<checkout>/.jax_cache``;
2. builds the program's model and engine at the configuration's sizes, as
   its family (``bench/families/<family>.py``) checks them against the
   program, with the weights made on the chip in one jitted call from
   ``--seed``, warms every step width the mix can use, and drives a short
   burst of requests through the engine so that nothing compiles in the
   window;
3. replays the mix open-loop (``traffic.py``): requests are submitted
   between ``Engine.tick`` calls once due, and every emitted token is
   stamped when the tick that produced it returns;
4. measures ``--seconds`` seconds.  With ``--trace 0`` it reports the cell's
   end-to-end metrics; with ``--trace 1`` it records a profiler trace of the
   last ``TRACE_S`` seconds of the window and reports the per-layer metrics,
   each computed by its reader in ``bench/metrics/``;
5. frees the program, then recomputes a sample of the requests the window
   finished with the plain float32 reference (``bench/reference/<family>.py``)
   and checks the mean and the 99th percentile of the gaps between a served
   token's logit and the reference's best logit against the cell's limits
   (``bench/limits/<cell>.json``).

The last line of standard output is the result; the last lines of standard
error name each number compared and its limit.

``--control int8`` serves with the program's own int8 weight path switched
on (the lower-precision control of the correctness check), and ``--fault``
breaks the timed path on purpose; neither is used by a benchmark run.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the benchmark's modules are imported as the package ``bench`` (its
# directory must not shadow the standard library's ``trace``)
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import counts, load, traffic  # noqa: E402
from bench import trace as trace_reduce  # noqa: E402

TRACE_S = 8.0          # traced span: the last seconds of the window
DRAIN_S = 60.0         # wait after the window for first tokens still due
SAMPLE_TOKENS = 384    # served tokens the correctness sample reaches
SAMPLE_MIN, SAMPLE_ROWS = 4, 8   # requests in the sample: at least, at most
WARM_UID = 1 << 40     # uids of the warm-up burst


class NoChip(SystemExit):
    pass


# ---------------------------------------------------------------------------
# The cell: configuration, mix, metrics and limits, all found by name.
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list
    limits: dict
    root: Path = ROOT       # the checkout whose bench/ holds the cell's files


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / entry["file"]).read_text())
    mix = traffic.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    mine = lambda ms: [m for m in ms  # noqa: E731
                       if workload in m.get("workloads", [workload])]
    limits = json.loads(
        (root / "bench" / "limits" / f"{workload}.json").read_text())
    return Cell(workload, w["chips"], cfg, mix, mine(bench["end_to_end"]),
                mine(bench["per_layer"]), limits, root)


def find_chips(n: int):
    """The first ``n`` accelerator devices, or exit 3 without a result."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < n:
        print(f"bench: this cell needs {n} accelerator chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise NoChip(3)
    return devs[:n]


def enable_compile_cache(root: Path = ROOT) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def seed_key(seed: int):
    """The run's root key: all 64 bits of the seed count."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


# ---------------------------------------------------------------------------
# The system under test.
# ---------------------------------------------------------------------------


def program_config(cfg: dict, reduced: bool = False):
    """The program's ArchConfig and the run's configuration dictionary, from
    the configuration's family (``bench/families/<family>.py``)."""
    return counts.family(cfg).program_config(cfg, reduced)


def build_engine(arch, mix: dict, key, control: str | None):
    import jax
    from repro.models import build_model
    from repro.quant import QuantConfig
    from repro.serve import Engine, EngineConfig, MemoryConfig, SchedulerConfig

    e = mix["engine"]
    model = build_model(arch)
    params = jax.block_until_ready(jax.jit(model.init)(key))
    config = EngineConfig(
        scheduler=SchedulerConfig(slots=e["slots"], chunk_size=e["chunk"],
                                  policy="fifo"),
        memory=MemoryConfig(max_len=e["max_len"]),
        quant=QuantConfig(weights=control) if control else None)
    return Engine(model, params, config)


def warm_up(engine) -> None:
    """Compile (or load) every program the window can run: the step at each
    power-of-two width up to the chunk, then a short burst through the whole
    tick path (admission, row reset, guardrail, sampling, release)."""
    import jax
    import jax.numpy as jnp
    from repro.serve import Request

    B, C = engine.B, 1
    zeros = jnp.zeros((B,), jnp.int32)
    while True:
        # keep the returned cache: a step may consume (donate) its input
        logits, engine.cache = engine._step(
            engine.params, engine.cache, jnp.zeros((B, C), jnp.int32), zeros,
            zeros)
        jax.block_until_ready(logits)
        if C >= engine.chunk:
            break
        C *= 2
    for i in range(2):
        engine.submit(Request(uid=WARM_UID + i, prompt=[1, 2, 3][:i + 2],
                              max_new_tokens=3))
    while engine.queue or any(s.req is not None for s in engine.slots):
        engine.tick()


class CompileCount:
    """Programs JAX compiled or fetched from its persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


# ---------------------------------------------------------------------------
# The measured loop.
# ---------------------------------------------------------------------------


@dataclass
class ReqRec:
    uid: int
    due: float
    submit: float
    req: object
    admit: float | None = None
    stamps: list = field(default_factory=list)
    fed: int = 0            # tokens of this request the model has consumed


@dataclass
class Tick:
    start: float
    end: float
    prefill: bool           # fed prompt tokens
    live: int               # slots holding a request after the tick
    flops: float            # model FLOPs of the tokens fed and sampled
    contexts: list          # decode-only tick: each live row's context
    index: int | None       # position in the window (None before it)
    width: int              # the step's chunk width (power of two)
    ended: int              # requests that finished in the tick


class Recorder:
    """Per-request and per-tick records, read from the engine after each
    tick returns (the engine stamps only the first token itself)."""

    def __init__(self, engine, cfg: dict, family=None):
        self.engine, self.cfg = engine, cfg
        self.family = family or counts.family(cfg)
        self.gc = []                       # (start, seconds) of collections
        self._gc_at = None
        gc.callbacks.append(self._gc)
        self.reqs: dict[int, ReqRec] = {}
        self.ticks: list[Tick] = []
        self.n_finished = len(engine.finished)
        self.head = self.family.head_flops(cfg)

    def _gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_at = time.perf_counter()
        elif self._gc_at is not None:
            self.gc.append((self._gc_at, time.perf_counter() - self._gc_at))

    def close(self) -> None:
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)

    def submitted(self, plan, req, due: float, now: float) -> None:
        self.reqs[plan.uid] = ReqRec(plan.uid, due, now, req)

    def tick(self, start: float, end: float, prefill_before: int,
             index: int | None) -> None:
        eng = self.engine
        touched = {}
        for s in eng.slots:
            if s.req is not None and s.req.uid in self.reqs:
                touched[s.req.uid] = s.pos
        for r in eng.finished[self.n_finished:]:
            if r.uid in self.reqs:
                touched[r.uid] = len(r.prompt) + len(r.output) - 1
        ended = len(eng.finished) - self.n_finished
        self.n_finished = len(eng.finished)
        flops, contexts, most = 0.0, [], 1
        for uid, fed in touched.items():
            rec = self.reqs[uid]
            if rec.admit is None:
                rec.admit = end
            new = len(rec.req.output) - len(rec.stamps)
            rec.stamps.extend([end] * new)
            flops += (self.family.span_flops(self.cfg, rec.fed, fed)
                      + new * self.head)
            if fed > rec.fed:
                contexts.append(fed)
                most = max(most, fed - rec.fed)
            rec.fed = fed
        prefill = eng.stats["prefill_tokens"] > prefill_before
        live = sum(1 for s in eng.slots if s.req is not None)
        self.ticks.append(Tick(start, end, prefill, live, flops,
                               [] if prefill else contexts, index,
                               1 << (most - 1).bit_length(), ended))


def annotate(engine) -> None:
    """Host spans around the calls into each layer of the engine, so that
    the trace can say what the host was doing in each idle gap."""
    from jax.profiler import TraceAnnotation

    def wrap(name, fn):
        def spanned(*a, **k):
            with TraceAnnotation(name):
                return fn(*a, **k)
        return spanned

    for attr, name in (("_admit", "admit"), ("_schedule", "schedule"),
                       ("_step", "step"), ("_row_health", "guardrail"),
                       ("_reset_row", "reset_row")):
        setattr(engine, attr, wrap(name, getattr(engine, attr)))


@dataclass
class Window:
    open: float = 0.0
    close: float = 0.0
    seconds: float = 0.0
    setup_s: float = 0.0
    trace_dir: str | None = None
    lateness: list = field(default_factory=list)
    compiles: int = 0
    stop: float = 0.0       # when the run stopped waiting for tokens
    queue_at_open: int = 0
    queue_at_close: int = 0


def drive(engine, rec: Recorder, mix: dict, seed: int, seconds: float,
          trace: bool, compiles: CompileCount, rate: float | None = None,
          drain_s: float = DRAIN_S) -> Window:
    """Replay the mix open-loop and measure ``seconds`` seconds of it."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.serve import Request

    backlog = mix["arrivals"]["kind"] == "backlog"
    plans = traffic.schedule(mix, seed, rec.cfg["vocab_size"], seconds,
                             rate=rate)
    nxt = next(plans)
    win = Window(seconds=seconds)
    clock = time.perf_counter
    t0 = clock()
    preroll_end = t0 + mix["preroll_s"]
    t_open = t_close = trace_at = None
    window_span = tick_no = None
    while True:
        now = clock()
        while t0 + nxt.due_s <= now and (
                not backlog or len(engine.queue) < engine.B):
            req = Request(uid=nxt.uid, prompt=nxt.prompt,
                          max_new_tokens=nxt.max_new)
            engine.submit(req)
            rec.submitted(nxt, req, t0 + nxt.due_s, now)
            win.lateness.append(now - (t0 + nxt.due_s))
            nxt = next(plans)
        busy = [s.req is not None for s in engine.slots]
        if t_open is None and now >= preroll_end and (not backlog
                                                       or all(busy)):
            # an open-loop window opens when its first request is due
            t_open = now if backlog else preroll_end
            win.setup_s = time.time() - T_PROCESS - (now - t_open)
            win.queue_at_open = len(engine.queue)
            c_open = compiles.n
            trace_at = t_open + max(0.0, seconds - TRACE_S)
            tick_no = 0
        if trace and t_open is not None and window_span is None and \
                now >= trace_at and t_close is None:
            win.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(win.trace_dir)
            window_span = TraceAnnotation("window")
            window_span.__enter__()
        if t_open is not None and t_close is None and now >= t_open + seconds:
            t_close = now
            win.compiles = compiles.n - c_open
            win.queue_at_close = len(engine.queue)
            if window_span is not None:
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
            if backlog:
                break
        if t_close is not None and (now > t_close + drain_s or all(
                r.stamps for r in rec.reqs.values()
                if t_open <= r.due < t_open + seconds)):
            break
        if not any(busy) and not engine.queue:
            time.sleep(max(0.0, min(t0 + nxt.due_s - clock(), 0.05)))
            continue
        before = engine.stats["prefill_tokens"]
        start = clock()
        if window_span is not None and t_close is None:
            with TraceAnnotation("tick", i=tick_no):
                engine.tick()
        else:
            engine.tick()
        end = clock()
        rec.tick(start, end, before, tick_no)
        if tick_no is not None:
            tick_no += 1
    win.open, win.close, win.stop = t_open, t_close, clock()
    return win


# ---------------------------------------------------------------------------
# End-to-end metrics (host clock).
# ---------------------------------------------------------------------------


def nearest_rank(values, q: float) -> float:
    """The q-quantile of ``values`` by nearest rank."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def end_to_end(run) -> dict:
    w, recs = run.win, run.rec.reqs.values()
    in_window = run.in_window
    out = {}
    names = {m["name"] for m in run.cell.end_to_end}
    if "output_tok_s" in names:
        n = sum(1 for r in recs for t in r.stamps if in_window(t))
        out["output_tok_s"] = n / (w.close - w.open)
    if "ttft_p50_ms" in names:
        ttft = [((r.stamps[0] if r.stamps else w.stop) - r.due) * 1e3
                for r in run.due_in_window()]
        out["ttft_p50_ms"] = nearest_rank(ttft, 0.50)
    out["setup_s"] = w.setup_s
    return out


def attempted(run) -> list[ReqRec]:
    """Offline: requests that held a slot in the window; online: requests
    due in it."""
    w = run.win
    if run.cell.mix["arrivals"]["kind"] == "backlog":
        return [r for r in run.rec.reqs.values()
                if r.admit is not None and r.admit <= w.close
                and (r.req.t_done is None or r.req.t_done > w.open)]
    return run.due_in_window()


ERRORS = ("error", "numeric_error", "capacity", "shed", "deadline")


# ---------------------------------------------------------------------------
# Per-layer metrics: one reader per metric in bench/metrics/.
# ---------------------------------------------------------------------------


def reader(name: str, root: Path = ROOT):
    """``bench/metrics/<name>.py``, or the file of the quantity the name
    splits from (``decode_tick_ms.online`` -> ``decode_tick_ms.py``)."""
    for stem in (name, name.split(".")[0]):
        if (root / "bench" / "metrics" / f"{stem}.py").exists():
            return load("metrics", stem, root).read
    raise SystemExit(f"no reader for per-layer metric {name!r}")


def per_layer(run) -> dict:
    out = {}
    for m in run.cell.per_layer:
        v = reader(m["name"], run.cell.root)(run)
        if v is not None:
            out[m["name"]] = v
    return out


# ---------------------------------------------------------------------------
# Correctness: served tokens against the plain reference.
# ---------------------------------------------------------------------------


def sample(run, seed: int) -> list[ReqRec]:
    """Requests that the window finished, drawn from the seed: the longest
    always, then random others until the sample holds SAMPLE_TOKENS served
    tokens."""
    done = sorted((r for r in run.rec.reqs.values()
                   if r.req.stop_reason == "length" and r.stamps
                   and run.in_window(r.stamps[-1])), key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.output), -r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed + 1).permutation(len(rest))
    picked = [longest]
    for i in order:
        if len(picked) >= SAMPLE_ROWS or (
                len(picked) >= SAMPLE_MIN
                and sum(len(r.req.output) for r in picked) >= SAMPLE_TOKENS):
            break
        picked.append(rest[i])
    return picked


def check(run, seed: int, key) -> dict:
    """The numbers compared, each beside its limit, over every served token
    of the sample: ``mean_gap`` and ``gap_p99``, the mean and the 99th
    percentile of the gap by which a served token's logit lies below the
    reference's best at its position; ``errored_requests``, requests that
    ended in an error or short of their length.  The widest gap is logged
    beside them: it does not separate the program from its int8 control
    (see PERF.md)."""
    picked = sample(run, seed)
    seqs = [(r.req.prompt, r.req.output) for r in picked]
    ref = load("reference", run.cfg["family"], run.cell.root)
    gaps = ref.served_gaps(run.cfg, key, seqs,
                           length=run.cell.mix["engine"]["max_len"],
                           rows=SAMPLE_ROWS)
    flat = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    errors = sum(1 for r in run.rec.reqs.values()
                 if r.req.stop_reason in ERRORS)
    lim = run.cell.limits
    run.notes.append(
        f"reference: {len(picked)} requests, {flat.size} served tokens, "
        f"longest output {max((len(s) for _, s in seqs), default=0)}; "
        f"widest gap {float(flat.max()):.5f}, tokens off the reference's "
        f"choice {float(np.mean(flat > 0)):.4f}")
    return {"mean_gap": {"value": float(flat.mean()),
                         "limit": lim["mean_gap"]},
            "gap_p99": {"value": float(np.quantile(flat, 0.99)),
                        "limit": lim["gap_p99"]},
            "errored_requests": {"value": errors, "limit": 0}}


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------


@dataclass
class Run:
    cell: Cell
    cfg: dict
    rec: Recorder
    win: Window
    devices: list
    peak: dict | None = None
    trace: dict | None = None
    notes: list = field(default_factory=list)

    quantile = staticmethod(nearest_rank)

    @property
    def family(self):
        """The counts of the cell's family (``bench/families/``)."""
        return self.rec.family

    def in_window(self, t: float) -> bool:
        return self.win.open < t <= self.win.close

    def due_in_window(self) -> list[ReqRec]:
        w = self.win
        return [r for r in self.rec.reqs.values()
                if w.open <= r.due < w.open + w.seconds]


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             control: str | None = None, fault: str | None = None,
             reduced: bool = False) -> dict:
    """Measure one cell; returns the result line as a dictionary.  With
    ``reduced`` the program runs at its ``.reduced()`` sizes on whatever
    device JAX has (the harness's own CPU tests): no device metric then."""
    import jax

    if reduced:
        devices = jax.devices()[:1]
    else:
        devices = find_chips(cell.chips)
        enable_compile_cache()
    compiles = CompileCount()
    family = counts.family(cell.cfg, cell.root)
    arch, cfg = family.program_config(cell.cfg, reduced)
    key = seed_key(seed)
    engine = build_engine(arch, cell.mix, key, control)
    warm_up(engine)
    if fault:
        from bench import faults
        faults.plant(engine, fault)
    say(f"set-up: {compiles.n} programs compiled or fetched "
        f"({compiles.compile_s:.1f} s compiling)")
    rec = Recorder(engine, cfg, family)
    if trace:
        annotate(engine)
    win = drive(engine, rec, cell.mix, seed, seconds, trace, compiles)
    run = Run(cell, cfg, rec, win, devices)
    if not reduced:
        peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
        kind = devices[0].device_kind
        if kind not in peaks:
            raise SystemExit(f"no peaks for device kind {kind!r} in "
                             "bench/peaks.json")
        run.peak = peaks[kind]
    mem = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in mem)
    if cell.mix["arrivals"]["kind"] != "backlog":
        late = sorted(win.lateness)
        say(f"generator lateness: median {1e3 * nearest_rank(late, 0.5):.3f}"
            f" ms, max {1e3 * late[-1]:.3f} ms over {len(late)} requests")
    say(f"compiles inside the window: {win.compiles}")
    say(f"peak_bytes_in_use: {peak_bytes}")
    say(f"queue at window open {win.queue_at_open}, at close "
        f"{win.queue_at_close}; ticks in window "
        f"{sum(1 for t in rec.ticks if run.in_window(t.end))}")
    by_width = {}
    for t in rec.ticks:
        if run.in_window(t.end):
            by_width.setdefault(t.width, []).append(t.end - t.start)
    say("ticks by width: " + "; ".join(
        f"C={c} n={len(d)} median {1e3 * nearest_rank(d, 0.5):.1f} ms "
        f"sum {sum(d):.2f} s" for c, d in sorted(by_width.items())))
    med = {c: nearest_rank(d, 0.5) for c, d in by_width.items()}
    slow = sorted((t for t in rec.ticks if run.in_window(t.end)
                   and t.end - t.start > 2 * med[t.width]),
                  key=lambda t: t.start - t.end)
    say(f"ticks over twice their width's median: {len(slow)}, "
        f"{sum(t.end - t.start - med[t.width] for t in slow):.3f} s over; "
        "slowest: " + ", ".join(
            f"#{t.index} C={t.width} {1e3 * (t.end - t.start):.0f} ms "
            f"ended {t.ended}" for t in slow[:4]))
    gcs = [d for s0, d in rec.gc if win.open < s0 <= win.close]
    say(f"garbage collections in the window: {len(gcs)}, "
        f"{sum(gcs):.3f} s, longest {max(gcs, default=0.0):.3f} s")
    rec.close()
    if cell.mix["arrivals"]["kind"] != "backlog":
        ttft = sorted(((r.stamps[0] if r.stamps else win.stop) - r.due) * 1e3
                      for r in run.due_in_window())
        say(f"ttft over {len(ttft)} requests due: mean "
            f"{sum(ttft) / len(ttft):.1f} ms, p50 "
            f"{nearest_rank(ttft, 0.5):.1f} ms, p80 "
            f"{nearest_rank(ttft, 0.8):.1f} ms, max {ttft[-1]:.1f} ms")
        itl = sorted((b - a) * 1e3 for r in rec.reqs.values()
                     for a, b in zip(r.stamps, r.stamps[1:])
                     if run.in_window(b))
        if itl:
            say(f"itl over {len(itl)} gaps: " + ", ".join(
                f"p{round(100 * q)} {nearest_rank(itl, q):.1f}"
                for q in (0.5, 0.9, 0.95, 0.99))
                + f" ms, max {itl[-1]:.1f} ms; "
                f"{sum(g > 100 for g in itl)} over 100 ms")
    if trace:
        dec = {t.index: t.contexts for t in rec.ticks
               if t.index is not None and t.contexts}
        events = trace_reduce.load(win.trace_dir)
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        run.trace = trace_reduce.reduce(events, set(dec))
        run.trace["decode_contexts"] = dec
    done_by = {r.req.stop_reason for r in rec.reqs.values()}
    att = attempted(run)
    failed = sum(1 for r in att if r.req.stop_reason in ERRORS)
    if trace:
        metrics = per_layer(run)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
    else:
        metrics = end_to_end(run)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
    for note in run.notes:
        say(note)
    for name, value in metrics.items():
        say(f"{name} = {value!r} {units[name]}")
    say(f"stop reasons seen: {sorted(map(str, done_by))}")
    # free the program before the reference runs: a process's peak never
    # falls, and the reference must not be what sets it
    del engine, rec.engine
    gc.collect()
    checks = check(run, seed, key)
    say(run.notes[-1])
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": len(att),
              "failed": failed,
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()},
              "device": dev}
    if run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    if reduced:
        result["metrics"] = {}        # a rehearsal reports no device metric
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name} = {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8",), default=None,
                    help="serve with the program's int8 weights (control)")
    ap.add_argument("--fault", default=None,
                    help="break the timed path (see bench/faults.py)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=args.control, fault=args.fault)
    except NoChip as e:
        return int(e.code)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
