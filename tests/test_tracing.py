"""The serving engine's own tracing (serve/tracing.py): one ring record per
stepping tick, padding counts, admission stamps, the ring's bound, spans
closed on a step error, the ``engine.*`` spans in a profiler trace, the
named scopes in the step's compiled HLO, and the readers built on the ring
(``sla_report``, ``GET /v1/metrics``)."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.structures import StructureConfig
from repro.models import build_model
from repro.serve import (Engine, EngineConfig, MemoryConfig, Request,
                         SchedulerConfig, SpeculativeConfig, Tracer)
from repro.serve import tracing
from repro.serve.engine import _bucket

SPANS = {"engine." + p for p in ("tick",) + tracing.PHASES}


@pytest.fixture(scope="module")
def tiny():
    """The model, its weights, and one jitted step shared by the plain
    engines (jit keys its compiles by shape, so they compile once).  Dense
    linears: the spans and counters do not depend on the structure, and
    dense weights are made in a fraction of BLAST's time."""
    cfg = dataclasses.replace(configs.ARCHS["smollm-135m"].reduced(
        vocab=64, d_model=32, n_layers=2, d_ff=64, n_heads=2, n_kv_heads=1),
        structure=StructureConfig(kind="dense"))
    model = build_model(cfg)
    return (model, jax.jit(model.init)(jax.random.PRNGKey(0)),
            jax.jit(model.prefill_chunk))


def _engine(tiny, *, slots=2, chunk=8, max_len=64, paged=False, spec=0):
    model, params, step = tiny
    return Engine(model, params, EngineConfig(
        scheduler=SchedulerConfig(slots=slots, chunk_size=chunk),
        memory=MemoryConfig(max_len=max_len, paged=paged, page_size=8),
        speculative=SpeculativeConfig(k=spec, draft_rank_frac=0.9)),
        step_fn=None if paged else step)


def _submit(eng, n=3, plen=11, max_new=5, uid0=0, temperature=0.0):
    reqs = [Request(uid=uid0 + i, prompt=list(range(1, plen + i)),
                    max_new_tokens=max_new, temperature=temperature)
            for i in range(n)]
    for r in reqs:
        eng.submit(r)
    return reqs


class TestRecords:
    @pytest.mark.parametrize("spec", [0, 2])
    def test_one_record_per_stepping_tick(self, tiny, spec):
        eng = _engine(tiny, spec=spec)
        _submit(eng, temperature=0.0)
        ticks = 0
        while eng.tick():
            ticks += 1
        r = eng.tracer.records()
        assert eng.tracer.n == len(r) == eng.stats["steps"] > 0
        assert ticks >= len(r)
        phases = sum(r[p] for p in tracing.PHASES)
        assert np.all(phases <= r["end"] - r["start"])
        assert np.all(np.diff(r["end"]) > 0)
        assert np.all(r["B"] == eng.B)
        assert np.all(r["live"] <= eng.B) and r["live"][-1] == 0
        assert bool(np.any(r["spec"])) == bool(spec)
        assert r["compiles"].sum() > 0        # the first ticks compile

    def test_padding_is_columns_less_tokens(self, tiny):
        eng = _engine(tiny, slots=3)
        shapes = []
        schedule = eng._schedule

        def spy(*a, **k):
            n = schedule(*a, **k)
            if n.any():
                shapes.append(n.copy())
            return n
        eng._schedule = spy
        _submit(eng, n=5, temperature=0.7)
        eng.run()
        r = eng.tracer.records()
        assert len(r) == len(shapes)
        for rec, n in zip(r, shapes):
            assert rec["C"] == _bucket(int(n.max()))
            assert (rec["B"] * rec["C"] - rec["prompt"] - rec["decode"]
                    == eng.B * _bucket(int(n.max())) - int(n.sum()))
        s = eng.tracer.summary()
        fed = int(sum(n.sum() for n in shapes))
        cols = sum(eng.B * _bucket(int(n.max())) for n in shapes)
        assert s["padded_share"] == pytest.approx(100 * (1 - fed / cols))

    def test_nested_span_pauses_its_parent(self):
        tr = Tracer(size=4)
        slots = []
        with tr.tick(0, slots):
            tr.step(1, 1, 0, 1)
            with tr.span("emit"):
                with tr.span("sample"):
                    sum(range(200_000))
        r = tr.records()[0]
        assert r["sample"] > 0
        assert r["emit"] < r["sample"]
        assert r["emit"] + r["sample"] <= r["end"] - r["start"]

    def test_tick_without_a_step_leaves_no_record(self, tiny):
        eng = _engine(tiny)
        assert eng.tick() is False
        assert eng.tracer.n == 0


class TestAdmitStamp:
    def test_submit_admit_first_in_order(self, tiny):
        eng = _engine(tiny, slots=2)
        reqs = _submit(eng, n=4)
        eng.run()
        for r in reqs:
            assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
        # the last two waited in the queue for a slot
        assert reqs[3].t_admit > reqs[0].t_admit

    def test_preempted_request_keeps_its_first_stamp(self, tiny):
        eng = _engine(tiny, slots=1)
        (req,) = _submit(eng, n=1, max_new=6)
        eng.tick()
        first = req.t_admit
        assert first is not None
        eng._preempt(0)
        assert eng.slots[0].req is None and req.n_preempted == 1
        eng.run()
        assert req.t_admit == first and req.done


class TestRing:
    def test_ring_stays_at_its_size(self, tiny):
        eng = _engine(tiny)
        eng.tracer = Tracer(size=8)
        _submit(eng, n=2, max_new=12)
        eng.run()
        tr = eng.tracer
        r = tr.records()
        assert tr.n > 8 and len(r) == 8 and tr._ring.shape == (8,)
        assert np.all(np.diff(r["end"]) > 0)
        assert tr.reaches(r["end"][0]) and not tr.reaches(r["start"][0])
        assert len(tr.records(r["end"][2], r["end"][5])) == 3

    def test_records_are_bounded_in_bytes(self):
        assert tracing.RING * tracing.RECORD.itemsize <= 8 * 2**20


class _Recorder:
    """Stands in for the profiler's annotations: logs enters and exits."""
    log: list = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


class TestSpans:
    def test_step_error_closes_its_spans(self, tiny, monkeypatch):
        monkeypatch.setattr(tracing, "TraceAnnotation", _Recorder)
        monkeypatch.setattr(tracing, "StepTraceAnnotation", _Recorder)
        _Recorder.log = []
        eng = _engine(tiny)
        _submit(eng, n=2)

        def broken(*a):
            raise RuntimeError("device lost")
        eng._step = broken
        assert eng.tick() is True
        assert eng.stats["step_errors"] == 1
        depth, opened = 0, set()
        for kind, name in _Recorder.log:
            depth += 1 if kind == "enter" else -1
            assert depth >= 0
            opened.add(name)
        assert depth == 0 and eng.tracer._open is None
        assert {"engine.tick", "engine.admit", "engine.schedule",
                "engine.dispatch"} <= opened
        (rec,) = eng.tracer.records()
        assert rec["dispatch"] > 0 and rec["wait"] == 0

    def test_spans_in_a_profiler_trace(self, tiny, tmp_path):
        eng = _engine(tiny)
        _submit(eng, n=2, max_new=3)
        eng.tick()                    # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.run()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                            recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        names = {ev.name for plane in pd.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert SPANS <= names


class TestNamedScopes:
    def test_step_hlo_names_its_layers(self, tiny):
        model, params, _ = tiny
        cache = model.init_cache(2, 32)
        z = jnp.zeros((2,), jnp.int32)
        txt = jax.jit(model.prefill_chunk).lower(
            params, cache, jnp.zeros((2, 4), jnp.int32), z, z
        ).compile().as_text()
        assert txt.startswith("HloModule jit_prefill_chunk")
        paths = set(re.findall(r'op_name="([^"]*)"', txt))
        for scope in ("embed", "attention", "cache_update", "linear",
                      "head"):
            assert any(f"/{scope}/" in p for p in paths), scope

    def test_paged_step_program_name(self, tiny):
        eng = _engine(tiny, paged=True)
        assert "prefill_chunk" in eng._paged_step.__name__
        assert eng._paged_step.__name__ == "paged_prefill_chunk"


class TestReaders:
    def test_sla_queue_depth_reads_the_ring(self, tiny):
        eng = _engine(tiny, slots=1)
        _submit(eng, n=3)
        eng.run()
        depth = eng.tracer.records()["queue"]
        sla = eng.sla_report()
        assert sla["queue_depth_max"] == int(depth.max()) == 2
        assert sla["queue_depth_p50"] == float(np.percentile(depth, 50))

    def test_metrics_endpoint_has_ticks(self, tiny):
        from repro.serve.http import Server
        eng = _engine(tiny)
        assert Server(eng)._metrics()["ticks"] == {"ticks": 0}
        _submit(eng, n=2)
        eng.run()
        t = Server(eng)._metrics()["ticks"]
        assert t["ticks"] == eng.tracer.n
        assert t["tick_ms"] >= t["wait_ms"] >= 0
        assert t["host_gap_ms"] >= 0 and 0 <= t["padded_share"] < 100
        assert t["compiles"] >= 1
