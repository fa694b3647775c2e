"""The readers of the engine's own spans and counters (``host_gap_ms``,
``step_wait_ms``, ``padded_share``, ``admit_wait_p50_ms``) on a reduced
open-loop run on the CPU: numbers in range from the program's tick ring and
admission stamps, and nothing when the ring no longer reaches the window's
open or the program keeps neither."""

import types

import pytest

from conftest import TINY_MIX

NAMES = ("host_gap_ms.offline", "step_wait_ms.offline",
         "padded_share.online", "admit_wait_p50_ms.online")
TINY_ONLINE = dict(TINY_MIX, name="tiny-online",
                   arrivals={"kind": "poisson", "rate_per_s": 8.0})


@pytest.fixture(scope="module")
def run():
    import jax
    from bench import run as R
    from conftest import config

    cell = R.Cell("granite-3-2b-blast50.tiny-online", 1,
                  config("granite-3-2b-blast50"), TINY_ONLINE, [], [], {})
    seed = 2**31 + 11
    arch, cfg = R.program_config(cell.cfg, reduced=True)
    engine = R.build_engine(arch, cell.mix, R.seed_key(seed), None)
    R.warm_up(engine)
    rec = R.Recorder(engine, cfg)
    win = R.drive(engine, rec, cell.mix, seed, 1.0, False, R.CompileCount(),
                  drain_s=10.0)
    rec.close()
    return R.Run(cell, cfg, rec, win, jax.devices()[:1])


def _read(run, name):
    from bench import run as R
    return R.reader(name)(run)


def test_readers_in_range(run):
    ticks = [t for t in run.rec.ticks if run.in_window(t.end)
             and not t.prefill]
    assert ticks and run.due_in_window()
    v = {n: _read(run, n) for n in NAMES}
    tick_ms = 1e3 * max(t.end - t.start for t in ticks)
    assert 0 < v["host_gap_ms.offline"] < tick_ms
    assert 0 <= v["step_wait_ms.offline"] < tick_ms
    assert 0 < v["padded_share.online"] < 100
    assert 0 <= v["admit_wait_p50_ms.online"] <= 1e3 * (run.win.stop
                                                        - run.win.open)
    # admission comes before the end of the tick after it
    from bench.metrics import queue_wait_p50_ms
    assert v["admit_wait_p50_ms.online"] <= queue_wait_p50_ms.read(run)


def test_nothing_when_the_ring_does_not_reach_the_window(run):
    from repro.serve import Tracer
    engine = run.rec.engine
    full = engine.tracer
    engine.tracer = Tracer(size=2)
    try:
        for i in range(3):
            with engine.tracer.tick(i, []):
                engine.tracer.step(1, 1, 0, 1)
        assert not engine.tracer.reaches(run.win.open)
        for name in NAMES[:3]:
            assert _read(run, name) is None
            assert name.split(".")[0] in run.notes[-1]
    finally:
        engine.tracer = full


def test_nothing_from_a_program_without_them(run):
    """The parent program keeps no ring and stamps no admission."""
    bare = types.SimpleNamespace(
        **{k: v for k, v in vars(run).items() if k != "rec"})
    bare.rec = types.SimpleNamespace(engine=object(), ticks=run.rec.ticks,
                                     reqs={})
    bare.due_in_window = lambda: [types.SimpleNamespace(
        due=0.0, req=types.SimpleNamespace(t_submit=0.0))]
    for name in NAMES:
        assert _read(bare, name) is None


def test_itl_p95_spans_at_least_one_tick(run):
    """Consecutive tokens of a row are stamped at the ends of two ticks, so
    no gap is shorter than the window's shortest tick."""
    ticks = [t.end - t.start for t in run.rec.ticks if run.in_window(t.end)]
    v = _read(run, "itl_p95_ms.online")
    assert 1e3 * min(ticks) <= v <= 1e3 * (run.win.stop - run.win.open)
