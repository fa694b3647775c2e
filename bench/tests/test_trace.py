"""The trace reduction on a hand-made trace with known answers, and on a
small trace recorded on a TPU v5e (``data/``)."""

import json

import pytest

from conftest import ROOT

MS = 1_000_000   # ns


def _synthetic():
    # window 0..100 ms; two ticks; a loop holds two operations
    host = [["window", 0, 100 * MS, {}],
            ["tick", 0, 40 * MS, {"i": 0}],
            ["step", 1 * MS, 30 * MS, {}],
            ["tick", 50 * MS, 40 * MS, {"i": 1}],
            ["guardrail", 85 * MS, 5 * MS, {}]]
    ops = [["%while.0", 5 * MS, 15 * MS], ["%fusion.1", 5 * MS, 5 * MS],
           ["%fusion.2", 12 * MS, 8 * MS], ["%copy.3", 60 * MS, 20 * MS],
           ["%late", 95 * MS, 10 * MS], ["%early", -10 * MS, 5 * MS]]
    modules = [["jit_prefill_chunk(1)", 5 * MS, 15 * MS],
               ["jit_prefill_chunk(1)", 60 * MS, 20 * MS]]
    return {"host": host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "layout": {}}


def test_busy_union_and_gaps():
    from bench import trace

    r = trace.reduce(_synthetic(), {1})
    # union: [5, 20) + [60, 80) + [95, 100) = 40 ms
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.040)
    ops = dict(r["device_ops"])
    # self times of the operations that start inside the window
    assert ops == pytest.approx({"%while.0": 0.002, "%fusion.1": 0.005,
                                 "%fusion.2": 0.008, "%copy.3": 0.020,
                                 "%late": 0.010})
    gaps = dict(r["idle_gaps"])
    # [0,5) inside step inside tick 0; [20,60) midpoint 40 = tick 0's end
    # -> outside; [80,95) midpoint 87.5 inside guardrail inside tick 1
    assert gaps["step"] == pytest.approx(0.005)
    assert gaps["outside any span"] == pytest.approx(0.040)
    assert gaps["guardrail"] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.040)
    assert r["decode_step_s"] == {1: pytest.approx(0.020)}


def test_short_names_and_self_times():
    from bench import trace

    assert trace.short("%fusion.12 = bf16[8] fusion(%x)") == "%fusion.12"
    assert trace.self_times([["a", 0, 10], ["b", 1, 2], ["c", 4, 3],
                             ["d", 5, 1]]) == \
        [("a", 0, 5), ("b", 1, 2), ("c", 4, 2), ("d", 5, 1)]


def test_union_merges_and_clips():
    from bench import trace

    assert trace.union([(0, 5), (3, 8), (10, 12), (-4, 1)], 0, 11) == \
        [(0, 8), (10, 11)]


def test_recorded_tpu_trace():
    from bench import trace

    path = ROOT / "bench" / "tests" / "data" / "tpu_v5e_trace.json"
    events = json.loads(path.read_text())
    ticks = sorted(h[3]["i"] for h in events["host"] if h[0] == "tick")
    r = trace.reduce(events, set(ticks))
    assert 0 < r["busy_s"] <= r["window_s"]
    # every tick of the recording ran the serving step on the device
    assert set(r["decode_step_s"]) == set(ticks)
    total_gaps = sum(v for _, v in r["idle_gaps"])
    assert total_gaps <= r["window_s"] - r["busy_s"] + 1e-9
