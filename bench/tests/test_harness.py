"""The harness end to end on the CPU at ``.reduced()`` sizes: a sound run
is correct, the timed path broken underneath is not, the program's int8
control reads further from the reference than the program, and without a
chip the command prints no result."""

import json
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT


def _run(cell, seed, **kw):
    from bench import run as R
    return R.run_cell(cell, seed, 2.0, False, reduced=True, **kw)


def test_sound_run_is_correct(tiny_cell):
    res = _run(tiny_cell(), 2**31 + 5)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"] == {}           # a rehearsal prints no device metric
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["stale_cache", "alter_token"])
def test_broken_timed_path_is_not_correct(tiny_cell, fault):
    res = _run(tiny_cell(), 2**31 + 6, fault=fault)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_int8_control_reads_further_than_the_program(tiny_cell):
    """Pooled over three seeds: at this size a run serves about a hundred
    tokens, too few for one seed's mean to be steady."""
    cell = tiny_cell()
    seeds = (2**31 + 8, 2**31 + 9, 2**31 + 10)
    sound = [_run(cell, s)["checks"] for s in seeds]
    control = [_run(cell, s, control="int8")["checks"] for s in seeds]
    for name in ("mean_gap", "gap_p99"):
        assert (sum(c[name]["value"] for c in control)
                > 3 * sum(c[name]["value"] for c in sound)), name


def test_no_chip_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-3-2b-blast50.offline-decode", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-3-2b-blast50.offline-decode", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
    for w in bench["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
    from bench import run as R
    for m in bench["per_layer"]:
        assert callable(R.reader(m["name"]))


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           ("stale_cache", False)])
def test_step_that_donates_its_cache(tiny_cell, monkeypatch, fault, correct):
    """A program may compile its step to consume (donate) the cache it is
    given: warm-up keeps the cache each step returns, and the stale-cache
    fault returns a copy taken before the step."""
    import jax
    from bench import run as R

    build = R.build_engine

    def donating(*a, **k):
        engine = build(*a, **k)
        engine._step = jax.jit(engine.model.prefill_chunk,
                               donate_argnums=(1,))
        return engine

    monkeypatch.setattr(R, "build_engine", donating)
    res = _run(tiny_cell(), 2**31 + 12, fault=fault)
    assert res["correct"] is correct
