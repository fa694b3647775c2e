"""A configuration's family is found by the name in its file: a new family
enters the benchmark by new files alone, and ``dense_gqa`` refuses a
program whose layers it does not describe."""

import hashlib
import json
import shutil

import pytest

from conftest import ROOT, TINY_MIX, config


def _hashes(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_family_enters_by_files_alone(tmp_path):
    from bench import run as R

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(tmp_path)
    bench_json = json.loads((tmp_path / "BENCHMARK.json").read_text())

    fam, name, cell = "gqa_twin", "granite-3-2b-twin", "granite-3-2b-twin.tiny"
    b = tmp_path / "bench"
    (b / "families" / f"{fam}.py").write_text(
        "from bench.families.dense_gqa import *  # noqa: F401,F403\n")
    (b / "reference" / f"{fam}.py").write_text(
        "from bench.reference.dense_gqa import *  # noqa: F401,F403\n")
    (b / "configs" / f"{name}.json").write_text(json.dumps(
        dict(config("granite-3-2b-blast50"), name=name, family=fam)))
    (b / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    shutil.copy(b / "limits" / "granite-3-2b-blast50.offline-decode.json",
                b / "limits" / f"{cell}.json")
    grown = dict(bench_json)
    grown["configs"] = bench_json["configs"] + [{
        "name": name, "source": "https://huggingface.co/ibm-granite/"
        "granite-3.0-2b-base/blob/main/config.json",
        "file": f"bench/configs/{name}.json", "reduced": [],
        "why": "the BLAST configuration under a family of another name"}]
    grown["workloads"] = bench_json["workloads"] + [{
        "name": cell, "config": name, "traffic": "tiny", "chips": 1,
        "why": "a tiny backlog"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))

    res = R.run_cell(R.load_cell(cell, tmp_path), 2**31 + 13, 2.0, False,
                     reduced=True)
    assert res["correct"] is True and res["attempted"] > 0

    # BENCHMARK.json gained entries; no file that was there changed
    after = _hashes(tmp_path)
    assert [p for p, h in before.items()
            if p.name != "BENCHMARK.json" and after.get(p) != h] == []


@pytest.mark.parametrize("arch", ["mamba2-130m", "granite-moe-1b-a400m"])
def test_dense_gqa_refuses_other_layers(arch):
    """The file states the program's own sizes, so only the layers differ."""
    from bench import run as R
    from repro import configs

    a = configs.get(arch)
    cfg = dict(config("granite-3-2b-blast50"), arch=arch,
               hidden_size=a.d_model, intermediate_size=a.d_ff,
               num_hidden_layers=a.n_layers, num_attention_heads=a.n_heads,
               num_key_value_heads=a.n_kv_heads, head_dim=a.head_dim_,
               vocab_size=a.vocab, tie_word_embeddings=a.tie_embeddings,
               rope_theta=a.rope_theta)
    with pytest.raises(SystemExit, match="differs from the configuration "
                                         "file: layers$"):
        R.program_config(cfg)
