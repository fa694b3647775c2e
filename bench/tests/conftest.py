"""Shared helpers of the benchmark's own tests (CPU, reduced sizes).

Run them from the checkout root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MIX = {
    "name": "tiny", "engine": {"slots": 4, "max_len": 64, "chunk": 8},
    "arrivals": {"kind": "backlog"},
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                   "min": 3, "max": 30},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                   "min": 2, "max": 24},
    "block": 8, "preroll_s": 1}


def config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.fixture
def tiny_cell():
    """A cell of the BLAST configuration, cut to ``.reduced()`` sizes and a
    tiny backlog, with the limit of the full-size offline cell."""
    from bench import run as R

    def make(config_name="granite-3-2b-blast50", mix=None, limit=None):
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        wl = f"{config_name}.offline-decode"
        mine = lambda ms: [m for m in ms  # noqa: E731
                           if wl in m.get("workloads", [wl])]
        lim = json.loads((ROOT / "bench" / "limits" / f"{wl}.json")
                         .read_text())
        if limit is not None:
            lim = dict(lim, **limit)
        return R.Cell(wl, 1, config(config_name), mix or TINY_MIX,
                      mine(bench["end_to_end"]), mine(bench["per_layer"]),
                      lim)
    return make
