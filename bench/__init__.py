"""On-chip serving benchmark of the BLAST engine (see run.py)."""

import functools
import importlib.util
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def load(kind: str, stem: str, root: Path = ROOT):
    """``<root>/bench/<kind>/<stem>.py`` as a module: a configuration's
    family (``families``), its reference (``reference``) or a per-layer
    reader (``metrics``), found by the name that BENCHMARK.json or the
    configuration file gives.  Each file is executed once per process."""
    return _load_file((root / "bench" / kind / f"{stem}.py").resolve())


@functools.cache
def _load_file(path: Path):
    name = "bench_" + "_".join(path.parts[-2:]).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
