"""Device time of the serving step by named scope (``jax.named_scope``:
``ssd_conv``, ``ssd_scan``, ``attention``, ``cache_update``, ``linear``,
``embed``, ``head``, ...).

    python scripts/scope_time.py --workload <config>.<mix> --seed N \
        [--decode 24] [--prefill 4] [--context 1536] \
        [--out scope_time.json]

Builds the cell's engine as ``bench/run.py`` does (on the chip; without one
it exits 3, unless ``--reduced`` rehearses it on the CPU at the model's
``.reduced()`` sizes), compiles its step at C = 1 (every row decoding at
``--context``) and at the mix's chunk width, runs each under the profiler,
and sums each device operation's self time (``bench/trace.py``) by the
innermost known scope in the ``op_name`` that the compiled HLO gives that
operation.  An operation whose ``op_name`` names none of them counts as
``other``; one whose name the HLO text lacks, as ``unmapped``.  The
profiler's device operations carry only their HLO names,
so the HLO text is what maps them to scopes.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402
from bench import trace as trace_reduce  # noqa: E402

SCOPES = ("ssd_conv", "ssd_scan", "attention", "cache_update", "linear",
          "embed", "head", "guardrail", "sample")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"',
                    re.M)


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` named in an HLO ``op_name`` path."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return "other"


def op_scopes(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> scope, from the compiled module's text."""
    return {name: scope_of(op) for name, op in _INSTR.findall(hlo_text)}


def profile(compiled, args, n: int) -> dict[str, float]:
    """Device seconds per scope over ``n`` runs of ``compiled``."""
    import jax

    jax.block_until_ready(compiled(*args))          # settle allocations
    where = tempfile.mkdtemp(prefix="scope-time-")
    jax.profiler.start_trace(where)
    for _ in range(n):
        jax.block_until_ready(compiled(*args))
    jax.profiler.stop_trace()
    events = trace_reduce.load(where)
    scopes = op_scopes(compiled.as_text())
    out = defaultdict(float)
    devices = list(events["devices"].values())
    for dev in devices:
        for name, _, self_ns in trace_reduce.self_times(dev["ops"]):
            scope = scopes.get(name.lstrip("%")) or scopes.get(
                name.lstrip("%_"), "unmapped")
            out[scope] += self_ns * 1e-9
    return {k: v / max(1, len(devices)) / n for k, v in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--decode", type=int, default=24)
    ap.add_argument("--prefill", type=int, default=4)
    ap.add_argument("--context", type=int, default=1536)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    cell = R.load_cell(args.workload)
    if not args.reduced:
        try:
            R.find_chips(cell.chips)
        except R.NoChip as e:
            return int(e.code)
        R.enable_compile_cache()
    mix = cell.mix
    if args.reduced:
        mix = dict(mix, engine=dict(mix["engine"], slots=4, chunk=16,
                                    max_len=128))
    arch, _ = R.program_config(cell.cfg, args.reduced)
    engine = R.build_engine(arch, mix, R.seed_key(args.seed), None)
    B, C = engine.B, engine.chunk
    context = min(args.context, mix["engine"]["max_len"] - C - 1)
    result = {"workload": cell.name, "device": jax.devices()[0].device_kind,
              "rows": B, "context": context}
    for width, runs in ((1, args.decode), (C, args.prefill)):
        call = (engine.params, engine.cache, jnp.ones((B, width), jnp.int32),
                jnp.full((B,), context, jnp.int32),
                jnp.full((B,), width, jnp.int32))
        compiled = engine._step.lower(*call).compile()
        per = profile(compiled, call, runs)
        total = sum(per.values())
        result[f"C={width}"] = {
            "step_ms": 1e3 * total,
            "ms": {k: 1e3 * v for k, v in sorted(per.items(),
                                                 key=lambda kv: -kv[1])},
            "share": {k: v / total for k, v in per.items()} if total else {}}
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
