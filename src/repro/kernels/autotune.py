"""Empirical tiling autotuner for the fused BLAST kernels.

``ops.py`` picks ``(block_t, block_r)`` with a VMEM-budget heuristic
(``pick_blast_blocks``).  The heuristic is shape-blind about *throughput*:
it returns the largest resident-set-feasible tiles, which is right for big
prefill GEMMs but measurably wrong for skinny decode calls where grid
overhead and r-tile granularity dominate.  This module times the real
candidate configs per ``(T, m, n, b, r, G, dtype, kind, backend)`` key and
persists the winners, so repeated engine builds and serving runs skip
straight to the measured-best tiling.

Contract
--------
* Disabled by default: ``ops`` falls back to ``pick_blast_blocks`` —
  enabling/disabling never changes numerics, only tile choices.
* ``enable(path)`` installs a process-wide ``TuningCache`` backed by a JSON
  file (see below); ``lookup`` is a trace-time dict read, so tuned tiles
  bake into jitted programs compiled after enabling.
* ``tune_blast`` times each candidate with compiled real kernels
  (best-of-``reps`` wall time after ``block_until_ready``) and records the
  winner; re-tuning an already-cached key is a no-op unless ``force``.

Cache file format (version 2)::

    {"version": 2,
     "entries": {"T8.m128.n64.b4.r24.G1.float32.int8.cpu.a8": [8, 32], ...}}

Keys encode the call signature (logical T before padding, full factor
shape, group size G, input dtype, factor kind float/int8/int4, JAX
backend, activation storage none/int8 — W8A8/W4A8 calls tile differently
from their float-activation twins, so they tune independently); values are
``[block_t, block_r]``.  Unknown versions are ignored (treated as empty) so
stale caches can never poison a run — version 1 files predate the
activation-storage key component and are exactly the mis-hit the bump
guards against.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

_VERSION = 2
_DEFAULT_PATH = os.path.join(".", ".autotune", "blast_tiling.json")


@dataclasses.dataclass(frozen=True)
class Key:
    """Identity of one tiling decision (all static trace-time ints/strs)."""

    T: int
    m: int
    n: int
    b: int
    r: int
    G: int = 1
    dtype: str = "float32"
    kind: str = "float"     # float | int8 | int4 (factor storage)
    backend: str = "cpu"
    act: str = "none"       # none | int8 (activation storage: A8 paths)

    def encode(self) -> str:
        a = {"none": "anone", "int8": "a8"}.get(self.act, f"a{self.act}")
        return (f"T{self.T}.m{self.m}.n{self.n}.b{self.b}.r{self.r}"
                f".G{self.G}.{self.dtype}.{self.kind}.{self.backend}.{a}")


class TuningCache:
    """On-disk (JSON) block-size cache with in-memory mirror."""

    def __init__(self, path: str | None = None):
        self.path = path or _DEFAULT_PATH
        self.entries: dict[str, tuple[int, int]] = {}
        self.load()

    def load(self) -> None:
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            return
        if not isinstance(raw, dict) or raw.get("version") != _VERSION:
            return
        for k, v in raw.get("entries", {}).items():
            if (isinstance(v, (list, tuple)) and len(v) == 2
                    and all(isinstance(x, int) and x > 0 for x in v)):
                self.entries[k] = (v[0], v[1])

    def save(self) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": _VERSION,
                       "entries": {k: list(v)
                                   for k, v in sorted(self.entries.items())}},
                      f, indent=0, sort_keys=True)
        os.replace(tmp, self.path)

    def get(self, key: Key) -> tuple[int, int] | None:
        return self.entries.get(key.encode())

    def put(self, key: Key, blocks: tuple[int, int]) -> None:
        self.entries[key.encode()] = (int(blocks[0]), int(blocks[1]))


# -- module state (consulted by kernels/ops.py at trace time) ----------------

_STATE: dict = {"cache": None}


def enable(path: str | None = None) -> TuningCache:
    """Install (or reuse) the process-wide cache.  Idempotent per path."""
    cache = _STATE["cache"]
    if cache is None or (path is not None and cache.path != path):
        cache = TuningCache(path)
        _STATE["cache"] = cache
    return cache


def disable() -> None:
    _STATE["cache"] = None


def enabled() -> bool:
    return _STATE["cache"] is not None


def cache() -> TuningCache | None:
    """The installed process-wide cache (None while disabled)."""
    return _STATE["cache"]


def lookup(key: Key) -> tuple[int, int] | None:
    """Tuned blocks for ``key``, or None (→ caller uses the heuristic).
    Trace-time read: runs inside jit tracing, so results must stay stable
    for the life of the process unless the user re-tunes before a retrace."""
    cache = _STATE["cache"]
    return None if cache is None else cache.get(key)


def save() -> None:
    if _STATE["cache"] is not None:
        _STATE["cache"].save()


# -- candidate generation & timing -------------------------------------------


def candidates(T: int, m: int, n: int, b: int, r: int,
               bytes_per_el: int = 4,
               factor_bytes: float | None = None) -> list[tuple[int, int]]:
    """VMEM-feasible (block_t, block_r) configs worth timing.

    The sweep is the heuristic's own search lattice (block_t halvings ×
    legal r-tiles, ``ops._legal_block_r``) clamped to the call's actual
    (rounded-up) T and r — a handful of configs, always including the
    heuristic's pick.
    """
    from repro.kernels import ops  # local: ops imports this module
    from repro.kernels.blast_matmul import VMEM_BUDGET, vmem_bytes

    fb = bytes_per_el if factor_bytes is None else factor_bytes
    packed = fb < 1
    lane = ops._rank_lane(packed)
    t_cap = min(128, ops._round_up(T, 8))
    out: list[tuple[int, int]] = []
    bt = t_cap
    while bt >= 8:
        for br in (4 * lane, 2 * lane, lane):
            br = ops._legal_block_r(min(br, ops._round_up(r, lane)), r,
                                    packed)
            if (vmem_bytes(bt, br, m, n, b, bytes_per_el, fb,
                           bytes_per_el) <= VMEM_BUDGET
                    and (bt, br) not in out):
                out.append((bt, br))
        if bt == 8:
            break
        bt = max(bt // 2, 8)
    heur = ops.pick_blast_blocks(T, m, n, b, r, bytes_per_el, factor_bytes)
    heur = (min(heur[0], t_cap), ops._legal_block_r(heur[1], r, packed))
    if heur not in out:
        out.append(heur)
    return out


def _time_call(fn, reps: int = 3) -> float:
    import jax

    jax.block_until_ready(fn())  # compile + warm outside the timed region
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def tune_blast(T: int, m: int, n: int, b: int, r: int, *,
               G: int = 1, dtype=None, kind: str = "float",
               act: str = "none", reps: int = 3, force: bool = False,
               seed: int = 0) -> tuple[int, int]:
    """Measure the candidate tilings for one BLAST call shape and cache the
    winner.  Operands are synthetic (timing only).  Returns the chosen
    ``(block_t, block_r)``; with tuning disabled, returns the heuristic
    pick without timing or caching.  ``act="int8"`` times the W8A8/W4A8
    integer-contraction path (requires ``kind`` int8/int4).
    """
    import jax
    import jax.numpy as jnp

    from repro import quant as qt
    from repro.kernels import ops

    dtype = jnp.dtype(jnp.float32 if dtype is None else dtype)
    if act != "none" and kind == "float":
        raise ValueError("act='int8' requires quantized factors "
                         "(kind int8/int4)")
    key = Key(T=T, m=m, n=n, b=b, r=r, G=G, dtype=dtype.name, kind=kind,
              backend=jax.default_backend(), act=act)
    fb = {"float": dtype.itemsize, "int8": 1, "int4": 0.5}[kind]
    cache = _STATE["cache"]
    if cache is None:
        return ops.pick_blast_blocks(T, m, n, b, r, dtype.itemsize, fb)
    if not force:
        hit = cache.get(key)
        if hit is not None:
            return hit

    rng = jax.random.PRNGKey(seed)
    kx, kf = jax.random.split(rng)
    p, q = m // b, n // b
    x = jax.random.normal(kx, (T, n), dtype=dtype)
    lead = (G,) if G > 1 else ()
    ku, ks, kv = jax.random.split(kf, 3)
    U = jax.random.normal(ku, (*lead, b, p, r), dtype=dtype)
    S = jax.random.normal(ks, (*lead, b, b, r), dtype=dtype)
    V = jax.random.normal(kv, (*lead, b, q, r), dtype=dtype)
    if kind != "float":
        bits = 8 if kind == "int8" else 4
        uv_axes = (len(lead) + 1, len(lead) + 2)   # per U_i / V_j block
        Uq = qt.quantize(U, bits=bits, block_axes=uv_axes)
        Sq = qt.quantize(S, bits=bits, block_axes=(len(lead) + 2,))
        Vq = qt.quantize(V, bits=bits, block_axes=uv_axes)

    def run(bt: int, br: int):
        if kind == "float":
            if G > 1:
                return ops.blast_matmul_grouped(x, U, S, V,
                                                block_t=bt, block_r=br)
            return ops.blast_matmul(x, U, S, V, block_t=bt, block_r=br)
        if G > 1:
            su = Uq.scale.reshape(G, b)
            ss = Sq.scale.reshape(G, b, b)
            sv = Vq.scale.reshape(G, b)
            if kind == "int4":
                return ops.blast_matmul_grouped_q4(
                    x, Uq.q, Sq.q, Vq.q, su, ss, sv,
                    block_t=bt, block_r=br, act=act)
            return ops.blast_matmul_grouped_q(
                x, qt.int_values(Uq), qt.int_values(Sq), qt.int_values(Vq),
                su, ss, sv, block_t=bt, block_r=br, act=act)
        return ops.blast_matmul_q(x, Uq, Sq, Vq, block_t=bt, block_r=br,
                                  act=act)

    best, best_t = None, float("inf")
    for bt, br in candidates(T, m, n, b, r, dtype.itemsize, fb):
        try:
            dt = _time_call(lambda: run(bt, br), reps=reps)
        except Exception:  # infeasible tiling on this backend: skip
            continue
        if dt < best_t:
            best, best_t = (bt, br), dt
    if best is None:  # every candidate failed — keep the heuristic
        return ops.pick_blast_blocks(T, m, n, b, r, dtype.itemsize, fb)
    cache.put(key, best)
    return best
