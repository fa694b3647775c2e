"""Continuous-batching inference engine with chunked prefill.

A fixed pool of B slots advances through one jitted ``prefill_chunk`` per
iteration.  Each iteration the scheduler packs a *mixed* batch under a token
budget (vLLM-style chunked prefill): slots still ingesting their prompt
contribute up to ``chunk_size`` prompt tokens, slots in generation contribute
exactly one token — so a 512-token prompt costs ceil(512/C) steps instead of
512, while decodes keep flowing in the same batches.

One model call serves every row shape: ``prefill_chunk(params, cache,
tokens (B, C), steps (B,), n_tokens (B,))`` writes each slot's KV/state cache
at its own offset and masks the ragged tail columns.  The per-iteration chunk
width C is bucketed to powers of two, so the jitted step function (shared
across engines via ``step_fn`` — jit's trace cache keys it by chunk shape)
compiles O(log chunk_size) variants total.

API v2 (serve/config.py): ``Engine(model, params, EngineConfig(...))`` plus
``async generate(prompt, sampling, priority=...)`` streaming one token at a
time, ``generate_batch`` for scripts, and ``cancel(uid)``.  The legacy flat
kwargs still work through ``EngineConfig.from_legacy`` (warns once).

With ``MemoryConfig(paged=True)`` cache memory is sized in tokens, not
slots: serve/paged.py pools fixed-size pages under the sequence-axis cache
leaves, shares page-aligned prompt prefixes across requests (recurrent
families share via state snapshots), and the scheduler preempts the
lowest-priority longest-running generation when the pool runs dry —
``SchedulerConfig.policy`` picks priority-aware vs FIFO admission.

A finished slot is recycled immediately for the next queued request — no
batch drain.  Sampling: greedy or temperature.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import heapq
import threading
import time
import warnings
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant as qt
from repro.core import structures
from repro.parallel import NO_PARALLEL
from repro.serve import resilience as rsl
from repro.serve.config import EngineConfig, SamplingParams
from repro.serve.faults import FaultError, FaultPlan
from repro.serve.paged import PagedCache
from repro.serve.tracing import Tracer


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    priority: int = 0          # lower = more urgent (0 = interactive)
    prefix_len: int | None = None  # shared-prefix hint (tokens): recurrent
    #                            families snapshot state exactly here
    deadline_s: float | None = None  # end-to-end deadline override
    #                            (None: SchedulerConfig.deadline_s)
    # filled by the engine:
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False    # cache-capacity truncation ONLY (see stop_reason)
    stop_reason: str | None = None  # length | capacity | cancelled | shed |
    #                            deadline | numeric_error | error
    n_preempted: int = 0       # times this request lost its pages and re-queued
    degrade_level: int = 0     # numeric-guardrail ladder rung (resilience.py)
    degrade_path: list = dataclasses.field(default_factory=list)
    n_step_errors: int = 0     # times implicated in a step exception
    t_submit: float | None = None
    t_admit: float | None = None   # first time it took a slot (kept on resume)
    t_first: float | None = None   # first output token (TTFT = t_first-t_submit)
    t_done: float | None = None


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    pos: int = 0            # next absolute position to write
    to_feed: deque = dataclasses.field(default_factory=deque)  # prompt left
    feed: list = dataclasses.field(default_factory=list)  # full feed (prefix reg)
    reg_at: int | None = None  # page-aligned prefix-registration boundary


def _bucket(n: int) -> int:
    """Round a chunk width up to a power of two (bounds jit retraces)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _blast_shapes(tree) -> list[tuple[int, int, int, int]]:
    """(d_out, d_in, b, r) for every BLAST linear in a params tree — reads
    the *array* shapes, so truncated draft params report their r'."""
    out = []
    if isinstance(tree, dict):
        if set(tree) - {"bias"} == {"U", "S", "V"}:
            u, v = tree["U"], tree["V"]
            # trailing 3 axes are (b, p, r) even under cycle/expert stacking
            b, p, r = (int(d) for d in u.shape[-3:])
            out.append((b * p, b * int(v.shape[-2]), b, r))
            return out
        for v in tree.values():
            out += _blast_shapes(v)
    return out


def _reset_row(batch_axis, cache, template, b):
    """Overwrite row ``b`` of every cache leaf (along that leaf's batch
    axis) with the one-row pristine ``template``."""
    return jax.tree.map(
        lambda bax, c, t: jax.lax.dynamic_update_slice_in_dim(c, t, b, bax),
        batch_axis, cache, template)


_LEGACY_WARNED = False


class Engine:
    def __init__(self, model, params, config: EngineConfig | None = None, *,
                 step_fn=None, **legacy):
        """``config``: an ``EngineConfig`` (serve/config.py) grouping the
        scheduler / memory / speculative / autotune knobs.  Passing the old
        flat kwargs (``batch_slots=…, max_len=…``) still works through
        ``EngineConfig.from_legacy`` but warns once per process.

        ``step_fn``: optionally share one ``jax.jit(model.prefill_chunk)``
        across engines — jit's trace cache keys compiled steps by chunk
        shape, so engines with the same slot count reuse each other's
        compiles (non-paged mode only; the paged step closes over the page
        geometry).

        Autotune: warm the BLAST kernel tiling cache at engine build —
        every structured linear the model dispatches is timed at this
        engine's decode width (B·1 rows) and full-chunk prefill width, and
        the winning (block_t, block_r) configs persist to
        ``AutotuneConfig.cache_path`` (JSON; see kernels/autotune.py).

        Quantize-at-load: when the model config's ``quant.weights`` knob is
        set (or a ``config.quant`` override is passed) and ``params`` are
        still float, they convert to per-block QArrays here, once.
        ``quant.cache`` must be set on the *model's* config (``init_cache``
        allocates int8 + scales from it); an override requesting cache
        quantization the model was not built with raises.

        Self-speculative decoding (``SpeculativeConfig.k > 0``): draft k
        tokens per decode round with a rank-truncated view of the SAME
        weights, verify in one all-logits ``prefill_chunk``, accept the
        exact greedy prefix — greedy outputs are token-identical to plain
        decode, rejected suffixes roll back bit-exactly."""
        if legacy:
            if config is not None:
                raise TypeError("pass either an EngineConfig or the legacy "
                                f"flat kwargs, not both: {sorted(legacy)}")
            global _LEGACY_WARNED
            if not _LEGACY_WARNED:
                _LEGACY_WARNED = True
                warnings.warn(
                    "Engine(model, params, batch_slots=…, …) is deprecated; "
                    "pass Engine(model, params, EngineConfig(...)) — see the "
                    "migration table in src/repro/serve/README.md",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig.from_legacy(**legacy)
        if config is None:
            config = EngineConfig()
        self.config = config
        sch, mem = config.scheduler, config.memory
        self.model = model
        qcfg = (config.quant if config.quant is not None
                else getattr(model.cfg, "quant", None))
        if (qcfg is not None and qcfg.cache != "none"
                and not model.cfg.cache_quant):
            # cache shapes are baked into the model at construction
            raise ValueError(
                "quant.cache is a model-construction knob: build the model "
                "with ArchConfig.quant (init_cache allocates int8 + scales "
                "from it); the Engine quant= override only covers weights")
        if (qcfg is not None and qcfg.weight_bits is not None
                and not qt.tree_is_quantized(params)):
            params = jax.jit(
                lambda p: model.quantize_params(p, qcfg))(params)
        if qcfg is not None and getattr(qcfg, "activations", "none") != "none":
            # trace-time toggle: every step function jitted from here on
            # contracts int8 activation codes (W8A8/W4A8 kernels)
            structures.set_activations(qcfg.activations)
        self.params = params
        self.B = sch.slots
        self.max_len = mem.max_len
        self.chunk = max(1, int(sch.chunk_size))
        self.token_budget = (self.B * self.chunk if sch.token_budget is None
                             else max(1, int(sch.token_budget)))
        self.policy = sch.policy
        if self.policy not in ("priority", "fifo"):
            raise ValueError(f"unknown scheduler policy {sch.policy!r}")

        # -- cache storage: slot-static tree, or the paged pool -------------
        self._pc: PagedCache | None = None
        if mem.paged:
            n_pp = mem.max_len // mem.page_size
            pages = (self.B * n_pp + 1 if mem.pages is None
                     else int(mem.pages))
            snap = (max(4, pages // 4) if mem.snap_slots is None
                    else int(mem.snap_slots))
            self._pc = PagedCache(model, self.B, mem.max_len, mem.page_size,
                                  pages, snap, mem.prefix_sharing)
            self._paged_step = self._pc.make_step()
            self.cache = None
        else:
            self.cache = model.init_cache(self.B, mem.max_len)
            # one pristine row: the reset source for any slot
            self._template = model.init_cache(1, mem.max_len)
        # per-leaf batch-axis position (stacked layer caches carry a leading
        # "layers" axis, so batch is NOT uniformly axis 0)
        axes = model.cache_axes()
        is_axes = lambda x: x is None or (isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))
        self._batch_axis = jax.tree.map(
            lambda ax: ax.index("batch"), axes, is_leaf=is_axes)
        # in place: the reset consumes (donates) the cache it rewrites
        self._reset_row = jax.jit(
            functools.partial(_reset_row, self._batch_axis),
            donate_argnums=(0,))
        self.slots = [_Slot() for _ in range(self.B)]
        self._rr = 0  # round-robin start for budget allocation
        self.queue: list = []   # heap of (prio_key, seq, Request)
        self._seq = 0
        self.key = jax.random.PRNGKey(config.seed)
        self._step = step_fn if step_fn is not None else jax.jit(
            model.prefill_chunk)
        self.finished: list[Request] = []   # everything ever completed
        # host spans and per-tick records (serve/tracing.py)
        self.tracer = Tracer()
        self.stats = {"steps": 0, "prefill_tokens": 0, "decode_tokens": 0,
                      "prefill_time": 0.0, "decode_time": 0.0,
                      # speculative rounds: drafted/accepted counts per round
                      "spec_rounds": 0, "spec_drafted": 0, "spec_accepted": 0,
                      "spec_emitted": 0,
                      # multi-tenant serving signals
                      "preemptions": 0, "prefix_hit_tokens": 0,
                      "prompt_tokens_submitted": 0,
                      # resilience counters (serve/resilience.py)
                      "numeric_trips": 0, "degrade_spec_off": 0,
                      "degrade_act_float": 0, "numeric_error_failures": 0,
                      "step_errors": 0, "requeues": 0, "shed": 0,
                      "deadline_expired": 0}
        # async streaming state
        self._lock = threading.Lock()
        self._streams: dict[int, tuple[Request, asyncio.Queue]] = {}
        self._driver: asyncio.Task | None = None
        self._auto_uid = 1 << 40

        # -- resilience: guardrails, fault plan, watchdog, fault isolation --
        res = config.resilience
        self.resilience = res
        self.health = rsl.Health()
        self._deadline_s = sch.deadline_s
        self._deadlines_armed = sch.deadline_s is not None
        self._guard = (rsl.Guardrail(res.logit_absmax) if res.guardrails
                       else None)
        self.fault_plan: FaultPlan | None = (
            FaultPlan.from_spec(res.fault_spec) if res.fault_spec else None)
        self._iter = 0                    # step attempts (incl. failed ones)
        self._step_inflight_since: float | None = None   # watchdog stamp
        self._last_stepped: set[int] = set()
        self._probe: list[list[int]] = []   # fault-bisect uid groups
        self._cleared: set[int] = set()     # uids proven innocent this hunt
        self._serve_float = False    # alternation toggle: rung-2 isolation
        self._step_float = None      # lazy jit twins traced with act="none"
        self._paged_step_float = None
        self._watchdog = (rsl.Watchdog(self, res.watchdog_deadline_s)
                          if res.watchdog_deadline_s else None)

        self.spec_k = max(0, int(config.speculative.k))
        self.draft_rank_frac = float(config.speculative.draft_rank_frac)
        if self.spec_k:
            needed = ("draft_plan", "truncate_params", "rollback_cache")
            if not all(hasattr(model, a) for a in needed):
                raise ValueError(
                    "speculative decoding needs a model with "
                    f"{needed} (repro.models.transformer.LM)")
            self.draft_plan = model.draft_plan(self.params,
                                               self.draft_rank_frac)
            plan = self.draft_plan
            self.draft_params = jax.jit(
                lambda p: model.truncate_params(p, plan))(self.params)
            if config.prestack and hasattr(model, "prestack_params"):
                self.draft_params = model.prestack_params(self.draft_params)
            # the draft cache stays slot-static even in paged mode: it is
            # rewound/resynced every round, so it never holds a prefix worth
            # sharing, and k+1-token rounds keep its working set tiny
            self.draft_cache = model.init_cache(self.B, self.max_len)
            self._draft_template = model.init_cache(1, self.max_len)
            self._spec_round = jax.jit(self._make_spec_round())
            if self._pc is not None:
                self._paged_spec = self._pc.make_spec_step(
                    self._make_spec_round())
        if config.prestack and hasattr(model, "prestack_params"):
            # eager, not jitted: leaves the bundles do not replace stay the
            # caller's arrays (a jit would return a copy of every weight)
            self.params = model.prestack_params(self.params)

        # -- mesh parallelism: same engine code from 1 to N devices ---------
        self.parallel = getattr(model, "parallel", NO_PARALLEL)
        if config.mesh is not None:
            from repro.launch.mesh import parse_mesh
            dp, tp = parse_mesh(config.mesh)
            if ((dp, tp) != (1, 1)
                    and (not self.parallel.active
                         or self.parallel.dp_size != dp
                         or self.parallel.tp_size != tp)):
                raise ValueError(
                    f"EngineConfig.mesh={config.mesh!r} wants a {dp}x{tp} "
                    "mesh but the model was not built on one — construct it "
                    "with build_model(cfg, make_parallel(make_serving_mesh("
                    f"{dp}, {tp}), serve=True)) so params, activations and "
                    "collectives agree")
        self.sharding_report: dict | None = None
        if self.parallel.active:
            self._shard_state()
        if config.autotune.enabled:
            self._warm_autotune(qcfg, config.autotune.cache_path)

    def _shard_state(self) -> None:
        """Lay params and caches out on the model's mesh.

        Runs AFTER quantize/truncate/prestack, so the specs from
        launch/sharding.py land on the final pytrees: QArray ``{q, scale}``
        leaves get congruent specs (scales follow their codes' row/block
        axis) and prestacked GroupBundles shard their trailing rank/output
        axes per the bundle plan.  ``serve=True`` parallel means params are
        TP-sharded and data-replicated; slot caches shard batch over "data";
        the paged pool replicates pages (globally indexed) but TP-shards
        heads/state dims.  Also flips the trace-time TP-mesh toggle so
        Pallas grouped applies compiled from here on run one launch per
        bundle per shard, and records the replicated-leaf report the
        benchmarks surface."""
        from repro.launch import sharding as shd
        par = self.parallel
        axes = self.model.axes()
        self.params = jax.device_put(
            self.params, shd.tree_shardings(self.params, axes, par))
        caxes = self.model.cache_axes()
        if self._pc is not None:
            self._pc.shard(par)
        else:
            csh = shd.tree_shardings(self.cache, caxes, par)
            self.cache = jax.device_put(self.cache, csh)
            self._template = jax.device_put(
                self._template,
                shd.tree_shardings(self._template, caxes, par))
        if self.spec_k:
            self.draft_params = jax.device_put(
                self.draft_params,
                shd.tree_shardings(self.draft_params, axes, par))
            self.draft_cache = jax.device_put(
                self.draft_cache,
                shd.tree_shardings(self.draft_cache, caxes, par))
            self._draft_template = jax.device_put(
                self._draft_template,
                shd.tree_shardings(self._draft_template, caxes, par))
        if par.tp_size > 1 and par.model_axis is not None:
            structures.set_tp_mesh(par.mesh, par.model_axis)
        self.sharding_report = shd.replication_report(self.params, axes, par)

    def _make_spec_round(self):
        """Build the fused draft-verify round: ONE jitted dispatch per round.

        Drafting k tokens with host-side control costs k device syncs plus
        k+3 dispatches — more wall time than the k+1 plain steps it
        replaces.  Fusing the draft scan, the all-logits verify, the greedy
        accept, the cache rollback and the draft-cache resync into a single
        jitted function leaves one dispatch and one host transfer (the
        drafted/accepted token ids) per round.
        """
        model, k = self.model, self.spec_k
        Cv = _bucket(k + 1)
        absmax = (self.resilience.logit_absmax if self.resilience.guardrails
                  else None)

        def spec_round(p, dp, cache, dcache, cur, steps, live, budget):
            B = cur.shape[0]
            # -- draft: k single-token steps on a throwaway dcache copy
            def body(carry, i):
                c, tok = carry
                lg, c = model.prefill_chunk(dp, c, tok[:, None], steps + i,
                                            live)
                nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                return (c, nxt), nxt
            _, seq = jax.lax.scan(body, (dcache, cur),
                                  jnp.arange(k, dtype=jnp.int32))
            draft_toks = seq.T                                     # (B, k)
            # -- verify: one full-model all-logits chunk over [t0, d_1..d_k]
            pad = jnp.zeros((B, Cv - k - 1), jnp.int32)
            vt = jnp.concatenate([cur[:, None], draft_toks, pad], axis=1)
            lg, new_cache = model.prefill_chunk(
                p, cache, vt, steps, live * (k + 1),
                all_logits=True, collect_states=True)
            with jax.named_scope("sample"):
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (B, Cv)
            # -- accept: longest greedy-matching draft prefix (+ bonus)
            match = draft_toks == greedy[:, :k]
            n_acc = jnp.where(match.all(axis=1), k,
                              jnp.argmax(~match, axis=1)).astype(jnp.int32)
            n_comm = jnp.minimum(n_acc + 1, budget) * live
            # -- guardrail: per-row health of the verify logits (the k+1
            # real columns only — bucket padding never gates a row)
            ok = structures.row_health(lg[:, :k + 1], absmax=absmax)
            # -- commit: bit-exact rewind + one ragged draft resync chunk
            cache = model.rollback_cache(cache, new_cache, steps, n_comm)
            _, dcache = model.prefill_chunk(dp, dcache, vt, steps, n_comm)
            return cache, dcache, draft_toks, greedy, n_acc, n_comm, ok

        return spec_round

    def _warm_autotune(self, qcfg, cache_path: str | None):
        """Tune the fused-kernel tiling for every unique BLAST shape this
        model dispatches, at the decode (B rows) and full-prefill-chunk
        widths this engine will actually run, then persist the cache."""
        from repro.kernels import autotune as at

        at.enable(cache_path)
        kind = {None: "float", 8: "int8", 4: "int4"}[
            qcfg.weight_bits if qcfg is not None else None]
        act = (getattr(qcfg, "activations", "none")
               if qcfg is not None else "none")
        dtype = jnp.dtype(self.model.cfg.compute_dtype)
        widths = sorted({self.B, self.B * _bucket(self.chunk)})
        shapes = []
        for spec in getattr(self.model, "linear_specs", list)():
            if spec.kind == "blast":
                shapes.append((spec.d_out, spec.d_in, spec.meta["b"],
                               spec.meta["r"]))
        if self.spec_k:
            # the draft model dispatches the same blocked shapes at the
            # truncated ranks — warm those too (draft steps run at decode
            # width and at the verify chunk width)
            shapes += _blast_shapes(self.draft_params)
        tp = self.parallel.tp_size
        if tp > 1:
            # under shard_map each device contracts its rank shard, so the
            # kernels launch at the LOCAL rank — warm those keys too
            shapes += [(m, n, b, r // tp)
                       for (m, n, b, r) in shapes if r % tp == 0]
        seen = set()
        for d_out, d_in, b, r in shapes:
            for T in widths:
                key = (T, d_out, d_in, b, r)
                if key in seen:
                    continue
                seen.add(key)
                at.tune_blast(T, d_out, d_in, b, r, dtype=dtype,
                              kind=kind, act=act, reps=1)
        at.save()

    # -- public ---------------------------------------------------------------

    def submit(self, req: Request):
        if not req.prompt:
            raise ValueError(f"request {req.uid}: empty prompt (generation "
                             "needs at least one conditioning token)")
        with self._lock:
            self._submit_locked(req)

    def _submit_locked(self, req: Request):
        req.t_submit = time.perf_counter()
        if req.deadline_s is not None:
            self._deadlines_armed = True
        self.stats["prompt_tokens_submitted"] += len(req.prompt)
        self._enqueue(req)

    def _prio(self, req: Request) -> int:
        """Effective scheduling priority: FIFO mode ignores priority
        classes entirely (arrival order, no priority preemption) — it is
        the baseline the serving benchmark contrasts against."""
        return req.priority if self.policy == "priority" else 0

    def _enqueue(self, req: Request):
        self._seq += 1
        heapq.heappush(self.queue, (self._prio(req), self._seq, req))

    def run(self, max_iters: int = 10_000) -> list[Request]:
        """Drive until queue + slots drain.  Returns completed requests."""
        n0 = len(self.finished)
        for _ in range(max_iters):
            with self._lock:
                if not self._tick_locked():
                    break
        return self.finished[n0:]

    def tick(self) -> bool:
        """One scheduler iteration (public: trace-driven benchmarks submit
        between ticks).  Returns False once queue + slots are drained."""
        with self._lock:
            return self._tick_locked()

    def _tick_locked(self) -> bool:
        """One scheduler iteration.  Returns False when fully drained.

        The jitted-step block runs under fault isolation: an exception
        never escapes the tick — the implicated request is failed (or the
        batch bisected until it is found) and every other active request is
        re-queued through deterministic recompute-on-resume.  The engine
        itself cannot be crashed by a poisoned step.

        The tick and its phases are spans of ``self.tracer``; a tick that
        runs a step leaves one record in its ring."""
        tr = self.tracer
        with tr.tick(self._iter, self.slots):
            with tr.span("admit"):
                self._expire_deadlines()
                self._shed_overflow()
                self._admit()
            tr.queued(len(self.queue))
            if not any(s.req for s in self.slots):
                return bool(self.queue)
            try:
                if self.spec_k and self._spec_eligible():
                    self._advance_spec(self.finished)
                else:
                    self._advance(self.finished)
            except Exception as exc:   # fault isolation — never the batch
                self._step_inflight_since = None
                self._handle_step_error(exc)
                return True
            if self._probe and self._last_stepped:
                # a clean step clears its participants: the culprit cannot
                # have been among them, so the bisect narrows
                self._cleared |= self._last_stepped
                self._probe[0] = [u for u in self._probe[0]
                                  if u not in self._last_stepped]
                self._prune_probe()
            return True

    def generate_batch(self, prompts, sampling: SamplingParams | None = None,
                       priority: int = 0) -> list[Request]:
        """Sync convenience wrapper: submit every prompt, drive to drain,
        return the requests in input order."""
        sampling = sampling or SamplingParams()
        reqs = []
        for prompt in prompts:
            with self._lock:
                uid = self._auto_uid
                self._auto_uid += 1
            req = Request(uid=uid, prompt=list(prompt),
                          max_new_tokens=sampling.max_new_tokens,
                          temperature=sampling.temperature, priority=priority)
            reqs.append(req)
            self.submit(req)
        self.run()
        return reqs

    async def generate(self, prompt, sampling: SamplingParams | None = None,
                       *, priority: int = 0, prefix_len: int | None = None,
                       uid: int | None = None,
                       deadline_s: float | None = None):
        """Async token stream for one request.  Closing the iterator early
        (client disconnect) cancels the request and releases its pages
        immediately.  All concurrent ``generate`` calls batch through one
        shared driver task, so streams interleave at engine-iteration
        granularity."""
        sampling = sampling or SamplingParams()
        with self._lock:
            if uid is None:
                uid = self._auto_uid
                self._auto_uid += 1
        req = Request(uid=uid, prompt=list(prompt),
                      max_new_tokens=sampling.max_new_tokens,
                      temperature=sampling.temperature, priority=priority,
                      prefix_len=prefix_len, deadline_s=deadline_s)
        q: asyncio.Queue = asyncio.Queue()
        with self._lock:
            self._streams[uid] = (req, q)
            self._submit_locked(req)
        self._ensure_driver()
        try:
            while True:
                tok = await q.get()
                if tok is None:
                    break
                yield tok
        finally:
            if not req.done:
                self.cancel(uid)
            with self._lock:
                self._streams.pop(uid, None)

    def cancel(self, uid: int):
        """Abort a queued or running request: its slot (pages, state rows,
        speculative draft-cache row) is released immediately, not at the
        next natural recycle."""
        with self._lock:
            for i, (_, _, req) in enumerate(self.queue):
                if req.uid == uid:
                    self.queue.pop(i)
                    heapq.heapify(self.queue)
                    self._finish(req, "cancelled")
                    return
            for b, slot in enumerate(self.slots):
                if slot.req is not None and slot.req.uid == uid:
                    req = slot.req
                    self._release_slot(b)
                    self._finish(req, "cancelled")
                    return

    def _ensure_driver(self):
        if self._driver is None or self._driver.done():
            self._driver = asyncio.get_running_loop().create_task(
                self._drive())

    async def _drive(self):
        """Single background task batching all async ``generate`` streams:
        run one engine iteration in a worker thread, flush freshly emitted
        tokens to each stream's queue, repeat until drained.  State is
        guarded by ``self._lock`` (``cancel``/``submit`` may run on the
        loop thread while an iteration runs in the worker)."""
        emitted: dict[int, int] = {}
        while True:
            with self._lock:
                work = bool(self.queue) or any(s.req for s in self.slots)
            if not work:
                break
            try:
                await asyncio.to_thread(self._tick_threadsafe)
            except Exception as exc:
                # _tick_locked already contains step faults; anything that
                # still escapes is a driver bug — fail every in-flight
                # request (streams see their terminator) instead of wedging
                self.health.record_error(exc)
                self.health.degrade(f"driver: {type(exc).__name__}")
                with self._lock:
                    for b, s in enumerate(self.slots):
                        if s.req is not None:
                            req = s.req
                            self._release_slot(b)
                            self._finish(req, "error")
                    while self.queue:
                        _, _, req = heapq.heappop(self.queue)
                        self._finish(req, "error")
                self._flush_streams(emitted)
                break
            self._flush_streams(emitted)
        self._flush_streams(emitted)

    def _tick_threadsafe(self):
        with self._lock:
            self._tick_locked()

    def _flush_streams(self, emitted: dict[int, int]):
        with self._lock:
            streams = list(self._streams.values())
        for req, q in streams:
            sent = emitted.get(req.uid, 0)
            for tok in req.output[sent:]:
                q.put_nowait(tok)
            emitted[req.uid] = len(req.output)
            if req.done:
                q.put_nowait(None)
                emitted.pop(req.uid, None)
                with self._lock:
                    self._streams.pop(req.uid, None)

    def _spec_eligible(self) -> bool:
        """Speculative rounds run only when every active slot is in greedy
        decode (prompt fully ingested, ≥1 sampled token) at degradation
        rung 0.  Prefill chunks and temperature sampling use the plain path
        — exactness of the accept rule needs argmax on both sides — and a
        guardrail-tripped request has already traded its draft away
        (ladder rung 1: ``spec_off``)."""
        active = [s for s in self.slots if s.req is not None]
        return bool(active) and all(
            not s.to_feed and s.req.output and s.req.temperature == 0
            and s.req.degrade_level == 0
            for s in active)

    def throughput(self) -> dict:
        """Prefill / decode tokens-per-second split from engine stats."""
        s = self.stats
        out = {
            "steps": s["steps"],
            "prefill_tok_s": (s["prefill_tokens"] / s["prefill_time"]
                              if s["prefill_time"] else 0.0),
            "decode_tok_s": (s["decode_tokens"] / s["decode_time"]
                             if s["decode_time"] else 0.0),
        }
        if self.spec_k:
            out["spec_rounds"] = s["spec_rounds"]
            out["acceptance_rate"] = (s["spec_accepted"] / s["spec_drafted"]
                                      if s["spec_drafted"] else 0.0)
            out["tokens_per_round"] = (s["spec_emitted"] / s["spec_rounds"]
                                       if s["spec_rounds"] else 0.0)
        return out

    def overloaded(self) -> bool:
        """Admission-control signal the HTTP frontend turns into 429 +
        Retry-After.  Lock-free on purpose: a hung step holds the engine
        lock, and shedding decisions must keep answering while it does."""
        hw = self.resilience.queue_high_water
        if hw is None:
            return False
        n_active = sum(1 for s in self.slots if s.req is not None)
        return len(self.queue) + n_active >= hw

    def healthz(self) -> dict:
        """Live condition snapshot for ``GET /healthz``.  Reads only the
        health lock (never the engine lock): this must answer while a step
        is wedged — detecting exactly that is the watchdog's job."""
        snap = self.health.snapshot()
        snap["queue_depth"] = len(self.queue)
        snap["active"] = sum(1 for s in self.slots if s.req is not None)
        snap["slots"] = self.B
        snap["overloaded"] = self.overloaded()
        if self._pc is not None:
            snap.update(self._pc.occupancy())
        return snap

    def resilience_report(self) -> dict:
        """Resilience counters + fault-plan fire log (chaos benchmark)."""
        s = self.stats
        out = {"health": self.health.snapshot(),
               "numeric_trips": s["numeric_trips"],
               "degrade_spec_off": s["degrade_spec_off"],
               "degrade_act_float": s["degrade_act_float"],
               "numeric_error_failures": s["numeric_error_failures"],
               "step_errors": s["step_errors"],
               "requeues": s["requeues"],
               "shed": s["shed"],
               "deadline_expired": s["deadline_expired"]}
        if self.fault_plan is not None:
            out["faults"] = self.fault_plan.report()
        return out

    def close(self):
        """Stop the watchdog thread (idempotent).  The engine itself holds
        no other background resources."""
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None

    def sla_report(self) -> dict:
        """TTFT / TPOT percentiles per priority class, plus the multi-tenant
        counters (preemption + prefix-hit rates, and the queue depth after
        admission over the ring of tick records, ``self.tracer``).

        Every finished request contributes to its class's ``requests`` and
        ``stop_reasons`` counts, but only requests that actually produced a
        first token contribute latency samples — a class whose requests were
        all shed/cancelled/expired reports explicit ``None`` percentiles
        rather than a fabricated 0.0 (or a ZeroDivisionError)."""
        def pct(xs, q):
            return float(np.percentile(np.asarray(xs), q)) if xs else None

        classes: dict[int, dict] = {}
        for r in self.finished:
            c = classes.setdefault(r.priority, {"ttft": [], "tpot": [],
                                                "requests": 0,
                                                "stop_reasons": {}})
            c["requests"] += 1
            reason = r.stop_reason or "unknown"
            c["stop_reasons"][reason] = c["stop_reasons"].get(reason, 0) + 1
            if r.t_submit is None or r.t_first is None:
                continue
            c["ttft"].append(r.t_first - r.t_submit)
            if r.t_done is not None and len(r.output) > 1:
                c["tpot"].append((r.t_done - r.t_first)
                                 / (len(r.output) - 1))
        per_class = {
            str(p): {"requests": c["requests"],
                     "completed": len(c["ttft"]),
                     "stop_reasons": c["stop_reasons"],
                     "ttft_p50_s": pct(c["ttft"], 50),
                     "ttft_p99_s": pct(c["ttft"], 99),
                     "tpot_p50_s": pct(c["tpot"], 50),
                     "tpot_p99_s": pct(c["tpot"], 99)}
            for p, c in sorted(classes.items())}
        s = self.stats
        depth = self.tracer.records()["queue"]
        out = {
            "classes": per_class,
            "preemptions": s["preemptions"],
            "preemption_rate": (s["preemptions"] / len(self.finished)
                                if self.finished else 0.0),
            "prefix_hit_tokens": s["prefix_hit_tokens"],
            "prefix_hit_rate": (s["prefix_hit_tokens"]
                                / s["prompt_tokens_submitted"]
                                if s["prompt_tokens_submitted"] else 0.0),
            "queue_depth_p50": (float(np.percentile(depth, 50))
                                if len(depth) else 0.0),
            "queue_depth_max": int(depth.max()) if len(depth) else 0,
            "resilience": self.resilience_report(),
        }
        if self._pc is not None:
            out["pool_tokens"] = self._pc.pool_tokens()
            out["pool_pages_free"] = self._pc.pages.n_free
            out["cache_bytes"] = self._pc.nbytes()
        return out

    # -- internals --------------------------------------------------------------

    def _reset_slot(self, b: int):
        if self._pc is not None:
            self._pc.reset_slot(b)
        else:
            self.cache = self._reset_row(self.cache, self._template,
                                         jnp.int32(b))
        if self.spec_k:
            self.draft_cache = self._reset_row(
                self.draft_cache, self._draft_template, jnp.int32(b))

    def _release_slot(self, b: int):
        """Free everything a departing request holds: its pages, its
        state-leaf rows, and its speculative draft-cache row."""
        slot = self.slots[b]
        if self._pc is not None:
            self._pc.free_slot(b)
        self._reset_slot(b)
        slot.req = None
        slot.to_feed = deque()
        slot.feed = []
        slot.reg_at = None
        slot.pos = 0

    def _finish(self, req: Request, reason: str):
        req.done = True
        req.stop_reason = reason
        req.truncated = reason == "capacity"
        req.t_done = time.perf_counter()
        self.finished.append(req)

    def _finish_slot(self, b: int, reason: str):
        req = self.slots[b].req
        self._release_slot(b)
        self._finish(req, reason)

    def _preempt(self, b: int):
        """Evict slot b's request: free its pages and state, re-queue it for
        recompute-on-resume (its sampled output is kept; the resumed request
        re-feeds prompt + output, and may hit its own registered prefix)."""
        req = self.slots[b].req
        self._release_slot(b)
        req.n_preempted += 1
        self.stats["preemptions"] += 1
        # the resume re-feeds prompt + output; count it into the prefix-hit
        # denominator so re-admission hits keep the rate a true fraction
        self.stats["prompt_tokens_submitted"] += (len(req.prompt)
                                                  + len(req.output))
        self._enqueue(req)

    # -- resilience internals (serve/resilience.py, serve/faults.py) -----------

    def _requeue_slot(self, b: int):
        """Release slot b and re-queue its request through the deterministic
        recompute-on-resume path (same mechanics as preemption: the sampled
        output is kept, the resume re-feeds prompt + output, and the cache
        row is rebuilt from tokens — a poisoned row is never patched)."""
        req = self.slots[b].req
        self._release_slot(b)
        self.stats["requeues"] += 1
        self.stats["prompt_tokens_submitted"] += (len(req.prompt)
                                                  + len(req.output))
        self._enqueue(req)

    def _numeric_trip(self, b: int):
        """Walk slot b's request one rung down the degradation ladder
        (resilience.DEGRADE_LADDER): spec off → float activations → fail
        with ``numeric_error``.  Only this request is touched."""
        req = self.slots[b].req
        self.stats["numeric_trips"] += 1
        with self.health._lock:
            self.health.numeric_trips += 1
        req.degrade_level += 1
        if req.degrade_level > len(rsl.DEGRADE_LADDER):
            self._release_slot(b)
            self.stats["numeric_error_failures"] += 1
            self._finish(req, "numeric_error")
            return
        rung = rsl.DEGRADE_LADDER[req.degrade_level - 1]
        req.degrade_path.append(rung)
        self.stats["degrade_" + rung] += 1
        self._requeue_slot(b)

    def _poll_faults_pre(self, sched_uids):
        """Arm the pre-dispatch fault kinds: an injected stall runs inside
        the already-open watchdog window; an injected driver error raises
        out of the step exactly like an opaque XLA failure would."""
        plan = self.fault_plan
        if plan is None:
            return
        for f in plan.poll("slow_step", self._iter, sched_uids):
            time.sleep(f.delay_s)
        for f in plan.poll("driver_error", self._iter, sched_uids):
            raise FaultError(
                f"injected driver fault at iteration {self._iter} "
                f"({f.describe()})", uid=f.uid if f.known else None)

    def _inject_nan(self, ok: np.ndarray, sched_uids) -> np.ndarray:
        """Merge due nan_logits faults into a step's ok mask (injection is
        a detector-level poison: the row is treated exactly as if the
        guardrail had caught real NaNs, without writing NaNs into the
        cache that deterministic recovery then depends on)."""
        plan = self.fault_plan
        if plan is not None:
            for f in plan.poll("nan_logits", self._iter, sched_uids):
                for b, slot in enumerate(self.slots):
                    if slot.req is not None and slot.req.uid == f.uid:
                        ok[b] = False
        return ok

    def _row_health(self, logits, sched_uids) -> np.ndarray | None:
        """(B,) ok mask for this step's logits, or None when the guardrail
        is off (no detector → injected nan faults stay dormant too)."""
        if self._guard is None:
            return None
        ok = np.asarray(self._guard.ok_rows(logits)).astype(bool)
        return self._inject_nan(ok, sched_uids)

    def _note_step_done(self, dt: float):
        """A step finished cleanly: once no culprit hunt is in flight and
        the step came in under the watchdog deadline, the engine is
        healthy again."""
        h = self.health
        if h.state != "degraded" or self._probe:
            return
        if self._watchdog is not None and dt > self._watchdog.deadline_s:
            return
        h.recover()

    def _requeue_error(self, b: int):
        """Requeue slot b after a step exception, failing the request
        outright once it has been implicated more than
        ``ResilienceConfig.step_error_limit`` times (bounds livelock under
        a persistent whole-batch fault)."""
        req = self.slots[b].req
        req.n_step_errors += 1
        if req.n_step_errors > self.resilience.step_error_limit:
            self._release_slot(b)
            self._finish(req, "error")
            return
        self._requeue_slot(b)

    def _handle_step_error(self, exc: Exception):
        """Contain a step exception: fail only the implicated request,
        requeue everything else through recompute-on-resume.  When the
        exception does not name a culprit (``exc.uid``), bisect across
        subsequent ticks — admission is restricted to one probe group at a
        time until a failing step leaves a singleton suspect."""
        self.stats["step_errors"] += 1
        self.health.record_error(exc)
        self.health.degrade(f"step error: {type(exc).__name__}: {exc}")
        self._last_stepped = set()
        active = {s.req.uid: b for b, s in enumerate(self.slots)
                  if s.req is not None}
        culprit = None
        uid = getattr(exc, "uid", None)
        if uid is not None and uid in active:
            culprit = uid
        else:
            suspects = sorted(u for u in active if u not in self._cleared)
            if not suspects:
                # the innocence evidence was wrong (e.g. a fault arming
                # later than the hunt began): restart over everything active
                self._cleared = set()
                suspects = sorted(active)
            if len(suspects) == 1:
                culprit = suspects[0]
            else:
                self._probe = rsl.bisect_groups(suspects)
        if culprit is not None:
            b = active.pop(culprit)
            req = self.slots[b].req
            self._release_slot(b)
            self._finish(req, "error")
            self._probe, self._cleared = [], set()
        # every other active request re-queues: the paged tables were
        # already mutated for this iteration's allocation, so nothing may
        # keep running on it
        for b, s in enumerate(self.slots):
            if s.req is not None:
                self._requeue_error(b)

    def _prune_probe(self):
        """Drop probe uids that are gone (finished) or proven innocent;
        advance to the next group when the head empties; end the hunt when
        no groups remain."""
        if not self._probe:
            return
        present = {r.uid for _, _, r in self.queue}
        present |= {s.req.uid for s in self.slots if s.req is not None}
        while self._probe:
            self._probe[0] = [u for u in self._probe[0]
                              if u in present and u not in self._cleared]
            if self._probe[0]:
                return
            self._probe.pop(0)
        self._cleared = set()

    def _expire_deadlines(self):
        """Fail queued and running requests past their end-to-end deadline
        (``Request.deadline_s`` overriding ``SchedulerConfig.deadline_s``),
        measured from submit — a deadline survives preemption and requeues."""
        if not self._deadlines_armed:
            return
        now = time.perf_counter()

        def expired(req: Request) -> bool:
            dl = (req.deadline_s if req.deadline_s is not None
                  else self._deadline_s)
            return (dl is not None and req.t_submit is not None
                    and now - req.t_submit > dl)

        keep = [item for item in self.queue if not expired(item[2])]
        if len(keep) != len(self.queue):
            for item in self.queue:
                if expired(item[2]):
                    self.stats["deadline_expired"] += 1
                    self._finish(item[2], "deadline")
            self.queue = keep
            heapq.heapify(self.queue)
        for b, slot in enumerate(self.slots):
            if slot.req is not None and expired(slot.req):
                self.stats["deadline_expired"] += 1
                self._finish_slot(b, "deadline")

    def _shed_overflow(self):
        """Admission control: above ``ResilienceConfig.queue_high_water``
        total requests in flight, shed the lowest-priority newest queued
        work (``stop_reason="shed"``) — the HTTP frontend surfaces the same
        signal as 429 + Retry-After before requests ever reach the queue."""
        hw = self.resilience.queue_high_water
        if hw is None:
            return
        n_active = sum(1 for s in self.slots if s.req is not None)
        while self.queue and len(self.queue) + n_active > hw:
            i = max(range(len(self.queue)),
                    key=lambda j: (self.queue[j][0], self.queue[j][1]))
            _, _, req = self.queue.pop(i)
            heapq.heapify(self.queue)
            self.stats["shed"] += 1
            self._finish(req, "shed")

    def _queue_head_idx(self) -> int | None:
        """Index of the next admissible queued request: the heap head
        normally; during a culprit hunt, the best-keyed request from the
        current probe group (or already proven innocent)."""
        if not self.queue:
            return None
        if not self._probe:
            return 0
        allowed = set(self._probe[0]) | self._cleared
        best = None
        for i, (p, s, req) in enumerate(self.queue):
            if req.uid in allowed and (best is None or (p, s) < best[0]):
                best = ((p, s), i)
        return None if best is None else best[1]

    def _float_plain_step(self):
        """Lazy jit twin of the step function traced with float activations
        (ladder rung 2).  A distinct jit object traces separately, so the
        int8-activation fast path keeps its own compiled programs; weights
        stay quantized — only the per-token activation rounding is gone."""
        if self._step_float is None:
            jfn = jax.jit(self.model.prefill_chunk)

            def call(*a):
                with structures.activations("none"):
                    return jfn(*a)
            self._step_float = call
        return self._step_float

    def _float_paged_step(self):
        if self._paged_step_float is None:
            jfn = self._pc.make_step()

            def call(*a):
                with structures.activations("none"):
                    return jfn(*a)
            self._paged_step_float = call
        return self._paged_step_float

    def _victim(self, below: int, exclude: set[int]) -> int | None:
        """Deterministic preemption victim: among active slots with strictly
        lower priority than ``below`` (higher number), the longest-running
        (most output tokens), ties to the highest slot index."""
        best = None
        for b, slot in enumerate(self.slots):
            if b in exclude or slot.req is None:
                continue
            if self._prio(slot.req) <= below:
                continue
            key = (self._prio(slot.req), len(slot.req.output), b)
            if best is None or key > best[0]:
                best = (key, b)
        return None if best is None else best[1]

    # -- admission -------------------------------------------------------------

    def _pages_needed(self, feed_len: int, hit: int) -> int:
        """Pages a request still needs to ingest its feed and sample once
        (admission gate; decode growth beyond that is handled by the
        in-step escalation)."""
        pc = self._pc
        if pc is None or not pc.has_paged:
            return 0
        return (feed_len + 1 + pc.ps - 1) // pc.ps - hit // pc.ps

    def _admit(self):
        self._prune_probe()
        for b, slot in enumerate(self.slots):
            if slot.req is not None or not self.queue:
                continue
            qi = self._queue_head_idx()
            if qi is None:
                return   # culprit hunt: nothing admissible this tick
            item = self.queue.pop(qi)
            heapq.heapify(self.queue)
            prio, _, req = item
            feed = req.prompt + req.output   # resume recomputes its output
            if self._pc is not None:
                hit = self._pc.prefix_lookup(feed)
                hit_len = hit.length if hit else 0
                need = self._pages_needed(len(feed), hit_len)
                # admission never preempts equal-or-higher priority work and
                # never waits on it either: evict cold prefix entries, then
                # strictly-lower-priority victims, else leave it queued
                while need > self._pc.pages.n_free:
                    if self._pc.evict_one(require_free=True):
                        # eviction can invalidate the hit entry — re-resolve
                        hit = self._pc.prefix_lookup(feed)
                        hit_len = hit.length if hit else 0
                        need = self._pages_needed(len(feed), hit_len)
                        continue
                    v = self._victim(self._prio(req), exclude=set())
                    if v is not None:
                        self._preempt(v)
                        continue
                    break
                if need > self._pc.pages.n_free:
                    if any(s.req for s in self.slots):
                        # wait for running work to free pages — the original
                        # heap key goes back, so arrival order is preserved
                        heapq.heappush(self.queue, item)
                        return
                    # sole candidate and the whole pool is still too small:
                    # this request can never fit
                    self._finish(req, "capacity")
                    continue
            self._reset_slot(b)
            if req.t_admit is None:
                req.t_admit = time.perf_counter()
            slot.req = req
            slot.pos = 0
            slot.feed = feed
            slot.to_feed = deque(feed)
            slot.reg_at = None
            if self._pc is not None:
                if hit is not None:
                    self._pc.prefix_admit(b, hit)
                    slot.pos = hit.length
                    slot.to_feed = deque(feed[hit.length:])
                    self.stats["prefix_hit_tokens"] += hit.length
                self._plan_registration(b, slot, hit)

    def _plan_registration(self, b: int, slot: _Slot, hit):
        """Decide where this request registers its prompt prefix.

        Pure-KV families register every page-aligned level once the prompt
        is ingested (page refs are free).  Families with recurrent/ring
        state pay one snapshot slot per entry, so they register a single
        boundary — the request's ``prefix_len`` hint, else the largest
        level a later *identical* prompt could still hit — and prefill
        chunks are clipped to land exactly on it."""
        pc = self._pc
        if not pc.sharing:
            return
        L = len(slot.feed)
        if pc.has_state:
            cap = slot.req.prefix_len if slot.req.prefix_len else L - 1
            reg = (min(cap, L) // pc.ps) * pc.ps
        else:
            reg = (L // pc.ps) * pc.ps
        covered = hit.length if hit else 0
        if reg > covered and reg > slot.pos:
            slot.reg_at = reg

    # -- scheduling ------------------------------------------------------------

    def _is_float(self, slot: _Slot) -> bool:
        """Ladder rung 2+: this request's steps run the float-activation
        trace (resilience.DEGRADE_LADDER)."""
        return (slot.req is not None
                and slot.req.degrade_level >= len(rsl.DEGRADE_LADDER))

    def _pick_mode(self) -> tuple[bool, bool]:
        """(float_mode, partitioned): which activation trace this iteration
        steps, and whether BOTH kinds of row are active.  Partitioned ticks
        alternate between the two sets — rung-2 rows never share a batch
        with rung-0/1 rows, so degrading one request cannot perturb the
        tokens of any other (the int8 and float traces are separate jitted
        programs; a row's logits depend only on its own cache row, but the
        trace choice is batch-global)."""
        has_f = any(self._is_float(s) for s in self.slots)
        has_n = any(s.req is not None and not self._is_float(s)
                    for s in self.slots)
        if has_f and has_n:
            self._serve_float = not self._serve_float
            return self._serve_float, True
        return has_f, False

    def _schedule(self, float_mode: bool = False) -> np.ndarray:
        """Token-budget pass: decodes first (1 token each, latency), then
        prefills split the remaining budget into ≤chunk_size chunks.  Slots
        are visited in round-robin order so a budget tighter than the active
        slot count rotates starvation instead of pinning it to high slots.
        Only rows matching ``float_mode`` (degradation rung 2+ vs below) are
        scheduled — the two activation traces never share a batch."""
        n = np.zeros((self.B,), np.int32)
        budget = self.token_budget
        order = [(b + self._rr) % self.B for b in range(self.B)]
        self._rr = (self._rr + 1) % self.B
        for b in order:
            slot = self.slots[b]
            if (slot.req is not None and not slot.to_feed and budget > 0
                    and self._is_float(slot) == float_mode):
                n[b] = 1
                budget -= 1
        for b in order:
            slot = self.slots[b]
            if (slot.req is None or not slot.to_feed
                    or self._is_float(slot) != float_mode):
                continue
            room = self.max_len - 1 - slot.pos  # leave headroom to sample
            take = min(len(slot.to_feed), self.chunk, budget, max(room, 0))
            if (slot.reg_at is not None and self._pc.has_state
                    and slot.pos < slot.reg_at):
                # land a chunk boundary exactly on the registration point so
                # the state snapshot corresponds to the registered tokens
                take = min(take, slot.reg_at - slot.pos)
            n[b] = take
            budget -= take
        return n

    def _alloc(self, n: np.ndarray) -> list:
        """Allocate pool pages for every scheduled row's write window,
        escalating on a dry pool: evict cold prefix entries → preempt a
        strictly-lower-priority victim → shrink the prefill take → as a
        last resort preempt the row itself (or capacity-finish it when it
        is the only active request and the empty pool still cannot hold
        it).  Returns per-slot (fresh, triples) plans."""
        pc = self._pc
        plans = [([], []) for _ in range(self.B)]
        allocated: set[int] = set()
        for b in range(self.B):
            slot = self.slots[b]
            if slot.req is None or n[b] == 0:
                continue
            while True:
                plan = pc.plan_writes(b, slot.pos, int(n[b]))
                if plan is not None:
                    plans[b] = plan
                    allocated.add(b)
                    break
                if pc.evict_one(require_free=True):
                    continue
                v = self._victim(self._prio(slot.req), allocated | {b})
                if v is not None:
                    if n[v]:
                        n[v] = 0
                    self._preempt(v)
                    continue
                take = pc.max_take(b, slot.pos)
                if slot.to_feed and take > 0:
                    n[b] = min(int(n[b]), take)
                    continue
                if sum(1 for s in self.slots if s.req is not None) == 1:
                    # the whole pool is free for this one request and its
                    # next token still does not fit: genuine capacity end
                    self._finish_slot(b, "capacity")
                else:
                    self._preempt(b)
                n[b] = 0
                break
        return plans

    def _pack_plans(self, plans: list):
        """Flatten per-slot page plans into the bucketed device operands:
        fresh page ids (pad: n_pages → reset drops them) and write-window
        (row, logical, physical) triples (pad: phys=n_pages → scatter
        drops them)."""
        pc = self._pc
        fresh = [p for f, _ in plans for p in f]
        triples = [t for _, ts in plans for t in ts]
        F = _bucket(max(len(fresh), 1))
        M = _bucket(max(len(triples), 1))
        fresh_a = np.full((F,), pc.n_pages, np.int32)
        fresh_a[:len(fresh)] = fresh
        rows = np.zeros((M,), np.int32)
        lps = np.zeros((M,), np.int32)
        phys = np.full((M,), pc.n_pages, np.int32)
        for i, (r, lp, p) in enumerate(triples):
            rows[i], lps[i], phys[i] = r, lp, p
        return (jnp.asarray(fresh_a), jnp.asarray(rows), jnp.asarray(lps),
                jnp.asarray(phys))

    def _advance(self, finished: list[Request]):
        tr = self.tracer
        with tr.span("schedule"):
            float_mode, partitioned = self._pick_mode()
            n = self._schedule(float_mode)
            if not n.any() and partitioned:
                # the selected set had no headroom this tick; try the other
                float_mode = not float_mode
                n = self._schedule(float_mode)
            plans = None
            if self._pc is not None:
                plans = self._alloc(n)
            if not n.any():  # every active slot is out of cache headroom
                for b, slot in enumerate(self.slots):
                    if slot.req is not None:
                        self._finish_slot(b, "capacity")
                return
            C = _bucket(int(n.max()))
            tokens = np.zeros((self.B, C), np.int32)
            steps = np.zeros((self.B,), np.int32)
            sampling = [False] * self.B
            prompt_toks = 0
            decode_toks = 0
            for b, slot in enumerate(self.slots):
                if slot.req is None or n[b] == 0:
                    continue
                steps[b] = slot.pos
                if slot.to_feed:
                    prompt_toks += int(n[b])
                    for i in range(n[b]):
                        tokens[b, i] = slot.to_feed.popleft()
                    sampling[b] = len(slot.to_feed) == 0  # holds prompt end
                else:
                    decode_toks += 1
                    tokens[b, 0] = slot.req.output[-1]
                    sampling[b] = True
            if self._pc is not None:
                fresh, rows, lps, phys = self._pack_plans(plans)
        tr.step(self.B, C, prompt_toks, decode_toks)
        self._iter += 1
        sched_uids = [self.slots[b].req.uid for b in range(self.B)
                      if self.slots[b].req is not None and n[b]]
        self._last_stepped = set(sched_uids)
        t0 = time.perf_counter()
        self._step_inflight_since = time.monotonic()   # watchdog window opens
        with tr.span("dispatch"):
            self._poll_faults_pre(sched_uids)
            if self._pc is not None:
                pc = self._pc
                pstep = (self._float_paged_step() if float_mode
                         else self._paged_step)
                logits, pool, static = pstep(
                    self.params, tuple(pc.pool), tuple(pc.static),
                    jnp.asarray(pc.tables), fresh, rows, lps, phys,
                    jnp.asarray(tokens), jnp.asarray(steps), jnp.asarray(n))
                pc.pool, pc.static = list(pool), list(static)
            else:
                step = self._float_plain_step() if float_mode else self._step
                logits, self.cache = step(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(steps), jnp.asarray(n))
            if self.spec_k and not float_mode:
                # keep the draft cache in sync through prefill / non-greedy
                # iterations: replay the same chunk through the draft model.
                # Float-mode ticks skip the replay: only rung-2 rows are
                # scheduled then, and a rung≥1 request never drafts again.
                _, self.draft_cache = self._step(
                    self.draft_params, self.draft_cache, jnp.asarray(tokens),
                    jnp.asarray(steps), jnp.asarray(n))
        with tr.span("wait"):
            logits = jax.block_until_ready(logits)
        self._step_inflight_since = None
        dt = time.perf_counter() - t0
        self.stats["steps"] += 1
        self.stats["prefill_tokens"] += prompt_toks
        self.stats["decode_tokens"] += decode_toks
        # mixed steps: split the iteration's wall time across the phases in
        # proportion to the tokens each fed (an all-or-nothing attribution
        # inflates the minority phase's tok/s)
        total = prompt_toks + decode_toks
        if total:
            self.stats["prefill_time"] += dt * prompt_toks / total
            self.stats["decode_time"] += dt * decode_toks / total
        self._note_step_done(dt)
        with tr.span("guardrail"):
            ok = self._row_health(logits, sched_uids)
        with tr.span("sample"), jax.named_scope("sample"):
            self.key, sub = jax.random.split(self.key)
            # logits: (B, 1, V) — the model's head already projected each
            # row's final live column only
            greedy = np.asarray(jnp.argmax(logits[:, 0], axis=-1))  # (B,)
        with tr.span("emit"):
            for b, slot in enumerate(self.slots):
                if slot.req is None or n[b] == 0:
                    continue
                if ok is not None and not bool(ok[b]):
                    # guardrail trip: requeue BEFORE advancing pos,
                    # registering the prefix or sampling — a poisoned row
                    # never contributes shared pages and never emits a
                    # garbage token; its cache rebuilds from tokens on
                    # resume at the next ladder rung
                    self._numeric_trip(b)
                    continue
                slot.pos += int(n[b])
                if slot.reg_at is not None and slot.pos >= slot.reg_at:
                    self._register(b, slot)
                if not sampling[b]:
                    continue
                if slot.req.temperature > 0:
                    with tr.span("sample"), jax.named_scope("sample"):
                        kb = jax.random.fold_in(sub, b)
                        nxt = int(jax.random.categorical(
                            kb, logits[b, 0] / slot.req.temperature))
                else:
                    nxt = int(greedy[b])
                self._emit(slot.req, nxt)
                if (len(slot.req.output) >= slot.req.max_new_tokens
                        or slot.pos >= self.max_len - 1):
                    self._finish_slot(
                        b, "length"
                        if len(slot.req.output) >= slot.req.max_new_tokens
                        else "capacity")

    def _emit(self, req: Request, tok: int):
        if not req.output:
            req.t_first = time.perf_counter()
        req.output.append(tok)

    def _register(self, b: int, slot: _Slot):
        pc = self._pc
        if pc.has_state:
            pc.register_prefix(b, slot.feed, slot.reg_at)
        else:
            pc.register_levels(b, slot.feed, slot.reg_at)
        slot.reg_at = None

    def _advance_spec(self, finished: list[Request]):
        """One draft-verify round (every active slot greedy-decoding).

        Round protocol, per row at cache length P with pending token t0
        (the last sampled output, not yet fed):

          draft   k C=1 steps of the truncated model on a throwaway copy of
                  the draft cache → d_1..d_k
          verify  ONE full-model chunk over [t0, d_1..d_k] at steps=P with
                  all_logits: column i's argmax g_i is exactly what plain
                  decode would sample after committing t0..d_i
          accept  longest prefix with d_{i+1} == g_i, plus the bonus g_n —
                  n_acc+1 tokens per round, ≥1 always
          commit  roll the full cache back to the n_comm = emitted committed
                  tokens (bit-exact), then resync the authoritative draft
                  cache with one draft chunk over the same buffer at
                  n_tokens = n_comm (dead columns are exact no-ops)

        The whole round is ONE jitted dispatch (``_make_spec_round``); only
        the tiny drafted/accepted token ids come back to the host.

        Paged mode allocates each live row's worst-case write window
        (min(k+1, budget) tokens) up front; if the pool cannot hold a
        window even after eviction/preemption, the iteration falls back to
        the plain path (which can shrink to one token or preempt).  After
        the commit, pages past the new length return to the pool — the
        rollback already rewound their contents in the view, so nothing
        stale is ever scattered."""
        k = self.spec_k
        B = self.B
        tr = self.tracer
        with tr.span("schedule"):
            steps = np.zeros((B,), np.int32)
            live = np.zeros((B,), np.int32)
            cur = np.zeros((B,), np.int32)
            budget = np.zeros((B,), np.int32)
            for b, slot in enumerate(self.slots):
                if slot.req is not None:
                    steps[b] = slot.pos
                    live[b] = 1
                    cur[b] = slot.req.output[-1]
                    # clamp the round's emission to the request budget and
                    # the cache headroom (both ≥ 1 for a scheduled decode row)
                    budget[b] = min(
                        slot.req.max_new_tokens - len(slot.req.output),
                        (self.max_len - 1) - slot.pos)
            plans = None
            if self._pc is not None:
                plans = self._alloc_spec(live, steps, budget)
                if plans is not None:
                    fresh, rows, lps, phys = self._pack_plans(plans)
        if self._pc is not None and plans is None:
            self._advance(finished)   # pool pressure: plain path handles
            return
        n_live = int(live.sum())
        tr.step(B, _bucket(k + 1), 0, n_live, spec=True)
        self._iter += 1
        sched_uids = [self.slots[b].req.uid for b in range(self.B)
                      if self.slots[b].req is not None and live[b]]
        self._last_stepped = set(sched_uids)
        t0 = time.perf_counter()
        self._step_inflight_since = time.monotonic()   # watchdog window opens
        with tr.span("dispatch"):
            self._poll_faults_pre(sched_uids)
            if self._pc is not None:
                pc = self._pc
                (pool, static, self.draft_cache, draft_toks, greedy, n_acc,
                 n_comm, ok) = self._paged_spec(
                    self.params, self.draft_params, tuple(pc.pool),
                    tuple(pc.static), self.draft_cache,
                    jnp.asarray(pc.tables), fresh, rows, lps, phys,
                    jnp.asarray(cur), jnp.asarray(steps), jnp.asarray(live),
                    jnp.asarray(budget))
                pc.pool, pc.static = list(pool), list(static)
                sync_root = pc.pool[0] if pc.pool else pc.static[0]
            else:
                (self.cache, self.draft_cache, draft_toks, greedy, n_acc,
                 n_comm, ok) = self._spec_round(
                    self.params, self.draft_params, self.cache,
                    self.draft_cache, jnp.asarray(cur), jnp.asarray(steps),
                    jnp.asarray(live), jnp.asarray(budget))
                sync_root = self.cache
        with tr.span("wait"):
            draft_toks = np.asarray(draft_toks)
            greedy = np.asarray(greedy)
            n_acc = np.asarray(n_acc)
            n_comm = np.asarray(n_comm)
            jax.block_until_ready(sync_root)
        self._step_inflight_since = None
        dt = time.perf_counter() - t0
        self._note_step_done(dt)
        # verify-logit health came back with the round (one fused dispatch);
        # a tripped row walks the ladder instead of emitting garbage
        with tr.span("guardrail"):
            if self._guard is not None:
                okv = self._inject_nan(np.asarray(ok).astype(bool),
                                       sched_uids)
            else:
                okv = np.ones((self.B,), bool)
        good = live.astype(bool) & okv
        total_emitted = int(n_comm[good].sum())
        self.stats["steps"] += 1
        self.stats["decode_tokens"] += total_emitted
        self.stats["decode_time"] += dt
        self.stats["spec_rounds"] += 1
        self.stats["spec_drafted"] += k * n_live
        self.stats["spec_accepted"] += int(np.sum(n_acc[good]))
        self.stats["spec_emitted"] += total_emitted
        with tr.span("emit"):
            for b, slot in enumerate(self.slots):
                if slot.req is None:
                    continue
                if live[b] and not okv[b]:
                    # a poisoned verify round commits nothing for this row:
                    # the request requeues a rung further down the ladder
                    # and its pages (incl. the round's window) free with
                    # the slot
                    self._numeric_trip(b)
                    continue
                # emitted tokens: the accepted draft prefix, plus the bonus
                # (verify's next-token at the first mismatch) when it fit
                emit = int(n_comm[b])
                toks = [int(draft_toks[b, j])
                        for j in range(min(emit, int(n_acc[b])))]
                if emit == int(n_acc[b]) + 1:
                    toks.append(int(greedy[b, n_acc[b]]))
                for t in toks:
                    self._emit(slot.req, t)
                slot.pos += emit
                if self._pc is not None:
                    # pages allocated for the round's window but not
                    # committed
                    self._pc.free_beyond(b, slot.pos)
                if (len(slot.req.output) >= slot.req.max_new_tokens
                        or slot.pos >= self.max_len - 1):
                    self._finish_slot(
                        b, "length"
                        if len(slot.req.output) >= slot.req.max_new_tokens
                        else "capacity")

    def _alloc_spec(self, live, steps, budget) -> list | None:
        """Allocate each live row's speculative write window.  Returns None
        (after rolling back every allocation made here) when the pool
        cannot hold some window — the caller falls back to plain decode
        for this iteration."""
        pc = self._pc
        plans = [([], []) for _ in range(self.B)]
        allocated: set[int] = set()
        for b in range(self.B):
            if not live[b]:
                continue
            window = min(self.spec_k + 1, int(budget[b]))
            while True:
                plan = pc.plan_writes(b, int(steps[b]), window)
                if plan is not None:
                    plans[b] = plan
                    allocated.add(b)
                    break
                if pc.evict_one(require_free=True):
                    continue
                v = self._victim(self._prio(self.slots[b].req),
                                 allocated | {b})
                if v is not None:
                    self._preempt(v)
                    live[v] = 0
                    continue
                for ob in allocated:   # roll back: stale never-reset pages
                    for p in plans[ob][0]:
                        pc.pages.deref(p)
                        pc.tables[ob, np.where(pc.tables[ob] == p)[0]] = 0
                return None
        return plans
