"""Paged block allocator for the serving caches (all four families).

Slot-static serving reserves ``max_len`` cache tokens per batch slot whether
a request uses 8 of them or 500, and stores a shared system prompt once per
slot.  This module sizes cache memory in *tokens* instead: a global pool of
fixed-size pages, per-request page tables, refcounted prefix sharing, and
the gather/scatter plumbing that feeds the existing jitted
``model.prefill_chunk`` unchanged.

Cache leaves split by their axes (``model.cache_axes()``):

  * **paged leaves** — leaves with a ``kv_seq`` axis of length ``max_len``
    (GQA K/V/pos + int8 scales, MLA latent/rope/pos + scales).  Pool
    storage is simply ``model.init_cache(n_pages, page_size)`` filtered to
    these leaves: the batch axis becomes the *page* axis, the sequence axis
    the within-page offset, so every storage format the model can allocate
    (float, int8 + scale rows) pages identically with zero per-format code.
  * **state leaves** — everything else: SSD / RG-LRU conv+state, and
    sliding-window rings (already O(window), not O(max_len)).  They stay
    slot-resident, and page-granular sharing is replaced by *snapshot
    slots*: a prefix entry stores a full copy of the row's state at the
    prefix boundary, restored on a prefix hit.

Per step the engine passes the jitted step an indices operand (the page
tables) plus the step's write plan; the wrapper

  1. resets freshly-allocated pages to the zero-page template (a recycled
     page carries the previous owner's ``pos`` values — stale entries
     would otherwise be attended as live keys),
  2. gathers each row's pages into a contiguous ``(B, max_len)`` view,
  3. runs the unchanged ``prefill_chunk`` on the view,
  4. scatters back only the pages inside each row's write window
     ``[steps, steps + n_tokens)``.

Shared prefix pages are never inside a write window (sharing is
page-aligned and a request's writes start after its shared prefix), so
copy-on-write degenerates to share-read-only + allocate-fresh-for-writes:
no page is ever copied, and step (4) cannot corrupt a shared page.
Speculative rounds ride the same wrapper: the round's rollback rewinds the
*view* bit-exactly before the scatter, and the engine frees any page the
round allocated beyond the committed length.

Host-side accounting (``PagePool``, ``PrefixIndex``) is plain numpy /
Python — allocation decisions happen at schedule time where the engine
already runs per-slot Python, and determinism falls out for free.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def _is_axes(x) -> bool:
    return x is None or (isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x))


# ---------------------------------------------------------------------------
# Host-side page accounting.
# ---------------------------------------------------------------------------


class PagePool:
    """Free list + per-page refcounts.

    Page 0 is the reserved *zero page* (pristine template content): page
    tables point unallocated logical pages at it, so a gathered view's tail
    always reads pos=-1 / zeros.  It is never allocated or freed.
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("page pool needs >= 2 pages (page 0 is the "
                             "reserved zero page)")
        self.n_pages = n_pages
        self.ref = np.zeros((n_pages,), np.int32)
        self._free = list(range(n_pages - 1, 0, -1))   # LIFO, page 0 reserved

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self) -> int | None:
        """Pop a free page with refcount 1, or None if the pool is dry."""
        if not self._free:
            return None
        p = self._free.pop()
        assert self.ref[p] == 0, f"free list held referenced page {p}"
        self.ref[p] = 1
        return p

    def ref_inc(self, p: int):
        assert p != 0 and self.ref[p] > 0, f"ref_inc of unowned page {p}"
        self.ref[p] += 1

    def deref(self, p: int):
        assert p != 0, "deref of the reserved zero page"
        assert self.ref[p] > 0, f"double free of page {p}"
        self.ref[p] -= 1
        if self.ref[p] == 0:
            self._free.append(p)


@dataclasses.dataclass
class PrefixEntry:
    length: int            # tokens covered (page-aligned)
    pages: list            # physical pages holding the prefix (KV leaves)
    snap: int | None       # snapshot slot holding the state leaves, if any
    last_use: int          # LRU clock


class PrefixIndex:
    """token-tuple-keyed prefix cache: exact (collision-free) chain keys."""

    def __init__(self):
        self.entries: dict[tuple, PrefixEntry] = {}
        self._clock = 0

    def tick(self) -> int:
        self._clock += 1
        return self._clock

    def lookup(self, tokens: list[int], page_size: int,
               max_tokens: int) -> PrefixEntry | None:
        """Longest registered page-aligned prefix of ``tokens`` covering at
        most ``max_tokens`` (serving always recomputes ≥1 prompt token —
        the sampler needs the last token's logits)."""
        j = min(len(tokens), max_tokens) // page_size
        while j > 0:
            e = self.entries.get(tuple(tokens[: j * page_size]))
            if e is not None:
                e.last_use = self.tick()
                return e
            j -= 1
        return None

    def lru(self) -> tuple | None:
        if not self.entries:
            return None
        return min(self.entries, key=lambda k: self.entries[k].last_use)


# ---------------------------------------------------------------------------
# Device-side paged cache.
# ---------------------------------------------------------------------------


class PagedCache:
    """Pool storage + jitted gather/scatter around ``prefill_chunk``.

    The engine owns policy (scheduling, preemption victims, admission);
    this class owns mechanics: leaf classification, page/snapshot pools,
    page tables, the prefix index, and the jitted step wrappers.
    """

    def __init__(self, model, slots: int, max_len: int, page_size: int,
                 n_pages: int, snap_slots: int, prefix_sharing: bool = True):
        if max_len % page_size != 0:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"page_size={page_size}")
        self.model = model
        self.B = slots
        self.max_len = max_len
        self.ps = page_size
        self.n_pp = max_len // page_size
        self.sharing = prefix_sharing

        template = model.init_cache(slots, max_len)
        leaves, self.treedef = jax.tree.flatten(template)
        axes = model.cache_axes()
        bax_tree = jax.tree.map(lambda ax: ax.index("batch"), axes,
                                is_leaf=_is_axes)
        seq_tree = jax.tree.map(
            lambda ax: ax.index("kv_seq") if "kv_seq" in ax else -1, axes,
            is_leaf=_is_axes)
        self.bax, _ = jax.tree.flatten(bax_tree)
        seqax, _ = jax.tree.flatten(seq_tree)
        axes_flat, _ = jax.tree.flatten(axes, is_leaf=_is_axes)
        self.paged_mask: list[bool] = []
        for leaf, b, s in zip(leaves, self.bax, seqax):
            paged = s >= 0 and leaf.shape[s] == max_len
            if paged:
                assert s == b + 1, "paged leaves need kv_seq right after batch"
            self.paged_mask.append(paged)
        self.has_paged = any(self.paged_mask)
        self.has_state = not all(self.paged_mask)

        def _split(ls, mask_val):
            return [l for l, m in zip(ls, self.paged_mask) if m is mask_val]

        # pool storage: init_cache with batch=pages, max_len=page_size —
        # every storage format the model allocates pages identically
        self.n_pages = n_pages if self.has_paged else 2
        self.pool = _split(
            jax.tree.flatten(model.init_cache(self.n_pages, page_size))[0],
            True)
        self._page_tmpl = _split(
            jax.tree.flatten(model.init_cache(1, page_size))[0], True)
        self.static = _split(leaves, False)
        self._static_tmpl = list(self.static)
        self._pbax = _split(self.bax, True)
        self._sbax = _split(self.bax, False)
        self._paxes = _split(axes_flat, True)
        self._saxes = _split(axes_flat, False)

        # recurrent/ring state snapshots for prefix sharing
        self.n_snap = snap_slots if (self.has_state and prefix_sharing) else 0
        self.snap = (_split(jax.tree.flatten(
            model.init_cache(max(self.n_snap, 1), max_len))[0], False)
            if self.n_snap else [])
        self._snap_free = list(range(self.n_snap - 1, -1, -1))

        self.pages = PagePool(self.n_pages)
        self.tables = np.zeros((slots, self.n_pp), np.int32)  # 0 = unallocated
        self.prefix = PrefixIndex()

        self._jit_slot_reset = jax.jit(self._slot_reset_impl)
        self._jit_snap_save = jax.jit(self._snap_save_impl)
        self._jit_snap_restore = jax.jit(self._snap_restore_impl)

    def shard(self, parallel) -> None:
        """Lay the pool / static / snapshot leaves out on the serving mesh.

        Each leaf reuses its family's cache axes (``model.cache_axes()``) —
        so int8 scale rows shard alongside their codes — with one rewrite:
        the *page* axis (the leaf position the axes call "batch") and the
        snapshot-slot axis replicate, because pages and snapshots are pooled
        resources every data shard must reach by global index.  Slot-static
        leaves keep the batch→data sharding; the in-page ``kv_seq`` axis and
        head axes shard over "model" per the standard rules."""
        from jax.sharding import NamedSharding
        from repro.launch.sharding import partition_spec
        if not parallel.active:
            return

        def put(leaves, axes_list, *, pooled):
            out = []
            for leaf, ax in zip(leaves, axes_list):
                if pooled:
                    ax = tuple(None if a == "batch" else a for a in ax)
                spec = partition_spec(ax, leaf.shape, parallel)
                out.append(jax.device_put(
                    leaf, NamedSharding(parallel.mesh, spec)))
            return out

        self.pool = put(self.pool, self._paxes, pooled=True)
        self._page_tmpl = put(self._page_tmpl, self._paxes, pooled=True)
        self.static = put(self.static, self._saxes, pooled=False)
        self._static_tmpl = put(self._static_tmpl, self._saxes, pooled=False)
        if self.snap:
            self.snap = put(self.snap, self._saxes, pooled=True)

    # -- jitted mechanics ----------------------------------------------------

    def _reset_fresh(self, pool, fresh):
        """Reset freshly-allocated pages to the zero-page template (recycled
        pages carry the previous owner's pos/content)."""
        out = []
        for leaf, tmpl, b in zip(pool, self._page_tmpl, self._pbax):
            idx = (slice(None),) * b + (fresh,)
            out.append(leaf.at[idx].set(tmpl, mode="drop"))
        return out

    def _gather(self, pool, table):
        """pool pages → contiguous (B, max_len) view per paged leaf."""
        B, n_pp = table.shape
        out = []
        for leaf, b in zip(pool, self._pbax):
            g = jnp.take(leaf, table.reshape(-1), axis=b)
            sh = g.shape[:b] + (B, n_pp * self.ps) + g.shape[b + 2:]
            out.append(g.reshape(sh))
        return out

    def _scatter(self, pool, view, rows, lps, phys):
        """Write the (row, logical page) → physical page triples back.
        Padding triples point phys at n_pages (dropped)."""
        idx = rows * self.n_pp + lps                      # (M,)
        out = []
        for leaf, v, b in zip(pool, view, self._pbax):
            v2 = v.reshape(v.shape[:b] + (self.B * self.n_pp, self.ps)
                           + v.shape[b + 2:])
            src = jnp.take(v2, idx, axis=b)               # (..., M, ps, ...)
            out.append(leaf.at[(slice(None),) * b + (phys,)].set(
                src, mode="drop"))
        return out

    def _merge(self, paged_leaves, static_leaves):
        pi, si, out = iter(paged_leaves), iter(static_leaves), []
        for m in self.paged_mask:
            out.append(next(pi) if m else next(si))
        return out

    def _split_new(self, leaves):
        paged = [l for l, m in zip(leaves, self.paged_mask) if m]
        static = [l for l, m in zip(leaves, self.paged_mask) if not m]
        return paged, static

    def make_step(self):
        """Jitted paged step: reset-fresh → gather → prefill_chunk →
        scatter-write-window.  jit keys compiled variants by the bucketed
        (chunk, fresh, triples) shapes.  Its program is named
        ``paged_prefill_chunk``."""
        model, treedef = self.model, self.treedef

        def paged_prefill_chunk(params, pool, static, table, fresh, rows,
                                lps, phys, tokens, steps, n_tokens):
            pool = self._reset_fresh(list(pool), fresh)
            view = self._gather(pool, table)
            cache = jax.tree.unflatten(treedef, self._merge(view, static))
            logits, new_cache = model.prefill_chunk(params, cache, tokens,
                                                    steps, n_tokens)
            new_paged, new_static = self._split_new(
                jax.tree.flatten(new_cache)[0])
            new_pool = self._scatter(pool, new_paged, rows, lps, phys)
            return logits, tuple(new_pool), tuple(new_static)

        return jax.jit(paged_prefill_chunk)

    def make_spec_step(self, inner):
        """Wrap a fused speculative round (see ``Engine._make_spec_round``)
        with the same reset/gather/scatter plumbing.  The round's rollback
        rewinds the *view* bit-exactly, so scattering the full k+1-token
        write window writes rejected positions back with their pre-round
        (or zero-template) bytes.  Its program is named
        ``paged_spec_round``."""
        treedef = self.treedef

        def paged_spec_round(params, dp, pool, static, dcache, table, fresh,
                             rows, lps, phys, cur, steps, live, budget):
            pool = self._reset_fresh(list(pool), fresh)
            view = self._gather(pool, table)
            cache = jax.tree.unflatten(treedef, self._merge(view, static))
            cache, dcache, draft_toks, greedy, n_acc, n_comm, ok = inner(
                params, dp, cache, dcache, cur, steps, live, budget)
            new_paged, new_static = self._split_new(
                jax.tree.flatten(cache)[0])
            new_pool = self._scatter(pool, new_paged, rows, lps, phys)
            return (tuple(new_pool), tuple(new_static), dcache, draft_toks,
                    greedy, n_acc, n_comm, ok)

        return jax.jit(paged_spec_round)

    def _slot_reset_impl(self, static, b):
        out = []
        for leaf, tmpl, bx in zip(static, self._static_tmpl, self._sbax):
            idx = (slice(None),) * bx + (b,)
            out.append(leaf.at[idx].set(tmpl[idx]))
        return tuple(out)

    def _snap_save_impl(self, snap, static, dst, b):
        out = []
        for s_leaf, leaf, bx in zip(snap, static, self._sbax):
            idx_d = (slice(None),) * bx + (dst,)
            idx_s = (slice(None),) * bx + (b,)
            out.append(s_leaf.at[idx_d].set(leaf[idx_s]))
        return tuple(out)

    def _snap_restore_impl(self, static, snap, src, b):
        out = []
        for leaf, s_leaf, bx in zip(static, snap, self._sbax):
            idx_d = (slice(None),) * bx + (b,)
            idx_s = (slice(None),) * bx + (src,)
            out.append(leaf.at[idx_d].set(s_leaf[idx_s]))
        return tuple(out)

    # -- host-side bookkeeping ----------------------------------------------

    def reset_slot(self, b: int):
        """Reset slot b's state-leaf rows from the pristine template (pages
        need no reset here — they are freed, and recycled pages reset on
        allocation)."""
        if self.static:
            self.static = list(self._jit_slot_reset(
                tuple(self.static), jnp.int32(b)))

    def free_slot(self, b: int):
        """Release every page slot b's table references (shared prefix pages
        survive through their index/entry refcounts)."""
        for lp in range(self.n_pp):
            p = int(self.tables[b, lp])
            if p:
                self.pages.deref(p)
                self.tables[b, lp] = 0

    def slot_pages(self, b: int) -> int:
        return int(np.count_nonzero(self.tables[b]))

    def plan_writes(self, b: int, pos: int, n: int):
        """Allocate pages covering row b's write window [pos, pos+n).

        Returns ``(fresh, triples)`` — fresh page ids to zero-reset and
        (row, lp, phys) scatter triples — or None if the pool ran dry
        (allocations made so far are rolled back; the engine evicts or
        preempts and retries)."""
        if not self.has_paged or n <= 0:
            return [], []
        lp0, lp1 = pos // self.ps, (pos + n - 1) // self.ps
        fresh, triples = [], []
        for lp in range(lp0, lp1 + 1):
            p = int(self.tables[b, lp])
            if p == 0:
                p = self.pages.alloc()
                if p is None:
                    for fp in fresh:           # roll back this plan
                        self.pages.deref(fp)
                        self.tables[b, np.where(self.tables[b] == fp)[0]] = 0
                    return None
                self.tables[b, lp] = p
                fresh.append(p)
            triples.append((b, lp, p))
        return fresh, triples

    def max_take(self, b: int, pos: int) -> int:
        """Largest n for which ``plan_writes(b, pos, n)`` would succeed
        right now (existing pages + free pool)."""
        if not self.has_paged:
            return self.max_len
        take = 0
        budget = self.pages.n_free
        lp = pos // self.ps
        off = pos
        while lp < self.n_pp:
            if int(self.tables[b, lp]) == 0:
                if budget == 0:
                    break
                budget -= 1
            take += (lp + 1) * self.ps - off
            off = (lp + 1) * self.ps
            lp += 1
        return take

    def free_beyond(self, b: int, pos: int):
        """Free pages wholly beyond ``pos`` tokens (speculative rollback:
        pages allocated for a round's write window but left uncommitted)."""
        first_unused = (pos + self.ps - 1) // self.ps
        for lp in range(first_unused, self.n_pp):
            p = int(self.tables[b, lp])
            if p:
                self.pages.deref(p)
                self.tables[b, lp] = 0

    # -- prefix sharing -------------------------------------------------------

    def prefix_lookup(self, tokens: list[int]) -> PrefixEntry | None:
        if not self.sharing:
            return None
        # always leave ≥1 token to recompute: the sampler needs the last
        # prompt token's logits, which the prefix cache does not store
        return self.prefix.lookup(tokens, self.ps, len(tokens) - 1)

    def prefix_admit(self, b: int, entry: PrefixEntry):
        """Point slot b's table at a shared prefix and restore its state
        snapshot.  Caller sets slot.pos = entry.length."""
        for lp, p in enumerate(entry.pages):
            assert int(self.tables[b, lp]) == 0
            self.pages.ref_inc(p)
            self.tables[b, lp] = p
        if entry.snap is not None:
            self.static = list(self._jit_snap_restore(
                tuple(self.static), tuple(self.snap),
                jnp.int32(entry.snap), jnp.int32(b)))

    def register_prefix(self, b: int, tokens: list[int], length: int) -> bool:
        """Register slot b's first ``length`` (page-aligned) tokens.

        KV pages are shared by reference (the entry holds a refcount on
        each); state leaves are copied into a snapshot slot.  Returns False
        when a needed snapshot slot cannot be found even after evicting
        unreferenced entries."""
        if not self.sharing or length <= 0 or length % self.ps:
            return False
        key = tuple(tokens[:length])
        if key in self.prefix.entries:
            return True
        snap = None
        if self.has_state:
            while not self._snap_free:
                if not self.evict_one():
                    return False
            snap = self._snap_free.pop()
            self.snap = list(self._jit_snap_save(
                tuple(self.snap), tuple(self.static),
                jnp.int32(snap), jnp.int32(b)))
        pages = [int(self.tables[b, lp]) for lp in range(length // self.ps)]
        assert all(pages) or not self.has_paged
        for p in pages:
            if p:
                self.pages.ref_inc(p)
        self.prefix.entries[key] = PrefixEntry(
            length=length, pages=[p for p in pages if p], snap=snap,
            last_use=self.prefix.tick())
        return True

    def register_levels(self, b: int, tokens: list[int], length: int):
        """Register every page-aligned prefix level up to ``length`` (pure-KV
        models: entries share page refs, so a later request matching any
        shared depth hits; state models register single levels via
        ``register_prefix`` — each level would cost a snapshot slot)."""
        for j in range(1, length // self.ps + 1):
            self.register_prefix(b, tokens, j * self.ps)

    def evict_one(self, require_free: bool = False) -> bool:
        """Drop the least-recently-used prefix entry, releasing its page
        refs and snapshot slot.  Pages still referenced by a live request
        stay resident; fully-unreferenced ones return to the free list.

        ``require_free``: only evict an entry whose release returns at
        least one page to the free list (some page solely owned by the
        entry).  Page-pressure escalation uses this so it cannot wipe a
        hot shared prefix — still pinned by live page tables — without
        gaining any memory for the allocator."""
        order = sorted(self.prefix.entries,
                       key=lambda k: self.prefix.entries[k].last_use)
        for key in order:
            e = self.prefix.entries[key]
            if require_free and not any(
                    int(self.pages.ref[p]) == 1 for p in e.pages):
                continue
            self.prefix.entries.pop(key)
            for p in e.pages:
                self.pages.deref(p)
            if e.snap is not None:
                self._snap_free.append(e.snap)
            return True
        return False

    # -- accounting / invariants ----------------------------------------------

    def nbytes(self) -> int:
        from repro import quant as qt
        return (qt.tree_nbytes(self.pool) + qt.tree_nbytes(self.static)
                + qt.tree_nbytes(self.snap))

    def pool_tokens(self) -> int:
        return (self.n_pages - 1) * self.ps if self.has_paged else 0

    def occupancy(self) -> dict:
        """Pool residency snapshot for health/admission reporting: usable
        pages (the zero page is reserved), free pages, and the occupied
        fraction.  Reads only host-side counters — safe to call from the
        health endpoint while a step is in flight."""
        if not self.has_paged:
            return {"pages": 0, "pages_free": 0, "occupancy": 0.0}
        usable = self.n_pages - 1
        free = self.pages.n_free
        return {"pages": usable, "pages_free": free,
                "occupancy": (usable - free) / usable if usable else 0.0}

    def audit(self):
        """Invariant check (tests call this after every mutation batch):
        per-page refcounts equal table references + prefix-entry references;
        the free list is exactly the unreferenced pages, duplicate-free;
        snapshot slots are consistently owned."""
        refs = np.zeros((self.n_pages,), np.int32)
        for b in range(self.B):
            for lp in range(self.n_pp):
                p = int(self.tables[b, lp])
                if p:
                    refs[p] += 1
        for e in self.prefix.entries.values():
            for p in e.pages:
                refs[p] += 1
        assert refs[0] == 0, "zero page must never be referenced by tables"
        np.testing.assert_array_equal(refs, self.pages.ref)
        free = self.pages._free
        assert len(free) == len(set(free)), "duplicate pages in free list"
        assert 0 not in free, "zero page on the free list"
        expect_free = {p for p in range(1, self.n_pages) if refs[p] == 0}
        assert set(free) == expect_free, (set(free), expect_free)
        snaps = [e.snap for e in self.prefix.entries.values()
                 if e.snap is not None]
        assert len(snaps) == len(set(snaps)), "snapshot slot double-owned"
        assert set(snaps).isdisjoint(self._snap_free)
        assert set(snaps) | set(self._snap_free) <= set(range(self.n_snap))
