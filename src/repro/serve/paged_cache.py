"""Paged hierarchical KV-cache pool: vLLM-style block-pool memory
management specialized to the H-Matrix cache layout (DESIGN.md
section 8).

The dense serving cache pins ``Lmax`` rows (plus the coarse pyramid)
per slot, so HBM -- not FLOPs -- caps concurrency.  This module carves
every level of the hierarchical cache into PAGES of ``nr`` level-l rows
and manages them with:

* a host-side allocator (:class:`PagePool`): per-level free lists,
  per-request page tables, refcounts;
* hierarchical prefix sharing: a page's content is a pure function of
  the token prefix up to the end of its span (clamped to the prompt),
  so a registry keyed by ``(level, block, clamped_len, prefix_hash)``
  lets requests with a common prompt prefix map the SAME physical pages
  -- including each shared subtree's ancestor rows, which are pairwise
  means/sums of the same prefix and therefore bit-identical too;
* copy-on-write: pages are COW'd lazily on the first divergent write
  (the per-tick ancestor update touches exactly one page per level --
  the one whose span contains ``t``), so identical prompts share even
  their incomplete frontier pages until generation actually diverges;
* eviction: pages whose refcount drops to zero but that remain in the
  prefix registry park on an LRU list and are reclaimed on demand;
* preemption hooks: when the pool is exhausted the engine releases a
  victim's pages via :func:`PagePool.release_slot` and requeues it
  (recompute-on-resume, ``serve/scheduler.py``).

Two logical pages per level are reserved: ``ZERO`` (page 0, never
written -- fresh decode pages are initialized by copying it, which keeps
paged pools bit-identical to the zero-initialized dense cache) and
``TRASH`` (page 1 -- inactive engine rows point their update tables at
it, making their in-kernel writes inert without any extra masking).

Physical layout: a logical page covers all ``Hkv`` kv-head rows of its
request, so the device pools have ``num_pages * Hkv`` pool rows and
logical page ``p`` owns rows ``[p*Hkv, (p+1)*Hkv)``; the tick tables
handed to the kernels are already physical (``page * Hkv + head``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hierarchy as hc
from repro.core import h1d_decode as hd
from repro.core import quantization as qz


class PoolExhausted(RuntimeError):
    """Raised by the allocator when a level's free list and evictable
    list are both empty; the engine answers with preemption."""

    def __init__(self, level: int):
        super().__init__(f"page pool exhausted at level {level}")
        self.level = level


ZERO = 0      # reserved all-zeros page (never written)
TRASH = 1     # reserved write sink for inactive engine rows


@dataclasses.dataclass
class PoolStats:
    """Monotonic pool counters.  ``prefix_hits``/``prefix_misses``
    count LOOKUPS against the prefix registry during prefix-sharing
    admissions (one per page span), so ``prefix_hit_rate()`` is a true
    rate; ``shared_maps`` keeps counting the hit *mappings* for
    backward compatibility (equal to ``prefix_hits`` in practice).
    ``copy_launches`` counts the engine's flushes of page copies, one
    device program each (:func:`apply_copies`)."""
    cow_copies: int = 0
    evictions: int = 0
    shared_maps: int = 0
    fresh_pages: int = 0
    prefix_hits: int = 0
    prefix_misses: int = 0
    copy_launches: int = 0

    def snapshot(self) -> Dict[str, int]:
        return dataclasses.asdict(self)

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)

    def prefix_hit_rate(self) -> float:
        """Registry hit rate over prefix-sharing admissions (0.0 when
        no sharing-eligible lookup has happened)."""
        lookups = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / lookups if lookups else 0.0


class PagePool:
    """Host-side allocator for the paged hierarchical cache.

    All bookkeeping is numpy/python -- the device only ever sees the
    zeroed pools, batched page copies, prefill scatters, and the small
    per-tick indirection tables.
    """

    def __init__(self, *, slots: int, max_len: int, nr: int,
                 pool_pages: int, coarse_pages: Optional[Sequence[int]] = None,
                 quant_levels: int = 0):
        self.nr = nr
        self.Lp = hc.padded_length(max_len, nr)
        self.M = max(hc.num_levels(self.Lp, nr), 1)   # levels incl. fine
        self.slots = slots
        # dtype identity per level: levels < quant_levels store int8
        # pages with per-row scales.  The tag participates in the
        # prefix-registry keys (see _span_keys) -- it IS part of a
        # page's content identity.
        if quant_levels < 0:
            quant_levels = self.M
        self.quant_levels = min(quant_levels, self.M)
        self.quant = [l < self.quant_levels for l in range(self.M)]
        self.level_dtypes = ["int8:rowscale" if q else "f32"
                             for q in self.quant]
        # logical blocks per level: level l rows (Lp >> l) in nr-row pages
        self.nblocks = [(self.Lp >> l) // nr for l in range(self.M)]
        if pool_pages < 1:
            raise ValueError("pool_pages must be >= 1")
        sizes = [min(pool_pages, slots * self.nblocks[0])]
        for l in range(1, self.M):
            if coarse_pages is not None:
                sizes.append(coarse_pages[l - 1])
            else:
                # keep capacity proportional to the fine pool but never
                # below one page per slot (every request needs >= 1 page
                # per level regardless of its length)
                sizes.append(min(max(slots, pool_pages >> l),
                                 slots * self.nblocks[l]))
        self.num_pages = [s + 2 for s in sizes]          # + ZERO/TRASH
        self.free: List[List[int]] = [
            list(range(n - 1, 1, -1)) for n in self.num_pages]
        self.refcount = [np.zeros(n, np.int32) for n in self.num_pages]
        self.table = [np.full((slots, nb), -1, np.int32)
                      for nb in self.nblocks]
        # prefix-sharing registry: key -> (level, page); the reverse map
        # tells a writer whether its exclusively-owned page is still
        # advertised (and must be unregistered before mutation)
        self.registry: Dict[tuple, Tuple[int, int]] = {}
        self.key_of: Dict[Tuple[int, int], tuple] = {}
        # refcount-0 pages kept alive only by the registry, LRU order
        self.evictable: "OrderedDict[Tuple[int, int], None]" = OrderedDict()
        self.stats = PoolStats()

    # -- capacity ------------------------------------------------------
    def usable(self, l: int) -> int:
        return self.num_pages[l] - 2

    def used(self, l: int) -> int:
        ev = sum(1 for (ll, _) in self.evictable if ll == l)
        return self.usable(l) - len(self.free[l]) - ev

    def available(self, l: int) -> int:
        """Pages obtainable without preemption (free + evictable)."""
        return self.usable(l) - self.used(l)

    def occupancy(self) -> float:
        tot = sum(self.usable(l) for l in range(self.M))
        return sum(self.used(l) for l in range(self.M)) / max(tot, 1)

    def pages_needed(self, S: int) -> List[int]:
        """Per-level page count covering an S-token prompt."""
        return [max(1, -(-S // (self.nr << l))) for l in range(self.M)]

    def net_need(self, tokens: np.ndarray, *,
                 share: bool = True) -> List[int]:
        """Per-level page need for this prompt, net of prefix-registry
        hits (pages an admission would actually have to allocate)."""
        if not share:
            return self.pages_needed(len(tokens))
        return [sum(1 for key in keys if key not in self.registry)
                for keys in self._span_keys(tokens)]

    def can_admit(self, tokens: np.ndarray, *, share: bool = True) -> bool:
        """Conservative availability probe: needed-minus-shared per
        level against free + evictable."""
        return all(nn <= self.available(l) for l, nn in
                   enumerate(self.net_need(tokens, share=share)))

    # -- registry / refcount internals ---------------------------------
    def _span_keys(self, tokens: np.ndarray) -> List[List[tuple]]:
        """Registry keys for every (level, block) the prompt covers:
        ``(l, dtype_tag, blk, clamped_len, digest)`` where the digest is
        a CHAINED sha1 over the prefix bytes -- each level hashes the
        prompt once (O(S) per level, not O(S^2/nr) re-hashes per span),
        and a cryptographic digest makes a cross-prompt collision (which
        would silently serve another request's KV pages) a non-event,
        unlike Python's 64-bit ``hash``.

        ``dtype_tag`` is the level's page dtype + scale-granularity
        identity (``level_dtypes``): a page's bytes are a function of
        the prefix AND the storage format, so a registry persisted or
        re-primed across a ``cache_dtype``/``quant_levels`` config
        change must never hand an fp32-era page to an int8 pool (or
        vice versa)."""
        S = len(tokens)
        out: List[List[tuple]] = []
        for l, need in enumerate(self.pages_needed(S)):
            span = self.nr << l
            tag = self.level_dtypes[l]
            h = hashlib.sha1()
            keys = []
            for blk in range(need):
                n = min((blk + 1) * span, S)
                h.update(tokens[blk * span:n].tobytes())
                keys.append((l, tag, blk, n, h.copy().digest()))
            out.append(keys)
        return out

    def _alloc(self, l: int) -> int:
        if self.free[l]:
            return self.free[l].pop()
        for key2 in self.evictable:            # LRU: oldest first
            if key2[0] == l:
                self._unregister(l, key2[1])
                self.evictable.pop(key2)
                self.stats.evictions += 1
                return key2[1]
        raise PoolExhausted(l)

    def _unregister(self, l: int, page: int) -> None:
        key = self.key_of.pop((l, page), None)
        if key is not None:
            self.registry.pop(key, None)

    def _map(self, slot: int, l: int, blk: int, page: int) -> None:
        self.table[l][slot, blk] = page
        if self.refcount[l][page] == 0:
            self.evictable.pop((l, page), None)
        self.refcount[l][page] += 1

    def _decref(self, l: int, page: int) -> None:
        self.refcount[l][page] -= 1
        assert self.refcount[l][page] >= 0
        if self.refcount[l][page] == 0:
            if (l, page) in self.key_of:
                self.evictable[(l, page)] = None       # park, reclaimable
            else:
                self.free[l].append(page)

    def _maybe_check(self, *, slot: Optional[int] = None,
                     t: Optional[int] = None) -> None:
        """Opt-in runtime invariant mode (``REPRO_POOL_CHECK=1``): run
        the model checker's invariant functions after a mutating op, so
        fuzzing and ``analysis/pool_model.py`` share ONE invariant
        definition.  ``slot``/``t`` additionally run the tick write-set
        postconditions."""
        if not os.environ.get("REPRO_POOL_CHECK"):
            return
        from repro.analysis import pool_model
        vs = pool_model.check_pool_invariants(self)
        if slot is not None and t is not None:
            vs += pool_model.check_tick_postconditions(self, slot, t)
        if vs:
            raise AssertionError(
                "REPRO_POOL_CHECK: pool invariant violated:\n"
                + "\n".join(f"  [{v.kind}] {v.operand}: {v.detail}"
                            for v in vs))

    # -- request lifecycle ---------------------------------------------
    def admit(self, slot: int, tokens: np.ndarray, *,
              share: bool = True) -> Dict[int, List[Tuple[int, int]]]:
        """Map pages covering the prompt into ``slot``'s tables.

        Returns per level the ``(block, page)`` pairs that MISSED the
        prefix registry -- the engine scatters the dense prefill output
        into exactly those pages (registry hits reuse the existing
        physical page, content already bit-identical).

        TRANSACTIONAL: on :class:`PoolExhausted` every map AND every
        registration this call made is rolled back before re-raising.
        Leaving a failed admission's registrations behind is a
        correctness bug, not a leak -- the pages' content is only
        written by the engine's scatter AFTER a successful admit, so a
        stale key would serve GARBAGE to the next prompt that hashes to
        it (typically the same request retrying next tick).
        """
        assert not (self.table[0][slot] >= 0).any(), "slot not released"
        span_keys = self._span_keys(tokens) if share else None
        writes: Dict[int, List[Tuple[int, int]]] = {}
        placed: List[Tuple[int, int, int, Optional[tuple]]] = []
        try:
            for l, need in enumerate(self.pages_needed(len(tokens))):
                wl = []
                for blk in range(need):
                    key = span_keys[l][blk] if share else None
                    hit = self.registry.get(key) if share else None
                    if hit is not None:
                        self._map(slot, l, blk, hit[1])
                        placed.append((l, blk, hit[1], None))
                        self.stats.shared_maps += 1
                        self.stats.prefix_hits += 1
                    else:
                        p = self._alloc(l)
                        self._map(slot, l, blk, p)
                        self.stats.fresh_pages += 1
                        if share:
                            self.stats.prefix_misses += 1
                        wl.append((blk, p))
                        placed.append((l, blk, p, key))
                        if share:
                            self.registry[key] = (l, p)
                            self.key_of[(l, p)] = key
                writes[l] = wl
        except PoolExhausted:
            for l, blk, p, key in placed:
                if key is not None:
                    self._unregister(l, p)
                self.table[l][slot, blk] = -1
                self._decref(l, p)
            self._maybe_check()
            raise
        self._maybe_check()
        return writes

    def release_slot(self, slot: int) -> None:
        """Drop all of a slot's mappings (finish or preemption).
        Registered pages survive on the evictable LRU for future
        prefix hits; private pages return to the free lists."""
        for l in range(self.M):
            row = self.table[l][slot]
            for blk in np.nonzero(row >= 0)[0]:
                self._decref(l, int(row[blk]))
            row[:] = -1
        self._maybe_check()

    def admit_snapshot(self, slot: int,
                       blocks: Dict[int, Sequence[int]],
                       ) -> Dict[int, List[Tuple[int, int]]]:
        """Re-map a preempted slot's snapshotted blocks onto fresh
        PRIVATE pages (no registry sharing -- see :func:`restore_slot`
        for why).  Returns per level the ``(block, page)`` pairs in
        block order so the caller can scatter the saved bytes back.
        Raises :class:`PoolExhausted` with the partial mapping LEFT IN
        PLACE -- the caller unwinds with :func:`release_slot`."""
        out: Dict[int, List[Tuple[int, int]]] = {}
        for l, blks in blocks.items():
            pairs = []
            for b in blks:
                p = self._alloc(l)
                self._map(slot, l, int(b), p)
                pairs.append((int(b), p))
            out[l] = pairs
        self._maybe_check()
        return out

    def prepare_tick(self, slot: int, t: int,
                     copies: Dict[int, List[Tuple[int, int]]]) -> None:
        """Make the write-set of position ``t`` (one page per level: the
        page whose span contains ``t``) present and private.

        Fresh pages are zero-initialized by a ZERO-page copy; shared
        pages are COW'd; exclusively-owned pages still advertised in the
        prefix registry are unregistered (their content is about to
        change).  Device copies accumulate into ``copies`` (level ->
        list of (src_page, dst_page)) so a retry after
        :class:`PoolExhausted` + preemption never loses copies already
        scheduled."""
        for l in range(self.M):
            blk = t // (self.nr << l)
            p = int(self.table[l][slot, blk])
            if p < 0:
                np_ = self._alloc(l)
                self._map(slot, l, blk, np_)
                self.stats.fresh_pages += 1
                copies.setdefault(l, []).append((ZERO, np_))
            elif self.refcount[l][p] > 1:
                np_ = self._alloc(l)
                copies.setdefault(l, []).append((p, np_))
                self.table[l][slot, blk] = -1
                self._decref(l, p)
                self._map(slot, l, blk, np_)
                self.stats.cow_copies += 1
            elif (l, p) in self.key_of:
                self._unregister(l, p)
        self._maybe_check(slot=slot, t=t)

    # -- per-tick device tables ----------------------------------------
    def build_tables(self, pos: np.ndarray, active: np.ndarray,
                     Hkv: int) -> hd.PageTables:
        """Physical indirection tables for one decode tick.

        ``pos``: (slots,) host positions; ``active``: (slots,) bool.
        Inactive rows point at TRASH everywhere (attend output is
        discarded, update writes are inert)."""
        nr, M = self.nr, self.M
        R = self.slots * Hkv
        nbands = 2 + (M - 1)
        attend = np.full((R, nbands), TRASH * Hkv, np.int32)
        update = np.full((R, M), TRASH * Hkv, np.int32)
        heads = np.arange(Hkv, dtype=np.int32)
        for s in range(self.slots):
            rows = slice(s * Hkv, (s + 1) * Hkv)
            attend[rows] += heads[:, None]
            update[rows] += heads[:, None]
            if not active[s]:
                continue
            t = int(pos[s])
            b0 = t // nr
            pages = np.empty((nbands,), np.int32)
            pages[0] = self.table[0][s, b0]
            pages[1] = self.table[0][s, b0 - 1] if b0 >= 1 else TRASH
            for l in range(1, M):
                Il = t // (nr << l)
                pages[1 + l] = (self.table[l][s, Il - 1] if Il >= 1
                                else TRASH)
            upages = np.array(
                [self.table[l][s, t // (nr << l)] for l in range(M)],
                np.int32)
            assert (pages >= 0).all() and (upages >= 0).all(), \
                (s, t, pages, upages)
            attend[rows] = pages[None, :] * Hkv + heads[:, None]
            update[rows] = upages[None, :] * Hkv + heads[:, None]
        return hd.PageTables(attend=jnp.asarray(attend),
                             update=jnp.asarray(update))


# ---------------------------------------------------------------------------
# device-side pool construction and data movement
# ---------------------------------------------------------------------------

def init_paged_caches(cfg, pool: PagePool):
    """Model-level paged caches mirroring ``lm_init_decode_caches``:
    one :class:`~repro.core.h1d_decode.PagedH1DCache` per layer, leaves
    stacked over layers for scan-able stacks (the engine's slot axis
    then being 1, as for the dense cache).  A pool with quantized
    levels (``quant_levels > 0``) yields ``QuantPagedH1DCache`` leaves:
    int8 pages + per-row f32 scale arrays, the dtype split read off
    ``pool.quant`` so the pool object stays the single source of
    storage-format truth."""
    from repro.models.transformer import _stacked_caches
    Hkv, Dh = cfg.num_kv_heads, cfg.head_dim
    rows = [n * Hkv for n in pool.num_pages]
    if any(pool.quant):
        one = hd.init_quant_paged_pool(rows, pool.nr, Dh, Dh, cfg.jdtype,
                                       quant=tuple(pool.quant))
    else:
        one = hd.init_paged_pool(rows, pool.nr, Dh, Dh, cfg.jdtype)
    if _stacked_caches(cfg):
        return jax.tree.map(
            lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), one)
    return [one for _ in range(cfg.num_layers)]


def _quant_flags(cache) -> Tuple[bool, ...]:
    if isinstance(cache, hd.QuantPagedH1DCache):
        return tuple(bool(a.dtype == jnp.int8) for a in (cache.k, *cache.ck))
    return (False,) * (1 + len(cache.ck))


def _per_level(cache, fn, sfn=None):
    """Apply ``fn(level, k_arr, v_arr) -> (k, v)`` to every level's
    data arrays.  For a :class:`~repro.core.h1d_decode.QuantPagedH1DCache`
    the per-row scale arrays (same leading physical-row axes) go through
    ``sfn(level, ksc, vsc) -> (ksc, vsc)`` -- or pass unchanged when
    ``sfn`` is None."""
    k, v = fn(0, cache.k, cache.v)
    ck, cv = [], []
    for i, (a, b) in enumerate(zip(cache.ck, cache.cv)):
        a2, b2 = fn(i + 1, a, b)
        ck.append(a2)
        cv.append(b2)
    if not isinstance(cache, hd.QuantPagedH1DCache):
        return hd.PagedH1DCache(k=k, v=v, ck=tuple(ck), cv=tuple(cv))
    ksc, vsc = cache.ksc, cache.vsc
    cksc, cvsc = list(cache.cksc), list(cache.cvsc)
    if sfn is not None:
        ksc, vsc = sfn(0, ksc, vsc)
        for i in range(len(cksc)):
            cksc[i], cvsc[i] = sfn(i + 1, cksc[i], cvsc[i])
    return hd.QuantPagedH1DCache(k=k, v=v, ck=tuple(ck), cv=tuple(cv),
                                 ksc=ksc, vsc=vsc,
                                 cksc=tuple(cksc), cvsc=tuple(cvsc))


def _map_layers(caches, stacked: bool, fn):
    if stacked:
        return fn(caches)
    return [fn(c) for c in caches]


def _copy_table_shape(caches, Hkv: int, stacked: bool) -> Tuple[int, int]:
    """(levels, entries per level) of a flush's copy table.  Entries:
    the usable pages of the pool's smallest level (all but ZERO and
    TRASH), plus one.  A flush's copies (after the last-writer dedup)
    land on distinct private write-set pages of distinct slots; every
    slot prepared since the last flush holds one such page on every
    level, except at most one whose preparation ran out of pages and
    forced the flush, so no level takes more."""
    c = caches if stacked else caches[0]
    rows = [a.shape[int(stacked)] for a in (c.k, *c.ck)]
    return len(rows), min(rows) // Hkv - 1


def _copy_pages(caches, table, Hkv: int, stacked: bool):
    """Every level's page copies of one flush, in place.  ``table``:
    (levels, n, 2) int32 ``(src_page, dst_page)``; a negative
    ``dst_page`` marks an unused entry, which writes nothing."""
    heads = jnp.arange(Hkv, dtype=jnp.int32)

    def per_level(l, ka, va):
        src = (table[l, :, :1] * Hkv + heads).reshape(-1)
        dst = table[l, :, 1:]
        dst = jnp.where(dst >= 0, dst * Hkv + heads,
                        ka.shape[int(stacked)]).reshape(-1)

        def copy(a):
            if stacked:
                return a.at[:, dst].set(a[:, src], mode="drop")
            return a.at[dst].set(a[src], mode="drop")
        return copy(ka), copy(va)

    # scale arrays share the physical-row axis, so the same row copy
    # applies (a page's scales travel with its int8 payload)
    return _map_layers(caches, stacked,
                       lambda c: _per_level(c, per_level, per_level))


# one program per pool: the table's shape is fixed by the pool, so no
# count of copies or mix of levels compiles again; the caches are
# donated, so each scatter writes its pages in place
_copy_program = jax.jit(_copy_pages, donate_argnums=0,
                        static_argnums=(2, 3))


def apply_copies(caches, copies: Dict[int, List[Tuple[int, int]]],
                 Hkv: int, stacked: bool):
    """Batched page copies (COW + zero-init) of one flush: ``copies``
    maps level -> [(src_page, dst_page)].  All levels go to the device
    as one table in one donated program (``_copy_program``), so the
    caches passed in are consumed and the returned ones replace them.

    A mid-tick preemption can free a page that already has a pending
    copy and hand it to a later allocation, which schedules its own
    copy to the SAME destination -- scatter order over duplicate indices
    is undefined, so only the LAST copy per destination is kept (the
    stale one targeted a page its owner no longer holds)."""
    if not copies:
        return caches
    levels, n = _copy_table_shape(caches, Hkv, stacked)
    table = np.full((levels, n, 2), -1, np.int32)
    table[:, :, 0] = ZERO
    for l, pairs in copies.items():
        last = {d: s for s, d in pairs}          # last writer per dst
        assert len(last) <= n, (
            f"{len(last)} page copies at level {l} in one flush; the "
            f"pool's table holds {n}")
        for i, (d, s) in enumerate(last.items()):
            table[l, i] = (s, d)
    return _copy_program(caches, table, Hkv, stacked)


def scatter_prefill(caches, dense_caches,
                    writes: List[Tuple[int, Dict[int, List[Tuple[int, int]]]]],
                    Hkv: int, nr: int, stacked: bool):
    """Copy freshly prefilled cache blocks into their allocated pages.

    ``dense_caches``: the group-prefill H1DCache (rows ``gp * Hkv``);
    ``writes``: per admitted request ``(dense_row_index, level ->
    [(block, page)])`` as returned by :func:`PagePool.admit`."""
    idx: Dict[int, Tuple[list, list, list]] = {}
    for i, per_level_writes in writes:
        for l, pairs in per_level_writes.items():
            rows, blks, dst = idx.setdefault(l, ([], [], []))
            for blk, page in pairs:
                for h in range(Hkv):
                    rows.append(i * Hkv + h)
                    blks.append(blk)
                    dst.append(page * Hkv + h)
    if not idx:
        return caches
    jidx = {l: tuple(jnp.asarray(np.asarray(a, np.int32)) for a in v)
            for l, v in idx.items()}

    def per_layer(pool_c, dense_c):
        dlv = [(dense_c.k, dense_c.v)] + list(zip(dense_c.ck, dense_c.cv))
        quant = _quant_flags(pool_c)

        def blocks(dense_arr):
            """Gather the written (..., nr, D) page blocks from the
            dense prefill cache."""
            rows, blks, _ = jidx[l_cur[0]]
            if stacked:
                NL, Rr, Ll, D = dense_arr.shape
                blkd = dense_arr.reshape(NL, Rr, Ll // nr, nr, D)
                return blkd[:, rows, blks]
            Rr, Ll, D = dense_arr.shape
            blkd = dense_arr.reshape(Rr, Ll // nr, nr, D)
            return blkd[rows, blks]

        l_cur = [0]

        def per_level(l, ka, va):
            if l not in jidx:
                return ka, va
            l_cur[0] = l
            dst = jidx[l][2]
            dk, dv = dlv[l]

            def put(pool_arr, dense_arr):
                vals = blocks(dense_arr)
                if quant[l]:
                    vals, _ = qz.quantize_int8(vals, axis=-1)
                if stacked:
                    return pool_arr.at[:, dst].set(vals)
                return pool_arr.at[dst].set(vals)

            return put(ka, dk), put(va, dv)

        def per_level_sc(l, ksa, vsa):
            # prefill scales: same absmax rule the decode kernel applies
            # to its in-place rewrites, so a prefix-shared page and a
            # decode-rebuilt page of the same tokens carry identical
            # scales
            if l not in jidx or not quant[l]:
                return ksa, vsa
            l_cur[0] = l
            dst = jidx[l][2]
            dk, dv = dlv[l]

            def put(sc_arr, dense_arr):
                sc = qz.int8_scale(blocks(dense_arr), axis=-1)[..., 0]
                if stacked:
                    return sc_arr.at[:, dst].set(sc)
                return sc_arr.at[dst].set(sc)

            return put(ksa, dk), put(vsa, dv)

        return _per_level(pool_c, per_level, per_level_sc)

    if stacked:
        return per_layer(caches, dense_caches)
    return [per_layer(c, d) for c, d in zip(caches, dense_caches)]


def snapshot_slot(caches, pool: PagePool, slot: int, Hkv: int,
                  stacked: bool) -> Dict[int, tuple]:
    """Swap-out a slot's mapped pages to host memory (preemption mode
    'swap'): per level ``(blocks, k_content, v_content, k_scales,
    v_scales)`` where the content arrays carry all layers (stacked
    leading dim) and all ``Hkv`` page rows per block -- enough to
    restore the slot bit-exact later, unlike recompute-resume whose
    re-prefill only matches the decode-built cache to ~1e-6.  For int8
    levels the content is the raw int8 payload plus its per-row scales;
    fp32 levels carry ``None`` scales."""
    snap: Dict[int, tuple] = {}
    layers = [caches] if stacked else list(caches)

    for l in range(pool.M):
        blks = np.nonzero(pool.table[l][slot] >= 0)[0]
        if len(blks) == 0:
            continue
        rows = np.concatenate(
            [np.arange(Hkv) + int(pool.table[l][slot, b]) * Hkv
             for b in blks])
        rj = jnp.asarray(rows)

        def lvl_arrays(c, l=l):
            return ((c.k, c.v) if l == 0
                    else (c.ck[l - 1], c.cv[l - 1]))

        def lvl_scales(c, l=l):
            return ((c.ksc, c.vsc) if l == 0
                    else (c.cksc[l - 1], c.cvsc[l - 1]))

        has_sc = isinstance(layers[0], hd.QuantPagedH1DCache) and \
            _quant_flags(layers[0])[l]
        if stacked:
            ka, va = lvl_arrays(caches)
            ks = np.asarray(ka[:, rj])
            vs = np.asarray(va[:, rj])
            kss = vss = None
            if has_sc:
                ksa, vsa = lvl_scales(caches)
                kss = np.asarray(ksa[:, rj])
                vss = np.asarray(vsa[:, rj])
        else:
            ks = np.stack([np.asarray(lvl_arrays(c)[0][rj])
                           for c in layers])
            vs = np.stack([np.asarray(lvl_arrays(c)[1][rj])
                           for c in layers])
            kss = vss = None
            if has_sc:
                kss = np.stack([np.asarray(lvl_scales(c)[0][rj])
                                for c in layers])
                vss = np.stack([np.asarray(lvl_scales(c)[1][rj])
                                for c in layers])
        snap[l] = (blks.astype(np.int64), ks, vs, kss, vss)
    return snap


def restore_slot(caches, pool: PagePool, slot: int, snap, Hkv: int,
                 stacked: bool):
    """Swap-in a preempted slot: allocate private pages for every
    snapshotted block (no registry sharing -- decode-written content is
    only ~1e-6-equal to a prefill of the same tokens, and restore must
    be bit-exact), map them, and scatter the saved bytes back.  Raises
    :class:`PoolExhausted` (caller unwinds with ``release_slot``).

    The snapshot's per-level dtype must MATCH the pool's: a snapshot
    taken under a different ``cache_dtype``/``quant_levels`` config is
    a different wire format (int8 payloads are meaningless without
    their scales and vice versa), so a mismatch raises ``ValueError``
    instead of silently scattering garbage."""
    first = caches if stacked else caches[0]
    lvl_dtype = [a.dtype for a in (first.k, *first.ck)]
    for l, entry in snap.items():
        ks = entry[1]
        if ks.dtype != lvl_dtype[l]:
            raise ValueError(
                f"snapshot level-{l} dtype {ks.dtype} cannot restore "
                f"into a {lvl_dtype[l]} pool -- cache_dtype/quant_levels "
                "changed between snapshot and restore")

    placed = pool.admit_snapshot(
        slot, {l: entry[0] for l, entry in snap.items()})
    per_level_rows = {
        l: np.concatenate([np.arange(Hkv) + p * Hkv for _, p in pairs])
        for l, pairs in placed.items()}

    def per_layer(c, li):
        def per_level(l, ka, va):
            if l not in snap:
                return ka, va
            _, ks, vs, _, _ = snap[l]
            dst = jnp.asarray(per_level_rows[l])
            if stacked:
                return (ka.at[:, dst].set(jnp.asarray(ks)),
                        va.at[:, dst].set(jnp.asarray(vs)))
            return (ka.at[dst].set(jnp.asarray(ks[li])),
                    va.at[dst].set(jnp.asarray(vs[li])))

        def per_level_sc(l, ksa, vsa):
            if l not in snap or snap[l][3] is None:
                return ksa, vsa
            _, _, _, kss, vss = snap[l]
            dst = jnp.asarray(per_level_rows[l])
            if stacked:
                return (ksa.at[:, dst].set(jnp.asarray(kss)),
                        vsa.at[:, dst].set(jnp.asarray(vss)))
            return (ksa.at[dst].set(jnp.asarray(kss[li])),
                    vsa.at[dst].set(jnp.asarray(vss[li])))

        return _per_level(c, per_level, per_level_sc)

    if stacked:
        return per_layer(caches, 0)
    return [per_layer(c, li) for li, c in enumerate(caches)]


def gather_slot_cache(caches, pool: PagePool, slot: int, Hkv: int,
                      stacked: bool):
    """Reconstruct a slot's DENSE H1DCache from its page tables
    (unmapped blocks read as zeros, exactly the dense engine's initial
    state).  Used by the parity tests and debugging tooling.  Quantized
    levels are DEQUANTIZED to f32 on the way out -- the dense H1DCache
    has no scale side-band, so this is the quantized pool's lossy view
    (exact for zero/never-written rows, one rounding step otherwise)."""
    nr, Lp = pool.nr, pool.Lp

    def per_layer(pool_c):
        lvls = [(pool_c.k, pool_c.v)] + list(zip(pool_c.ck, pool_c.cv))
        quant = _quant_flags(pool_c)
        if isinstance(pool_c, hd.QuantPagedH1DCache):
            slvls = ([(pool_c.ksc, pool_c.vsc)]
                     + list(zip(pool_c.cksc, pool_c.cvsc)))
        outs = []
        for l, (ka, va) in enumerate(lvls):
            if quant[l]:
                ksa, vsa = slvls[l]
                ka = jnp.asarray(qz.dequantize_int8(
                    ka, jnp.asarray(ksa)[..., None]))
                va = jnp.asarray(qz.dequantize_int8(
                    va, jnp.asarray(vsa)[..., None]))
            Ll = Lp >> l
            shp = (ka.shape[0], Hkv, Ll, ka.shape[-1]) if stacked else \
                  (Hkv, Ll, ka.shape[-1])
            dk = np.zeros(shp, ka.dtype)
            dv = np.zeros(shp[:-1] + (va.shape[-1],), va.dtype)
            kh = np.asarray(ka)
            vh = np.asarray(va)
            for blk in np.nonzero(pool.table[l][slot] >= 0)[0]:
                page = int(pool.table[l][slot, blk])
                rows = slice(page * Hkv, (page + 1) * Hkv)
                cols = slice(blk * nr, (blk + 1) * nr)
                if stacked:           # (NL, Hkv, nr, D) pool rows
                    dk[:, :, cols] = kh[:, rows]
                    dv[:, :, cols] = vh[:, rows]
                else:
                    dk[:, cols] = kh[rows]
                    dv[:, cols] = vh[rows]
            outs.append((dk, dv))
        k, v = outs[0]
        ck = tuple(o[0] for o in outs[1:])
        cv = tuple(o[1] for o in outs[1:])
        return hd.H1DCache(k=jnp.asarray(k), v=jnp.asarray(v),
                           ck=jax.tree.map(jnp.asarray, ck),
                           cv=jax.tree.map(jnp.asarray, cv))

    return _map_layers(caches, stacked, per_layer)


def pool_bytes(caches) -> int:
    """Total HBM footprint of the paged pools (all layers/levels)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
