"""Batched serving engine: continuous batching over the unified model
API (prefill + single-token decode with hierarchical KV caches).

Design points for scale (DESIGN.md):
* decode state is a pure pytree -- slots join/leave by writing rows, the
  jit'd step never retraces;
* admission is planned per tick by a continuous-batching scheduler
  (``serve/scheduler.py``): per-tick token budget, chunked prefill
  (long prompts stream their tail through the regular decode ticks),
  bounded lookahead past a head-of-queue that does not fit, and
  requeue-on-preemption -- with the default knobs reproducing the
  legacy FIFO bucket grouping exactly;
* admission pads prompts to power-of-two length buckets, so prefill
  compiles O(log max_len) shapes, not one per distinct prompt length,
  and admits ALL planned requests sharing a bucket in one batched
  prefill call (per-row ``true_len``, row count padded to a power of
  two) so admission cost amortizes under load while the prefill jit
  cache stays O(log slots * log max_len);
* prompts longer than ``max_len - 1`` are rejected (or tail-truncated)
  at ``submit`` -- see ``ServeEngine.overflow``;
* generation ends at ``max_new_tokens``, a full cache, or any of the
  request's ``stop_tokens`` (the stop token is kept in ``out_tokens``);
* finished slots are frozen (their ``pos`` stops advancing) so the
  clamped cache writes of an idle slot never walk out of range;
* per-tick bookkeeping reads a host-side numpy mirror of the slot
  positions -- one device sync per step (the sampled tokens), not one
  per active slot;
* the hierarchical H1D cache gives O(nr log L) attention per token --
  with ``decode_impl='pallas'`` the whole tick's attend runs as ONE
  fused kernel launch (and the ancestor update as one more), so
  long-context decode cost is flat in practice;
* ``paged=True`` swaps the per-slot dense cache for the PAGED pool
  (``serve/paged_cache.py``): HBM is bounded by ``pool_pages``, not
  ``slots * max_len``, pages are prefix-shared across requests with
  copy-on-write, and pool exhaustion preempts the newest request
  (requeued; swap-mode page snapshots restore it bit-exact) instead of
  failing -- the dense slot path stays as the bit-parity oracle;
* the engine is deployment-shaped (request queue, slot map, step loop)
  while staying single-host here; the multi-pod serve driver shards the
  slot dim over DP axes (launch/serve.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import ModelConfig, get_model
from .scheduler import ContinuousBatchingScheduler, QueueEntry


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 32
    stop_tokens: Optional[Sequence[int]] = None
    out_tokens: Optional[List[int]] = None


class ServeEngine:
    """``overflow`` policy for prompts longer than ``max_len - 1`` (the
    cache needs >= 1 free position to generate anything): ``'error'``
    rejects at ``submit()``; ``'truncate'`` keeps the LAST
    ``max_len - 1`` prompt tokens (most recent context) and serves the
    rest of the request normally.  Silent admission used to prefill a
    cache longer than the slot rows, corrupting neighbouring slots.

    ``decode_impl`` overrides ``cfg.decode_impl`` (``'auto'`` |
    ``'jnp'`` | ``'pallas'`` | ``'pallas_interpret'``): ``'pallas'``
    runs each decode tick through the fused single-launch
    hierarchical-KV kernels (``kernels/h1d_decode_kernel``); ``'auto'``
    lets the process ``KernelPolicy`` resolve per backend.

    ``mesh`` enables sequence-parallel serving: the hierarchical cache
    shards its sequence axis over ``mesh[sp_axis]`` and every decode
    tick runs the fused kernels per shard under ``shard_map``
    (``repro.parallel.sp_attention``) -- the configuration that used to
    force ``impl='jnp'``.  Requires ``attention='h1d'`` and a padded
    ``max_len`` of at least ``data_axis_size * nr`` (one level-0 block
    per shard).

    ``paged=True`` serves from the paged hierarchical cache pool
    (``serve/paged_cache.py``): per-layer pools of ``pool_pages``
    nr-row pages (plus proportionally sized coarse-level pools) replace
    the ``slots * max_len`` dense slabs.  Requires ``attention='h1d'``
    without sliding-window layers and is host-local (``mesh`` must be
    None).  ``prefix_sharing`` maps bit-identical prompt-prefix pages
    (and their coarse ancestors) once across requests, copy-on-write.
    ``token_budget`` / ``lookahead`` / ``prefill_chunk`` tune the
    continuous-batching scheduler for either path.

    ``cache_dtype`` (default from ``cfg.cache_dtype``) selects the
    paged pool's page storage: ``'fp32'`` keeps the bit-parity oracle
    path; ``'int8'`` stores pages as int8 with per-row scales
    (``core.quantization``) and decodes through the quantized kernels
    -- ~4x more pages at fixed HBM.  ``quant_levels`` (default
    ``cfg.cache_quant_levels``) restricts quantization to hierarchy
    levels ``[0, n)``; -1 = all levels.  int8 requires ``paged=True``
    (the dense slab cache has no scale side-band)."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 8,
                 max_len: int = 512, greedy: bool = True, seed: int = 0,
                 overflow: str = "error", decode_impl: Optional[str] = None,
                 mesh=None, sp_axis: str = "data", paged: bool = False,
                 pool_pages: Optional[int] = None, prefix_sharing: bool = True,
                 token_budget: Optional[int] = None, lookahead: int = 0,
                 prefill_chunk: Optional[int] = None,
                 preempt_mode: str = "swap",
                 cache_dtype: Optional[str] = None,
                 quant_levels: Optional[int] = None):
        if preempt_mode not in ("swap", "recompute"):
            raise ValueError(f"unknown preempt_mode {preempt_mode!r}")
        if cache_dtype is None:
            cache_dtype = cfg.cache_dtype
        if cache_dtype not in ("fp32", "int8"):
            raise ValueError(f"unknown cache_dtype {cache_dtype!r}")
        if quant_levels is None:
            quant_levels = cfg.cache_quant_levels
        if cache_dtype == "int8" and not paged:
            raise ValueError("cache_dtype='int8' requires paged=True: the "
                             "dense slab cache has no per-page scale "
                             "side-band")
        self.cache_dtype = cache_dtype
        self.quant_levels = quant_levels
        if cfg.family == "encdec":
            raise NotImplementedError(
                "ServeEngine targets decoder-only families; enc-dec serving "
                "goes through launch/serve.py with per-request encoder runs")
        if overflow not in ("error", "truncate"):
            raise ValueError(f"unknown overflow policy {overflow!r}")
        if decode_impl is not None and decode_impl != cfg.decode_impl:
            cfg = dataclasses.replace(cfg, decode_impl=decode_impl)
        # validate against the canonical impl enum up front: a typo'd
        # decode_impl must fail at engine construction, not mid-serve
        from repro.kernels.tuning import canonical_impl
        canonical_impl(cfg.decode_impl)
        from repro.models.transformer import _stacked_caches
        from repro.parallel.sp_attention import sp_scope
        self.cfg = cfg
        self.overflow = overflow
        self.params = params
        self.fns = get_model(cfg)
        self.slots = slots
        self.max_len = max_len
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self._slot_axis = 1 if _stacked_caches(cfg) else 0
        self._stacked = _stacked_caches(cfg)

        self.mesh = mesh
        self.sp_axis = sp_axis
        sp_d = dict(mesh.shape).get(sp_axis, 1) if mesh is not None else 1
        if sp_d > 1:
            if cfg.attention != "h1d":
                raise ValueError(
                    "SP serving shards the hierarchical cache's sequence "
                    f"axis; attention={cfg.attention!r} has no such cache")
            from repro.core import hierarchy as hc
            Lp = hc.padded_length(max_len, cfg.nr)
            if Lp < sp_d * cfg.nr or Lp % (sp_d * cfg.nr):
                raise ValueError(
                    f"SP serving: padded max_len {Lp} cannot keep one "
                    f"nr={cfg.nr} block per shard on a {sp_d}-way "
                    f"'{sp_axis}' axis; use fewer shards or a longer "
                    f"max_len")

        self.sched = ContinuousBatchingScheduler(
            token_budget=token_budget, lookahead=lookahead,
            prefill_chunk=prefill_chunk)

        self.paged = paged
        self.pool = None
        if paged:
            from . import paged_cache as pc
            if mesh is not None:
                raise ValueError("paged serving is host-local: the page "
                                 "tables are host state; use either "
                                 "paged=True or mesh=, not both")
            if (cfg.attention != "h1d" or cfg.sliding_window > 0
                    or cfg.global_every > 0
                    or cfg.family not in ("dense", "moe", "vlm")):
                raise ValueError(
                    "paged serving requires a uniform h1d attention stack "
                    f"(family={cfg.family!r}, attention={cfg.attention!r}, "
                    f"sliding_window={cfg.sliding_window}, "
                    f"global_every={cfg.global_every})")
            self._pc = pc
            from repro.core import hierarchy as hc
            Lp = hc.padded_length(max_len, cfg.nr)
            if pool_pages is None:
                pool_pages = slots * (Lp // cfg.nr)   # dense-equivalent
            self.pool = pc.PagePool(
                slots=slots, max_len=max_len, nr=cfg.nr,
                pool_pages=pool_pages,
                quant_levels=(quant_levels if cache_dtype == "int8" else 0))
            self.prefix_sharing = prefix_sharing
            self.preempt_mode = preempt_mode
            self.caches = pc.init_paged_caches(cfg, self.pool)
        else:
            self.caches = self.fns.init_caches(params, cfg, slots, max_len)
        self.tokens = jnp.zeros((slots,), jnp.int32)
        self.pos = jnp.zeros((slots,), jnp.int32)
        # host-side mirror of ``pos``: the decode loop reads positions
        # every tick (done checks); keeping a numpy twin avoids a device
        # sync per active slot per step.
        self.pos_host = np.zeros((slots,), np.int64)
        self.active = np.zeros((slots,), bool)
        self.req: List[Optional[Request]] = [None] * slots
        # chunked prefill: tokens still to stream through decode ticks
        # per slot (outputs discarded while non-empty)
        self.feed: List[List[int]] = [[] for _ in range(slots)]
        # admission prompt per slot (preemption rebuilds the resume
        # prompt from it) and admission serial (preemption victim order)
        self._admitted: List[Optional[np.ndarray]] = [None] * slots
        self._admit_serial: Dict[int, int] = {}
        self._serial = 0
        self.preemptions = 0
        self.queue: List[QueueEntry] = []
        # telemetry bookkeeping (repro.obs): per-request wall-clock
        # marks keyed by id(req) -- submit time (TTFT) and last-token
        # time (inter-token latency) -- plus a per-tick prefill-token
        # accumulator (token-budget utilization) and the last-seen pool
        # stats (mirrored into obs counters as deltas).  All writes are
        # behind ``obs.enabled()`` so the disabled path stays free.
        self._t_submit: Dict[int, float] = {}
        self._t_last: Dict[int, float] = {}
        self._tick_prefill_tokens = 0
        self._pool_seen: Dict[str, int] = {}

        # Prompt length bucketing: right-pad prompts to the next power of
        # two (capped at max_len) so _prefill1 compiles O(log max_len)
        # shapes instead of one per distinct prompt length.  Only safe
        # when the padded tail cannot reach the true-position logits or
        # the decode-visible cache, so gated off for:
        #  * recurrent families (ssm/hybrid): the SSM prefill scan over
        #    pad tokens corrupts the state (and encdec never gets here);
        #  * sliding-window configs: the rolling local cache keeps only
        #    the LAST 2*window rows, so pads evict real in-window keys;
        #  * h1d coarse-q: coarse QUERY means average pad embeddings
        #    across cluster boundaries (the documented leak, DESIGN.md
        #    1.2), shifting logits at the true last token.
        self._bucket = (cfg.family not in ("ssm", "hybrid", "encdec")
                        and cfg.sliding_window == 0
                        and (cfg.attention != "h1d"
                             or cfg.causal_mode == "fine-q"))

        # the sp_scope context is entered at TRACE time (jit traces the
        # wrapper synchronously), so the h1d decode/attention entry
        # points see the mesh and route through the shard_map'd kernels
        def _decode_traced(p, c, tok, t):
            with sp_scope(self.mesh, self.sp_axis):
                return self.fns.decode_step(p, cfg, c, tok, t)

        def _decode_paged_traced(p, c, tok, t, tabs):
            return self.fns.decode_step(p, cfg, c, tok, t, page_tables=tabs)

        def _prefill_traced(p, batch, n):
            with sp_scope(self.mesh, self.sp_axis):
                return self.fns.prefill(p, cfg, batch, max_len, true_len=n)

        self._decode = jax.jit(_decode_paged_traced if paged
                               else _decode_traced)
        self._prefill1 = jax.jit(_prefill_traced)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        """Queue a request.  Prompts longer than ``max_len - 1`` (no
        room left to generate) are rejected or tail-truncated per the
        engine's ``overflow`` policy -- silently admitting them used to
        prefill an over-long cache whose slot write sliced into
        neighbouring slots' rows."""
        prompt = np.asarray(req.prompt, np.int32)
        S = int(prompt.shape[0])
        limit = self.max_len - 1
        if S > limit:
            if self.overflow == "truncate":
                # truncate a private copy -- the caller's Request object
                # is left intact (it may be logged or re-submitted to an
                # engine with a larger max_len)
                prompt = prompt[-limit:]
            else:
                raise ValueError(
                    f"prompt length {S} > max_len - 1 = {limit}; shorten "
                    f"the prompt or construct the engine with "
                    f"overflow='truncate'")
        req.out_tokens = []
        self.queue.append(QueueEntry(req=req, prompt=prompt))
        if obs.enabled():
            self._t_submit[id(req)] = time.perf_counter()
            obs.counter("serve.requests").inc()

    # -- telemetry -----------------------------------------------------
    def _note_token(self, req: Request) -> None:
        """TTFT on the first generated token, inter-token latency on
        every later one (both survive preemption: the marks are keyed
        by request, not slot)."""
        now = time.perf_counter()
        rid = id(req)
        if len(req.out_tokens) == 1:
            t0 = self._t_submit.get(rid)
            if t0 is not None:
                obs.histogram("serve.ttft_s").observe(now - t0)
        else:
            last = self._t_last.get(rid)
            if last is not None:
                obs.histogram("serve.itl_s").observe(now - last)
        self._t_last[rid] = now

    def _note_finish(self, req: Request) -> None:
        obs.counter("serve.finished").inc()
        rid = id(req)
        self._t_last.pop(rid, None)
        t0 = self._t_submit.pop(rid, None)
        if t0 is not None:
            obs.histogram("serve.request_latency_s").observe(
                time.perf_counter() - t0)

    def _tick_obs(self, n_active: int) -> None:
        """Per-tick gauges/counters (called only when telemetry is on)."""
        obs.counter("serve.ticks").inc()
        obs.gauge("serve.queue_depth").set(len(self.queue))
        obs.gauge("serve.active_slots").set(n_active)
        budget = self.sched.token_budget
        if budget:
            used = self._tick_prefill_tokens + n_active
            obs.gauge("serve.token_budget_util").set(used / budget)
        self._tick_prefill_tokens = 0
        if self.paged:
            obs.gauge("pool.occupancy").set(self.pool.occupancy())
            for k, v in self.pool.stats.snapshot().items():
                delta = v - self._pool_seen.get(k, 0)
                if delta:
                    obs.counter(f"pool.{k}").inc(delta)
                    self._pool_seen[k] = v

    def _bucket_len(self, S: int) -> int:
        """Padded prompt length: next power of two capped at max_len
        (identity when bucketing is gated off for this config)."""
        if not self._bucket:
            return S
        return max(S, min(1 << max(S - 1, 0).bit_length(), self.max_len))

    def _stopped(self, req: Request, tok: int) -> bool:
        return bool(req.stop_tokens) and tok in req.stop_tokens

    # -- admission -----------------------------------------------------
    def _can_admit_fn(self) -> Callable[[QueueEntry], bool]:
        """Admission feasibility for the scheduler.  The paged probe
        commits its per-level net page need on success, so entries
        planned earlier in the SAME tick count against later ones (the
        scheduler only calls it once per picked entry)."""
        if not self.paged:
            return lambda e: True
        planned = [0] * self.pool.M

        def can(e: QueueEntry) -> bool:
            chunk = e.prompt[:self.sched.chunk_len(len(e.prompt))]
            need = self.pool.net_need(np.asarray(chunk, np.int32),
                                      share=self.prefix_sharing)
            if all(need[l] + planned[l] <= self.pool.available(l)
                   for l in range(self.pool.M)):
                for l in range(self.pool.M):
                    planned[l] += need[l]
                return True
            return False

        return can

    def _admit(self):
        """Plan this tick's admissions with the scheduler and run one
        batched prefill per planned bucket group.  Swap-preempted
        entries restore first (no prefill needed, their pages scatter
        straight back), scanned over the same lookahead window."""
        free = [s for s in range(self.slots) if not self.active[s]]
        if not free or not self.queue:
            return
        j = 0
        while free and j < min(len(self.queue), self.sched.lookahead + 1):
            entry = self.queue[j]
            if entry.restore is not None and self._try_restore(entry,
                                                               free[0]):
                free.pop(0)
                self.queue.pop(j)
            else:
                j += 1
        if not free or not self.queue:
            return
        can = self._can_admit_fn()
        groups, self.queue = self.sched.plan(
            self.queue, len(free), int(self.active.sum()),
            self._bucket_len,
            lambda e: e.restore is None and can(e))
        for group in groups:
            self._admit_group(group, free)

    def _admit_group(self, group, free: List[int]):
        """One batched prefill: every entry in ``group`` shares the
        padded chunk-length bucket ``group.bucket``.  The row count is
        padded to a power of two as well (dummy rows discarded), keeping
        the prefill jit cache at O(log slots * log max_len) shapes."""
        g = len(group.entries)
        Lb = group.bucket
        gp = 1 << (g - 1).bit_length()       # pow2 row count
        prompts = np.zeros((gp, Lb), np.int32)
        ns = np.ones((gp,), np.int32)        # dummy rows: true_len 1
        for i, chunk in enumerate(group.chunks):
            prompts[i, :len(chunk)] = chunk
            ns[i] = len(chunk)
        batch = {"tokens": jnp.asarray(prompts)}
        logits, caches, pos = self._prefill1(self.params, batch,
                                             jnp.asarray(ns))
        dst = free[:g]
        del free[:g]

        kept = [True] * g
        if self.paged:
            kept = self._paged_admit_writes(group, dst, caches)
            if not any(kept):
                return

        if self.greedy:
            nxt = np.asarray(jnp.argmax(logits, -1)).astype(np.int32)
        else:
            # Sample the first generated token with PER-ROW keys:
            # one split per batched call, then each row folds in its
            # DESTINATION SLOT index (dummy pad rows use indices past
            # the slot range).  A single categorical over the padded
            # (gp, V) logits drew one gumbel tensor shaped by gp, so
            # the same request could sample a DIFFERENT first token
            # depending on how many dummy rows its bucket happened
            # to get -- sampling must be invariant to padding.
            self.key, kbase = jax.random.split(self.key)
            row_ids = jnp.asarray(
                np.array(dst + list(range(self.slots,
                                          self.slots + gp - g)),
                         np.int32))
            keys = jax.vmap(jax.random.fold_in, (None, 0))(kbase,
                                                           row_ids)
            nxt = np.asarray(jax.vmap(jax.random.categorical)(
                keys, logits)).astype(np.int32)

        if not self.paged:
            # Write the whole group into its slots with ONE tree.map
            # pass (contiguous free slots collapse to a single slice
            # write).  The slot dim (0, or 1 for scanned layer stacks)
            # may fold kv-heads into the batch (h1d caches: B*Hkv
            # rows), so slot s spans rows [s*r, (s+1)*r) with
            # r = full_rows // slots == rows per request of the batched
            # prefill cache.
            ax = self._slot_axis
            contig = dst == list(range(dst[0], dst[0] + g))

            def write(full, one):
                r = full.shape[ax] // self.slots
                src = [slice(None)] * one.ndim
                src[ax] = slice(0, g * r)
                idx = [slice(None)] * full.ndim
                if contig:
                    # slice write lowers to one dynamic_update_slice
                    idx[ax] = slice(dst[0] * r, (dst[0] + g) * r)
                else:
                    # one row-index scatter -- NOT one full-cache copy
                    # per destination slot
                    rows = np.concatenate([np.arange(s * r, (s + 1) * r)
                                           for s in dst])
                    idx[ax] = jnp.asarray(rows)
                return full.at[tuple(idx)].set(one[tuple(src)])

            self.caches = jax.tree.map(write, self.caches, caches)
        # batched token/pos scatter: 2 dispatches per group, not 2g
        slot_w: List[int] = []
        tok_w: List[int] = []
        pos_w: List[int] = []
        for i, entry in enumerate(group.entries):
            if not kept[i]:
                continue
            s = dst[i]
            req = entry.req
            chunk_n = int(ns[i])
            if obs.enabled():
                obs.counter("serve.admissions").inc()
                self._tick_prefill_tokens += chunk_n
            self.pos_host[s] = chunk_n
            self._admitted[s] = entry.prompt
            slot_w.append(s)
            pos_w.append(chunk_n)
            remainder = list(entry.prompt[chunk_n:].tolist())
            if entry.resume_token is not None:
                # preemption-resume: the next input was already sampled
                # before the preemption -- never re-sample it
                remainder.append(int(entry.resume_token))
            if remainder:
                # chunked prefill (or resume): the next input token is
                # known; the prefill's sampled token is discarded and
                # the tail streams through the decode ticks
                tok_w.append(remainder[0])
                self.feed[s] = remainder[1:]
                self.req[s] = req
                self.active[s] = True
                self._serial += 1
                self._admit_serial[s] = self._serial
                continue
            tok_w.append(int(nxt[i]))
            self.feed[s] = []
            self.req[s] = req
            req.out_tokens.append(int(nxt[i]))
            if obs.enabled():
                self._note_token(req)
            # done-check at admission: the first sampled token may
            # already satisfy max_new_tokens, a stop token, or a full
            # cache -- the slot then never activates, so no decode tick
            # is wasted and max_new_tokens is a hard cap (regression:
            # every request used to get >= 2 tokens).
            done = (len(req.out_tokens) >= req.max_new_tokens
                    or chunk_n >= self.max_len - 1
                    or self._stopped(req, int(nxt[i])))
            if done:
                if obs.enabled():
                    self._note_finish(req)
                self._release(s)
            else:
                self.active[s] = True
                self._serial += 1
                self._admit_serial[s] = self._serial
        idx = jnp.asarray(np.array(slot_w, np.int32))
        self.tokens = self.tokens.at[idx].set(
            jnp.asarray(np.array(tok_w, np.int32)))
        self.pos = self.pos.at[idx].set(
            jnp.asarray(np.array(pos_w, np.int32)))

    def _paged_admit_writes(self, group, dst, caches) -> List[bool]:
        """Map pool pages for every entry (prefix-sharing aware) and
        scatter the freshly prefilled blocks into the registry-missed
        pages.  An entry the pool cannot hold (availability-estimate
        races inside one tick) is unwound and requeued at the head.
        Returns the per-entry kept mask; dense prefill rows keep their
        original indices, so no remapping is needed for the scatter."""
        pc = self._pc
        writes = []
        kept = [False] * len(group.entries)
        failed = []
        for i, (entry, chunk) in enumerate(zip(group.entries,
                                               group.chunks)):
            s = dst[i]
            try:
                w = self.pool.admit(s, np.asarray(chunk, np.int32),
                                    share=self.prefix_sharing)
                writes.append((i, w))
                kept[i] = True
            except pc.PoolExhausted:
                self.pool.release_slot(s)
                failed.append(entry)
        # requeue unwound entries as a block, preserving arrival order
        # (per-entry insert(0, ...) reversed them)
        self.queue[:0] = failed
        if writes:
            self.caches = pc.scatter_prefill(
                self.caches, caches, writes, self.cfg.num_kv_heads,
                self.cfg.nr, self._stacked)
        return kept

    # -- release / preemption ------------------------------------------
    def _release(self, s: int):
        """Finish a slot: free paged pages, clear bookkeeping."""
        self.active[s] = False
        self.req[s] = None
        self.feed[s] = []
        self._admitted[s] = None
        self._admit_serial.pop(s, None)
        if self.paged:
            self.pool.release_slot(s)

    def _preempt(self, victim: int):
        """Evict a running request from its slot (pool pressure) and
        requeue it at the HEAD.

        ``preempt_mode='swap'`` (default) snapshots the victim's pages
        to host memory and restores them bit-exact at re-admission --
        greedy token streams stay IDENTICAL to the dense engine's.
        ``'recompute'`` folds generated tokens into a resume prompt and
        re-prefills on re-admission (no host memory, but the recomputed
        cache matches the decode-built one only to ~1e-6, so greedy
        continuations may drift at argmax near-ties); the already
        sampled next input rides along as ``resume_token`` so non-greedy
        requests never re-roll it."""
        req = self.req[victim]
        base = self._admitted[victim]
        if self.preempt_mode == "swap":
            snap = self._pc.snapshot_slot(self.caches, self.pool, victim,
                                          self.cfg.num_kv_heads,
                                          self._stacked)
            tok = int(np.asarray(self.tokens)[victim])
            entry = QueueEntry(
                req=req, prompt=base,
                restore={"pos": int(self.pos_host[victim]), "tok": tok,
                         "feed": list(self.feed[victim]), "pages": snap})
        elif req.out_tokens:
            prompt = np.concatenate(
                [base, np.asarray(req.out_tokens[:-1], np.int32)])
            entry = QueueEntry(req=req, prompt=prompt.astype(np.int32),
                               resume_token=int(req.out_tokens[-1]))
        else:
            # recompute mode, still prefilling: redo the whole prompt
            entry = QueueEntry(req=req, prompt=base)
        self.queue.insert(0, entry)
        self._release(victim)
        self.preemptions += 1
        obs.counter("serve.preemptions").inc()

    def _try_restore(self, entry: QueueEntry, s: int) -> bool:
        """Swap-in a preempted entry into free slot ``s``; False when
        the pool cannot hold its pages yet."""
        pc = self._pc
        snap = entry.restore["pages"]
        need = {l: len(entry_l[0]) for l, entry_l in snap.items()}
        if any(n > self.pool.available(l) for l, n in need.items()):
            return False
        try:
            self.caches = pc.restore_slot(self.caches, self.pool, s, snap,
                                          self.cfg.num_kv_heads,
                                          self._stacked)
        except pc.PoolExhausted:       # estimate raced; unwind
            self.pool.release_slot(s)
            return False
        self.req[s] = entry.req
        self._admitted[s] = entry.prompt
        self.feed[s] = list(entry.restore["feed"])
        self.pos_host[s] = entry.restore["pos"]
        idx = jnp.asarray(np.array([s], np.int32))
        self.tokens = self.tokens.at[idx].set(int(entry.restore["tok"]))
        self.pos = self.pos.at[idx].set(int(entry.restore["pos"]))
        self.active[s] = True
        self._serial += 1
        self._admit_serial[s] = self._serial
        obs.counter("serve.restores").inc()
        return True

    def _paged_prepare(self):
        """Allocate / COW this tick's write-set pages for every active
        slot, preempting the newest request on pool exhaustion."""
        pc = self._pc
        copies: Dict[int, List[Tuple[int, int]]] = {}

        def flush():
            # preemption snapshots read self.caches: pending COW /
            # zero-init copies (possibly the victim's own) must land
            # first or the snapshot captures stale page bytes
            nonlocal copies
            if copies:
                self.caches = pc.apply_copies(self.caches, copies,
                                              self.cfg.num_kv_heads,
                                              self._stacked)
                self.pool.stats.copy_launches += 1
                copies = {}

        order = sorted((serial, s) for s, serial in
                       self._admit_serial.items())
        for _, s in order:
            if not self.active[s]:
                continue
            while True:
                try:
                    self.pool.prepare_tick(s, int(self.pos_host[s]),
                                           copies)
                    break
                except pc.PoolExhausted:
                    victim = self.sched.choose_victim(self._admit_serial)
                    if victim == s and len(self._admit_serial) == 1:
                        raise RuntimeError(
                            "page pool exhausted with a single active "
                            "request; increase pool_pages") from None
                    flush()
                    self._preempt(victim)
                    if victim == s:    # newest == self: requeued, move on
                        break
        flush()

    # -- tick ----------------------------------------------------------
    def step(self) -> int:
        """One engine tick: admit + one decode step for all active slots.
        Returns number of active slots.

        Each phase of the tick is a profiler span inside ``serve.tick``:
        ``serve.admit``; ``serve.prepare`` (page allocation and copies)
        and ``serve.tables`` (page tables built and sent) when paged;
        ``serve.decode``, the jitted tick's call (argument transfer,
        output allocation, enqueue); ``serve.sample`` (next tokens and
        positions); ``serve.readback``, the host's wait for the sampled
        tokens; ``serve.bookkeep`` (per-slot outputs, done checks and
        the chunked-prefill feed)."""
        with obs.span("serve.tick"):
            n = self._step()
        if obs.enabled():
            self._tick_obs(n)
        return n

    def _step(self) -> int:
        with obs.span("serve.admit"):
            self._admit()
        if not self.active.any():
            return 0
        if self.paged:
            with obs.span("serve.prepare"):
                self._paged_prepare()
            if not self.active.any():        # everything preempted
                return 0
            with obs.span("serve.tables"):
                tabs = self.pool.build_tables(self.pos_host, self.active,
                                              self.cfg.num_kv_heads)
            with obs.span("serve.decode"):
                logits, self.caches = self._decode(self.params,
                                                   self.caches,
                                                   self.tokens, self.pos,
                                                   tabs)
        else:
            with obs.span("serve.decode"):
                logits, self.caches = self._decode(self.params,
                                                   self.caches,
                                                   self.tokens, self.pos)
        with obs.span("serve.sample"):
            if self.greedy:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                self.key, k = jax.random.split(self.key)
                nxt = jax.random.categorical(k, logits).astype(jnp.int32)
            self.tokens = nxt
            # Freeze finished/inactive slots: only slots active for THIS
            # decode advance.  A free-running pos eventually walks past
            # the cache rows, where the clamped cache writes would grind
            # on the last row every tick (and pos itself overflows);
            # pinning t keeps every write in range until the slot is
            # re-admitted.
            act = self.active.astype(np.int32)
            self.pos = self.pos + jnp.asarray(act)
            self.pos_host += act     # mirrors the device update exactly
        with obs.span("serve.readback"):
            nxt_host = np.asarray(nxt)
        with obs.span("serve.bookkeep"):
            self._bookkeep(nxt_host)
        return int(self.active.sum())

    def _bookkeep(self, nxt_host: np.ndarray) -> None:
        """Append each active slot's token, release finished slots and
        feed the next prompt token of slots still prefilling."""
        feed_idx: List[int] = []
        feed_tok: List[int] = []
        for s in range(self.slots):
            if not self.active[s]:
                continue
            if self.feed[s]:
                # chunked prefill in flight: the model just absorbed one
                # prompt token; the next input is known, logits dropped
                feed_idx.append(s)
                feed_tok.append(self.feed[s].pop(0))
                continue
            req = self.req[s]
            req.out_tokens.append(int(nxt_host[s]))
            if obs.enabled():
                self._note_token(req)
            done = (len(req.out_tokens) >= req.max_new_tokens
                    or int(self.pos_host[s]) >= self.max_len - 1
                    or self._stopped(req, int(nxt_host[s])))
            if done:
                if obs.enabled():
                    self._note_finish(req)
                self._release(s)
        if feed_idx:
            self.tokens = self.tokens.at[jnp.asarray(
                np.array(feed_idx, np.int32))].set(
                jnp.asarray(np.array(feed_tok, np.int32)))

    def run(self) -> None:
        while self.queue or self.active.any():
            self.step()
