"""Paged hierarchical KV-cache pool + continuous-batching scheduler:
paged-vs-dense greedy token parity (the dense slot engine is the
oracle), allocator/scheduler unit behavior, prefix sharing + COW,
eviction, preemption (swap and recompute), chunked prefill, and
bit-exact page reconstruction.

The randomized/property schedules run under ``REPRO_POOL_CHECK=1``:
the pool re-runs the model checker's invariants
(``analysis/pool_model.check_pool_invariants``) after every mutating
op, so these fuzzed engine runs double as an allocator soundness
sweep."""
import contextlib
import os

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serve import (ServeEngine, Request,
                         ContinuousBatchingScheduler, QueueEntry)
from repro.serve import paged_cache as pc


_STATE = {}


@contextlib.contextmanager
def _pool_check():
    """Run the enclosed engine schedule with per-op pool invariant
    checking (plain env try/finally: the hypothesis shim replays test
    bodies, which interacts badly with fixture-scoped monkeypatching)."""
    os.environ["REPRO_POOL_CHECK"] = "1"
    try:
        yield
    finally:
        os.environ.pop("REPRO_POOL_CHECK", None)


def _model():
    if "cfg" not in _STATE:
        cfg = get_smoke_config("llama3.2-1b")
        params, _ = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
        _STATE["cfg"], _STATE["params"] = cfg, params
    return _STATE["cfg"], _STATE["params"]


def _workload(seed, n, cfg, prefix_len=21):
    """Mixed prompts: ~half share a prefix (non-page-aligned so partial
    pages + their coarse ancestors get shared and later COW'd)."""
    rng = np.random.default_rng(seed)
    pre = (np.arange(prefix_len) * 5 % cfg.vocab_size).astype(np.int32)
    out = []
    for i in range(n):
        if rng.random() < 0.5:
            p = np.concatenate([pre, rng.integers(
                0, cfg.vocab_size, int(rng.integers(1, 16))).astype(np.int32)])
        else:
            p = rng.integers(0, cfg.vocab_size,
                             int(rng.integers(3, 40))).astype(np.int32)
        out.append((p, int(rng.integers(1, 8))))
    return out


def _run(wl, **kw):
    cfg, params = _model()
    eng = ServeEngine(cfg, params, max_len=64, **kw)
    reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=m)
            for i, (p, m) in enumerate(wl)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, [r.out_tokens for r in reqs]


_REF = {}


def _dense_ref(seed, n):
    cfg, _ = _model()
    if (seed, n) not in _REF:
        _REF[(seed, n)] = _run(_workload(seed, n, cfg), slots=2)[1]
    return _REF[(seed, n)]


# ---------------------------------------------------------------------------
# engine-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_paged_matches_dense_greedy(impl):
    """Same requests, same greedy tokens -- through the whole engine,
    jnp oracle and fused paged kernels."""
    cfg, _ = _model()
    wl = _workload(3, 6, cfg)
    ref = _dense_ref(3, 6)
    eng, out = _run(wl, slots=2, paged=True, decode_impl=impl)
    assert out == ref
    assert eng.pool.occupancy() == 0.0          # everything released


def test_prefix_sharing_and_cow_with_token_parity():
    """Identical prompts of non-span-aligned length must share pages
    (incl. partial frontier pages and coarse ancestors) at admission and
    privatize them lazily via copy-on-write on the first decode write --
    with tokens still identical to the dense engine."""
    cfg, _ = _model()
    p = (np.arange(30) * 3 % cfg.vocab_size).astype(np.int32)
    wl = [(p.copy(), 4) for _ in range(3)]
    _, ref = _run(wl, slots=3)
    eng, out = _run(wl, slots=3, paged=True, pool_pages=24)
    assert out == ref
    assert eng.pool.stats.shared_maps > 0
    assert eng.pool.stats.cow_copies > 0        # divergent writes COW'd
    assert eng.pool.stats.copy_launches > 0     # one per flush


def test_eviction_under_pool_pressure():
    """A pool far smaller than slots*Lmax forces the prefix registry's
    evictable pages to be reclaimed; token streams must not change."""
    cfg, _ = _model()
    wl = _workload(5, 8, cfg)
    ref = _dense_ref(5, 8)
    eng, out = _run(wl, slots=3, paged=True, pool_pages=10)
    assert out == ref
    assert eng.pool.stats.evictions > 0


def test_preemption_swap_restores_bit_exact_tokens():
    """Pool exhaustion mid-decode preempts the newest request; swap mode
    snapshots its pages and restores them bit-exact, so greedy tokens
    stay IDENTICAL to the never-preempted dense run."""
    cfg, _ = _model()
    wl = _workload(7, 10, cfg)
    ref = _dense_ref(7, 10)
    with _pool_check():
        eng, out = _run(wl, slots=4, paged=True, pool_pages=8,
                        lookahead=4)
    assert eng.preemptions > 0, "schedule no longer exercises preemption"
    assert out == ref


def test_preemption_recompute_resumes_consistently():
    """Recompute mode re-prefills prompt+generated on resume; lengths
    and the pre-preemption token prefix must be preserved even though
    the recomputed cache only matches to ~1e-6 (greedy continuations may
    legitimately drift at argmax near-ties, so only structure is
    asserted here -- bit-parity is swap mode's job)."""
    cfg, _ = _model()
    wl = _workload(7, 10, cfg)
    ref = _dense_ref(7, 10)
    eng, out = _run(wl, slots=4, paged=True, pool_pages=8, lookahead=4,
                    preempt_mode="recompute")
    assert eng.preemptions > 0
    for got, want, (_, m) in zip(out, ref, wl):
        assert len(got) == len(want) == m


@pytest.mark.parametrize("seed", [
    pytest.param(11, marks=pytest.mark.slow), 23])
def test_randomized_admission_eviction_preemption_schedule(seed):
    """Randomized workloads over randomized engine shapes: admission
    order, eviction and preemption schedules all differ from the dense
    run, greedy tokens must not."""
    cfg, _ = _model()
    rng = np.random.default_rng(seed)
    wl = _workload(seed, 8, cfg)
    ref = _dense_ref(seed, 8)
    kw = dict(slots=int(rng.integers(2, 6)),
              pool_pages=int(rng.integers(8, 16)),
              lookahead=int(rng.integers(0, 6)))
    with _pool_check():
        eng, out = _run(wl, paged=True, **kw)
    assert out == ref, kw


@given(st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=5, deadline=None)
@pytest.mark.slow
def test_property_random_schedules_match_dense(seed):
    """Property form of the schedule-parity invariant (hypothesis when
    installed): any pool size / lookahead / budget combination yields
    the dense engine's exact greedy streams."""
    cfg, _ = _model()
    rng = np.random.default_rng(seed)
    wl = _workload(seed % 97, 6, cfg)
    _, ref = _run(wl, slots=2)
    kw = dict(slots=int(rng.integers(2, 5)),
              pool_pages=int(rng.integers(7, 20)),
              lookahead=int(rng.integers(0, 5)),
              token_budget=int(rng.integers(16, 64)))
    with _pool_check():
        _, out = _run(wl, paged=True, **kw)
    assert out == ref, kw


def test_chunked_prefill_interleaves_and_matches():
    """prefill_chunk admits long prompts on a short chunk and streams
    the tail through the decode ticks; outputs must equal the dense
    whole-prompt prefill path."""
    cfg, _ = _model()
    wl = _workload(13, 6, cfg)
    ref = _dense_ref(13, 6)
    eng, out = _run(wl, slots=3, paged=True, pool_pages=16,
                    prefill_chunk=6, token_budget=24)
    assert out == ref


def test_reconstruction_bit_exact_against_dense_engine():
    """Mid-flight, every paged slot's MAPPED pages must reconstruct the
    EXACT dense cache rows for their blocks (prompt pages, shared pages,
    decode-written pages, zero-init decode pages) -- run both engines in
    lockstep and compare bit-for-bit.  Only mapped blocks are compared:
    the dense engine's bucketed prefill also writes PAD-token K/V rows
    beyond the prompt, which position masks hide from every attend and
    which the paged engine therefore never allocates at all."""
    cfg, params = _model()
    wl = _workload(17, 2, cfg)
    d = ServeEngine(cfg, params, slots=2, max_len=64)
    g = ServeEngine(cfg, params, slots=2, max_len=64, paged=True,
                    pool_pages=32)
    reqs_d = [Request(uid=i, prompt=p.copy(), max_new_tokens=m)
              for i, (p, m) in enumerate(wl)]
    reqs_g = [Request(uid=i, prompt=p.copy(), max_new_tokens=m)
              for i, (p, m) in enumerate(wl)]
    for rd, rg in zip(reqs_d, reqs_g):
        d.submit(rd)
        g.submit(rg)
    hkv = cfg.num_kv_heads
    nr = cfg.nr
    compared = 0
    for _ in range(4):
        d.step()
        g.step()
        for s in range(2):
            if not g.active[s]:
                continue
            rec = pc.gather_slot_cache(g.caches, g.pool, s, hkv,
                                       g._stacked)
            rows = slice(s * hkv, (s + 1) * hkv)
            lvls = [(rec.k, d.caches.k), (rec.v, d.caches.v)]
            lvls += [(a, b) for a, b in zip(rec.ck, d.caches.ck)]
            lvls += [(a, b) for a, b in zip(rec.cv, d.caches.cv)]
            lev_of = [0, 0] + [i + 1 for i in range(len(rec.ck))] \
                + [i + 1 for i in range(len(rec.cv))]
            for (a, b), l in zip(lvls, lev_of):
                blks = np.nonzero(g.pool.table[l][s] >= 0)[0]
                for blk in blks:
                    cols = slice(blk * nr, (blk + 1) * nr)
                    np.testing.assert_array_equal(
                        np.asarray(a[:, :, cols]),
                        np.asarray(b[:, rows, cols]),
                        err_msg=str((s, l, int(blk))))
                    compared += 1
    assert compared > 20        # the lockstep loop actually compared


# ---------------------------------------------------------------------------
# allocator / scheduler units (no model)
# ---------------------------------------------------------------------------

def test_pool_admit_is_transactional_on_exhaustion():
    """A failed admission must leave NO trace: no mapped blocks, no
    registry keys pointing at never-written pages (regression: a stale
    registration served garbage to the same prompt's retry)."""
    pool = pc.PagePool(slots=2, max_len=64, nr=8, pool_pages=4)
    toks = np.arange(40, dtype=np.int32)     # needs 5 fine pages > 4
    with pytest.raises(pc.PoolExhausted):
        pool.admit(0, toks)
    assert not pool.registry and not pool.key_of
    assert (pool.table[0][0] == -1).all()
    assert all(pool.used(l) == 0 for l in range(pool.M))
    # and the pool still serves a request that fits
    w = pool.admit(0, np.arange(16, dtype=np.int32))
    assert len(w[0]) == 2


def test_pool_refcount_sharing_and_release():
    pool = pc.PagePool(slots=3, max_len=64, nr=8, pool_pages=16)
    toks = np.arange(16, dtype=np.int32)
    pool.admit(0, toks)
    w1 = pool.admit(1, toks)
    assert w1[0] == []                       # full registry hit
    page = int(pool.table[0][0, 0])
    assert pool.table[0][1, 0] == page
    assert pool.refcount[0][page] == 2
    pool.release_slot(0)
    assert pool.refcount[0][page] == 1
    pool.release_slot(1)
    # registered pages park on the evictable LRU, not the free list
    assert (0, page) in pool.evictable
    assert pool.available(0) == pool.usable(0)


def test_pool_cow_on_first_divergent_write():
    pool = pc.PagePool(slots=2, max_len=64, nr=8, pool_pages=16)
    toks = np.arange(12, dtype=np.int32)     # partial page 1 (8..12)
    pool.admit(0, toks)
    pool.admit(1, toks)
    shared = int(pool.table[0][0, 1])
    assert pool.table[0][1, 1] == shared and pool.refcount[0][shared] == 2
    copies = {}
    pool.prepare_tick(0, 12, copies)         # slot 0 writes position 12
    assert int(pool.table[0][0, 1]) != shared     # COW'd away
    assert pool.refcount[0][shared] == 1          # slot 1 keeps original
    assert any(src == shared for src, _ in copies.get(0, []))


def test_scheduler_token_budget_and_lookahead():
    def entry(n, uid):
        return QueueEntry(req=uid, prompt=np.arange(n, dtype=np.int32))

    bucket = lambda s: 1 << max(s - 1, 0).bit_length()
    # legacy semantics: unlimited budget groups consecutive same-bucket
    sched = ContinuousBatchingScheduler()
    groups, rest = sched.plan([entry(5, 0), entry(6, 1), entry(20, 2)],
                              free_slots=4, n_active=0,
                              bucket_len=bucket, can_admit=lambda e: True)
    assert [[e.req for e in g.entries] for g in groups] == [[0, 1], [2]]
    assert not rest
    # budget: 10 tokens admits only the head (5), not 5+6
    sched = ContinuousBatchingScheduler(token_budget=10)
    groups, rest = sched.plan([entry(5, 0), entry(6, 1)], 4, 0,
                              bucket, lambda e: True)
    assert [[e.req for e in g.entries] for g in groups] == [[0]]
    assert [e.req for e in rest] == [1]
    # lookahead: an infeasible head is skipped within the window
    sched = ContinuousBatchingScheduler(lookahead=2)
    groups, rest = sched.plan([entry(30, 0), entry(5, 1)], 1, 1,
                              bucket, lambda e: len(e.prompt) < 10)
    assert [[e.req for e in g.entries] for g in groups] == [[1]]
    assert [e.req for e in rest] == [0]
    # anti-starvation: an idle engine admits its first pick even over
    # budget
    sched = ContinuousBatchingScheduler(token_budget=4)
    groups, _ = sched.plan([entry(30, 0)], 1, 0, bucket, lambda e: True)
    assert [[e.req for e in g.entries] for g in groups] == [[0]]
    # chunking caps the admitted chunk
    sched = ContinuousBatchingScheduler(prefill_chunk=8)
    groups, _ = sched.plan([entry(30, 0)], 1, 0, bucket, lambda e: True)
    assert len(groups[0].chunks[0]) == 8


def test_paged_engine_gating():
    cfg, params = _model()
    import dataclasses
    with pytest.raises(ValueError, match="uniform h1d"):
        ServeEngine(dataclasses.replace(cfg, sliding_window=16), params,
                    slots=1, max_len=64, paged=True)
    ssm = get_smoke_config("mamba2-1.3b")
    sp, _ = get_model(ssm).init(jax.random.PRNGKey(1), ssm)
    with pytest.raises(ValueError, match="uniform h1d"):
        ServeEngine(ssm, sp, slots=1, max_len=64, paged=True)


def test_pool_too_small_for_one_request_raises():
    cfg, params = _model()
    eng = ServeEngine(cfg, params, slots=1, max_len=64, paged=True,
                      pool_pages=2)
    eng.submit(Request(uid=0, prompt=np.arange(10, dtype=np.int32),
                       max_new_tokens=40))
    with pytest.raises(RuntimeError, match="pool"):
        eng.run()


# ---------------------------------------------------------------------------
# int8 quantized pool (cache_dtype='int8')
# ---------------------------------------------------------------------------

def _match_rate(out, ref):
    """Positional greedy token-match rate across all requests."""
    tot = sum(len(w) for w in ref)
    hit = sum(1 for a, b in zip(out, ref)
              for x, y in zip(a, b) if x == y)
    assert all(len(a) == len(b) for a, b in zip(out, ref))
    return hit / tot


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_int8_paged_token_match_rate(impl):
    """int8 paged engine vs the fp32 dense oracle on the standard mixed
    workload: greedy token-match rate >= 0.99 (in practice 1.0 on the
    smoke model -- int8 per-row dequant error rarely flips an argmax)."""
    cfg, _ = _model()
    wl = _workload(3, 6, cfg)
    ref = _dense_ref(3, 6)
    eng, out = _run(wl, slots=2, paged=True, cache_dtype="int8",
                    decode_impl=impl)
    assert _match_rate(out, ref) >= 0.99
    assert eng.pool.occupancy() == 0.0
    assert eng.cache_dtype == "int8"


def test_int8_schedules_eviction_sharing_cow():
    """Admission/eviction/COW schedules through the int8 pool: the same
    pressure configs the fp32 tests pin must still exercise sharing,
    COW and eviction, with token-match rate >= 0.99 vs the dense run."""
    cfg, _ = _model()
    # prefix sharing + COW (identical prompts)
    p = (np.arange(30) * 3 % cfg.vocab_size).astype(np.int32)
    wl = [(p.copy(), 4) for _ in range(3)]
    _, ref = _run(wl, slots=3)
    eng, out = _run(wl, slots=3, paged=True, pool_pages=24,
                    cache_dtype="int8")
    assert _match_rate(out, ref) >= 0.99
    assert eng.pool.stats.shared_maps > 0
    assert eng.pool.stats.cow_copies > 0
    # eviction under pool pressure
    wl = _workload(5, 8, cfg)
    ref = _dense_ref(5, 8)
    eng, out = _run(wl, slots=3, paged=True, pool_pages=10,
                    cache_dtype="int8")
    assert _match_rate(out, ref) >= 0.99
    assert eng.pool.stats.evictions > 0


def test_int8_preemption_swap_restores_bit_exact():
    """Swap-mode preemption snapshots int8 payloads WITH their per-row
    scales and restores them bit-exact: the preempted int8 run must
    produce EXACTLY the same tokens as a never-preempted int8 run (the
    int8 engine is schedule-independent, like the fp32 one)."""
    cfg, _ = _model()
    wl = _workload(7, 10, cfg)
    _, baseline = _run(wl, slots=4, paged=True, pool_pages=64,
                       cache_dtype="int8")
    eng, out = _run(wl, slots=4, paged=True, pool_pages=8, lookahead=4,
                    cache_dtype="int8")
    assert eng.preemptions > 0, "schedule no longer exercises preemption"
    assert out == baseline
    assert _match_rate(out, _dense_ref(7, 10)) >= 0.99


@given(st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=3, deadline=None)
@pytest.mark.slow
def test_property_int8_schedules_self_consistent(seed):
    """Property form for the quantized pool: ANY pool size / lookahead /
    budget combination yields the ample-pool int8 engine's exact greedy
    streams (schedule independence), and >= 0.99 of the dense fp32
    oracle's tokens."""
    cfg, _ = _model()
    rng = np.random.default_rng(seed)
    wl = _workload(seed % 97, 6, cfg)
    _, base = _run(wl, slots=2, paged=True, pool_pages=64,
                   cache_dtype="int8")
    _, ref = _run(wl, slots=2)
    kw = dict(slots=int(rng.integers(2, 5)),
              pool_pages=int(rng.integers(7, 20)),
              lookahead=int(rng.integers(0, 5)),
              token_budget=int(rng.integers(16, 64)))
    with _pool_check():
        _, out = _run(wl, paged=True, cache_dtype="int8", **kw)
    assert out == base, kw
    assert _match_rate(out, ref) >= 0.99, kw


def test_registry_keys_carry_dtype_identity():
    """Regression: prefix-registry keys must include the page's storage
    format -- the same tokens under different cache_dtype/quant_levels
    configs are different bytes and must never collide in a registry."""
    toks = np.arange(16, dtype=np.int32)
    pool_f = pc.PagePool(slots=1, max_len=64, nr=8, pool_pages=16)
    pool_q = pc.PagePool(slots=1, max_len=64, nr=8, pool_pages=16,
                         quant_levels=-1)
    pool_m = pc.PagePool(slots=1, max_len=64, nr=8, pool_pages=16,
                         quant_levels=1)      # fine int8, coarse fp32
    pool_f.admit(0, toks)
    pool_q.admit(0, toks)
    pool_m.admit(0, toks)
    kf, kq, km = (set(p.registry) for p in (pool_f, pool_q, pool_m))
    assert kf and kq and km
    assert not (kf & kq)                      # disjoint across dtypes
    # the mixed pool's fine keys match the int8 pool, coarse the fp32
    assert {k for k in km if k[0] == 0} == {k for k in kq if k[0] == 0}
    assert {k for k in km if k[0] > 0} == {k for k in kf if k[0] > 0}
    for key in pool_q.registry:
        assert key[1] == "int8:rowscale"
    for key in pool_f.registry:
        assert key[1] == "f32"


def test_int8_snapshot_restore_roundtrip_and_dtype_guard():
    """Pool-level swap snapshot of quantized pages restores payloads AND
    scales bit-exact into a fresh pool; restoring into a pool of a
    different cache_dtype raises instead of scattering garbage."""
    import jax.numpy as jnp
    from repro.core import h1d_decode as hd
    nr, Hkv, D = 8, 1, 4
    toks = np.arange(20, dtype=np.int32)

    def mk(quant_levels):
        pool = pc.PagePool(slots=1, max_len=64, nr=nr, pool_pages=8,
                           quant_levels=quant_levels)
        rows = [n * Hkv for n in pool.num_pages]
        if any(pool.quant):
            c = hd.init_quant_paged_pool(rows, nr, D, D,
                                         quant=tuple(pool.quant))
        else:
            c = hd.init_paged_pool(rows, nr, D, D)
        return pool, [c]                      # 1-layer, unstacked

    pool, caches = mk(-1)
    pool.admit(0, toks)
    key = jax.random.PRNGKey(0)
    c = caches[0]
    caches = [c._replace(
        k=jax.random.randint(key, c.k.shape, -127, 128, jnp.int8),
        v=jax.random.randint(key, c.v.shape, -127, 128, jnp.int8),
        ksc=jax.random.uniform(key, c.ksc.shape) + 0.5,
        vsc=jax.random.uniform(key, c.vsc.shape) + 0.5)]
    snap = pc.snapshot_slot(caches, pool, 0, Hkv, stacked=False)
    assert snap[0][3] is not None             # scales captured
    pool2, caches2 = mk(-1)
    caches2 = pc.restore_slot(caches2, pool2, 0, snap, Hkv,
                              stacked=False)
    for l in snap:
        src = np.nonzero(pool.table[l][0] >= 0)[0]
        dst = np.nonzero(pool2.table[l][0] >= 0)[0]
        np.testing.assert_array_equal(src, dst)
        sp = [int(pool.table[l][0, b]) for b in src]
        dp = [int(pool2.table[l][0, b]) for b in dst]
        a, b = caches[0], caches2[0]
        ak, av = (a.k, a.v) if l == 0 else (a.ck[l - 1], a.cv[l - 1])
        bk, bv = (b.k, b.v) if l == 0 else (b.ck[l - 1], b.cv[l - 1])
        asc = a.ksc if l == 0 else a.cksc[l - 1]
        bsc = b.ksc if l == 0 else b.cksc[l - 1]
        np.testing.assert_array_equal(np.asarray(ak)[sp],
                                      np.asarray(bk)[dp])
        np.testing.assert_array_equal(np.asarray(av)[sp],
                                      np.asarray(bv)[dp])
        np.testing.assert_array_equal(np.asarray(asc)[sp],
                                      np.asarray(bsc)[dp])
    pool3, caches3 = mk(0)                    # fp32 pool
    with pytest.raises(ValueError, match="dtype"):
        pc.restore_slot(caches3, pool3, 0, snap, Hkv, stacked=False)


# ---------------------------------------------------------------------------
# page copies: one donated program per flush
# ---------------------------------------------------------------------------

def _eager_apply_copies(caches, copies, Hkv, stacked):
    """The eager page copy the batched program replaced: per level one
    gather and one scatter over the expanded physical rows, last writer
    per destination kept.  The oracle for ``pc.apply_copies``."""
    idx = {}
    for l, pairs in copies.items():
        last = {d: s for s, d in pairs}
        pairs = [(s, d) for d, s in last.items()]
        src = np.concatenate([np.arange(Hkv) + s * Hkv for s, _ in pairs])
        dst = np.concatenate([np.arange(Hkv) + d * Hkv for _, d in pairs])
        idx[l] = (src, dst)

    def per_level(l, ka, va):
        if l not in idx:
            return ka, va
        src, dst = idx[l]
        if stacked:
            return (ka.at[:, dst].set(ka[:, src]),
                    va.at[:, dst].set(va[:, src]))
        return ka.at[dst].set(ka[src]), va.at[dst].set(va[src])

    return pc._map_layers(caches, stacked,
                          lambda c: pc._per_level(c, per_level, per_level))


def _random_pool_caches(key, pool, Hkv, stacked, layers=2, D=4):
    """Device caches for ``pool`` filled with random bytes (payloads and
    scales), stacked over ``layers`` or as a per-layer list."""
    import jax.numpy as jnp
    from repro.core import h1d_decode as hd
    rows = [n * Hkv for n in pool.num_pages]
    if any(pool.quant):
        one = hd.init_quant_paged_pool(rows, pool.nr, D, D,
                                       quant=tuple(pool.quant))
    else:
        one = hd.init_paged_pool(rows, pool.nr, D, D)
    lead = (layers,) if stacked else ()
    leaves, tree = jax.tree.flatten(one)
    keys = jax.random.split(key, len(leaves) * layers)

    def fill(a, k):
        shape = lead + a.shape
        if a.dtype == jnp.int8:
            return jax.random.randint(k, shape, -127, 128, jnp.int8)
        return jax.random.normal(k, shape, a.dtype)

    if stacked:
        return jax.tree.unflatten(tree, [fill(a, k) for a, k in
                                         zip(leaves, keys)])
    return [jax.tree.unflatten(tree, [fill(a, k) for a, k in zip(
        leaves, keys[i * len(leaves):(i + 1) * len(leaves)])])
            for i in range(layers)]


def _random_copies(rng, pool, n):
    """A random flush: a random subset of levels, each with 1..n
    distinct destinations, sources drawn from ZERO and live pages
    (COW), and some destinations scheduled twice (the dedup keeps the
    last)."""
    copies = {}
    levels = rng.choice(pool.M, int(rng.integers(1, pool.M + 1)),
                        replace=False)
    for l in levels:
        pages = np.arange(2, pool.num_pages[l])
        dsts = rng.choice(pages, int(rng.integers(1, min(n, len(pages))
                                                  + 1)), replace=False)
        pairs = []
        for d in dsts:
            for _ in range(int(rng.integers(1, 3))):   # repeats
                src = pc.ZERO if rng.random() < 0.5 else int(
                    rng.choice(pages))
                pairs.append((src, int(d)))
        copies[int(l)] = pairs
    return copies


_POOLS = {"fp32": 0, "int8": -1, "int8_fine": 1}


@pytest.mark.parametrize("stacked", [True, False],
                         ids=["stacked", "per_layer"])
@pytest.mark.parametrize("kind", list(_POOLS))
def test_apply_copies_matches_eager_oracle(kind, stacked):
    """The one donated copy program leaves every pool leaf (payloads
    and scales, every level, every layer) bit-identical to the eager
    per-level gather/scatter it replaced, over random flushes of ZERO
    and COW sources with repeated destinations."""
    Hkv = 2
    pool = pc.PagePool(slots=3, max_len=64, nr=8, pool_pages=16,
                       quant_levels=_POOLS[kind])
    rng = np.random.default_rng(len(kind) + stacked)
    shape = pc._copy_table_shape(
        _random_pool_caches(jax.random.PRNGKey(0), pool, Hkv, stacked),
        Hkv, stacked)
    assert shape == (pool.M, min(pool.num_pages) - 1)
    n = shape[1]
    for trial in range(3):
        caches = _random_pool_caches(jax.random.PRNGKey(trial), pool,
                                     Hkv, stacked)
        copies = _random_copies(rng, pool, n)
        want = jax.tree.map(np.asarray,
                            _eager_apply_copies(caches, copies, Hkv,
                                                stacked))
        got = pc.apply_copies(caches, copies, Hkv, stacked)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), b)


def test_apply_copies_compiles_once_and_donates():
    """Copy counts 1..slots over any mix of levels run through ONE
    compiled program per pool, and the caches handed in are consumed
    (donated) by every call."""
    Hkv = 2
    pool = pc.PagePool(slots=4, max_len=128, nr=8, pool_pages=32)
    rng = np.random.default_rng(5)
    caches = _random_pool_caches(jax.random.PRNGKey(1), pool, Hkv, True)
    pc._copy_program.clear_cache()
    for count in range(1, pool.slots + 1):
        for _ in range(2):
            levels = rng.choice(pool.M, int(rng.integers(1, pool.M + 1)),
                                replace=False)
            copies = {int(l): [(pc.ZERO, 2 + i) for i in range(count)]
                      for l in levels}
            old = jax.tree.leaves(caches)
            caches = pc.apply_copies(caches, copies, Hkv, True)
            assert all(a.is_deleted() for a in old)
    assert pc._copy_program._cache_size() == 1
    with pytest.raises(AssertionError, match="table holds"):
        n = pc._copy_table_shape(caches, Hkv, True)[1]
        pc.apply_copies(caches, {0: [(pc.ZERO, 2 + i)
                                     for i in range(n + 1)]}, Hkv, True)
