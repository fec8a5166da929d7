"""Kernel launch policy: backend detection, impl resolution, candidate
enumeration and cached autotuning for every fused-kernel launch.

Every launch decision the repo used to hand-set -- ``impl=`` strings,
``tq`` tile hints, wide-vs-deep ``sub`` layouts, decode grids -- now
resolves through one :class:`KernelPolicy` object (DESIGN.md section
10).  Resolution order, per launch:

    explicit override  >  on-disk tuning table  >  committed defaults

* **Override**: an explicit ``tq=``/``impl=`` kwarg from the caller
  bypasses tuning entirely (it is still legalized by
  :func:`resolve_tq` and validated by :func:`canonical_impl`).
* **Table**: a versioned JSON tuning table under
  ``.repro_tune/<backend>/<family>.json`` at the checkout root (git
  ignored; override the root with ``$REPRO_TUNE_CACHE``), keyed by
  shape bucket + dtype + mode and written by the measured
  :meth:`KernelPolicy.autotune_band` pass.
  Corrupt / stale / version-mismatched files fall back to the defaults
  with a ``RuntimeWarning`` -- never a crash, never silent.
* **Defaults**: a deterministic table committed with the source
  (``tuning_defaults.json``) so tier-1 CI is hermetic -- no measurement
  ever runs implicitly.

``impl='auto'`` picks the backend-appropriate implementation: the fused
Pallas kernels on TPU, the blocked-XLA program on every other backend
(on CPU it is both the gradient/decode oracle and the fast path; the
interpreted kernels remain an explicit opt-in for CI parity).  The
kernels are Mosaic (TPU) kernels: no backend but ``tpu`` resolves to
them.  Unknown impl strings raise ``ValueError`` listing :data:`IMPLS`.

Every resolution is appended to an in-process decision log
(``policy.decisions``) so tests and benchmarks can assert which config
a launch actually used; ``tuning_digest()`` hashes the defaults plus
all on-disk tables for the active backend, and rides in every
BENCH_*.json payload so committed baselines pin the tuning environment
they were measured under.

This module deliberately imports nothing from the kernel modules at
import time (they import it); measurement helpers import lazily.
"""
from __future__ import annotations

import collections
import hashlib
import json
import os
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

# canonical impl enum: the single source of truth for every ``impl=`` /
# ``attn_impl`` / ``decode_impl`` knob in the repo
IMPLS = ("auto", "jnp", "pallas", "pallas_interpret")

# kernel families with distinct launch-config search spaces.  band fwd
# and the fused dQ/dKVW backward share one tq (custom_vjp nondiff arg),
# but are enumerated separately so a future split stays cheap; the
# 'sub' families carry the wide/deep layout in their candidates; the
# decode families have a fixed one-program-per-row grid.
FAMILIES = (
    "band_fwd", "band_bwd",
    "sub_fwd", "sub_bwd",
    "decode_attend", "decode_update",
    "decode_attend_paged", "decode_update_paged",
    "decode_attend_paged_quant", "decode_update_paged_quant",
)

TABLE_VERSION = 1
_DEFAULTS_PATH = os.path.join(os.path.dirname(__file__),
                              "tuning_defaults.json")
# <checkout>/.repro_tune: a run depends only on the tree it runs from
DEFAULT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".repro_tune"))
_SUB = "sub"


def canonical_impl(impl: str) -> str:
    """Validate ``impl`` against the canonical enum.  Raises
    ``ValueError`` naming the allowed set on anything else -- unknown
    strings must never fall through to an arbitrary code path."""
    if impl not in IMPLS:
        raise ValueError(
            f"unknown impl {impl!r}: allowed impls are {IMPLS}")
    return impl


def detect_backend() -> str:
    """'tpu' | 'gpu' | 'cpu' from the active JAX default backend."""
    import jax
    b = jax.default_backend()
    if b in ("tpu", "gpu", "cuda", "rocm"):
        return "tpu" if b == "tpu" else "gpu"
    return "cpu"


def resolve_tq(L: int, nr: int, tq: int, mode: str, ratio: int = 1) -> int:
    """Largest kernel query-tile size <= the ``tq`` hint that is valid
    for (L, nr, mode).

    Symmetric modes need ``tq % nr == 0 and L % tq == 0``; ``sub``
    additionally needs the tile to align with the ``nq = nr * ratio``
    query blocks (``tq % nq == 0 or nq % tq == 0``), which the
    power-of-two hierarchy shapes always admit.  Raises on shapes no
    tile can cover (L not a multiple of nr), naming the caller's
    mode/ratio so multi-level traces stay debuggable.
    """
    if L % nr:
        raise ValueError(
            f"band_attention[mode={mode}, ratio={ratio}]: L={L} is not a "
            f"multiple of nr={nr}; no kernel tiling exists (pad the "
            f"sequence first)")
    cap = min(tq, L)
    if cap < nr:
        raise ValueError(
            f"band_attention[mode={mode}, ratio={ratio}]: tq hint {tq} < "
            f"nr={nr} cannot tile L={L}")
    if mode == _SUB:
        # hierarchy shapes: L = nr * 2**M -- any nr * 2**j <= cap divides
        # L and is compatible with the nq = nr * 2**l query blocks.
        t = nr
        while t * 2 <= cap and L % (t * 2) == 0:
            t *= 2
        return t
    for t in range((cap // nr) * nr, nr - 1, -nr):
        if L % t == 0:
            return t
    raise ValueError(
        f"band_attention[mode={mode}, ratio={ratio}]: no tile divides "
        f"L={L} (nr={nr})")


def shape_bucket(L: int) -> int:
    """Sequence lengths bucket to the next power of two: tuning entries
    generalize across nearby L without per-length re-measurement."""
    b = 1
    while b < L:
        b *= 2
    return b


def table_key(L: int, nr: int, mode: str, ratio: int = 1,
              dtype: str = "float32") -> str:
    return f"L{shape_bucket(L)}_nr{nr}_{mode}_r{ratio}_{dtype}"


def _load_defaults(path: Optional[str] = None) -> Dict[str, Any]:
    try:
        with open(path or _DEFAULTS_PATH) as f:
            return json.load(f)
    except (OSError, ValueError) as e:  # pragma: no cover - repo file
        warnings.warn(f"repro_tune: committed defaults unreadable "
                      f"({e}); using built-in fallbacks", RuntimeWarning)
        return {"version": TABLE_VERSION, "tables": {}}


class KernelPolicy:
    """One launch-policy object per process (see :func:`get_policy`).

    Owns backend detection, ``impl='auto'`` resolution, per-family
    candidate enumeration, the override > table > default resolution
    order, the measured autotune pass and its persisted tables, and the
    decision log that makes each of those choices assertable.
    """

    def __init__(self, backend: Optional[str] = None,
                 cache_dir: Optional[str] = None,
                 defaults_path: Optional[str] = None):
        self.backend = backend or detect_backend()
        env_dir = os.environ.get("REPRO_TUNE_CACHE")
        if env_dir is not None and ("\0" in env_dir
                                    or not env_dir.strip()):
            # a malformed override must not crash mid-autotune: every
            # later filesystem call would raise ValueError on the NUL
            # (or scatter tables into a '' relative path)
            warnings.warn(
                f"repro_tune: REPRO_TUNE_CACHE={env_dir!r} is not a "
                f"usable path; using the default cache dir",
                RuntimeWarning)
            env_dir = None
        self.cache_dir = os.path.normpath(
            cache_dir or env_dir or DEFAULT_CACHE_DIR)
        self.defaults = _load_defaults(defaults_path)
        self._tables: Dict[str, Dict[str, Any]] = {}
        self._memo: Dict[Tuple[str, str], Tuple[Dict[str, Any], str]] = {}
        self.decisions: collections.deque = collections.deque(maxlen=512)

    # -- impl resolution ----------------------------------------------------

    def resolve_impl(self, impl: str, family: str = "band") -> str:
        """Canonicalize ``impl`` and resolve ``'auto'`` to the backend
        default: fused Pallas kernels on TPU, blocked XLA elsewhere
        (the oracle path, which doubles as the fast CPU path)."""
        impl = canonical_impl(impl)
        if impl != "auto":
            return impl
        resolved = "pallas" if self.backend == "tpu" else "jnp"
        self._log(family, f"impl@{self.backend}", "auto",
                  {"impl": resolved})
        return resolved

    def kernel_impl(self) -> str:
        """The impl that exercises the fused kernel *bodies* on this
        backend (what the autotuner measures): compiled on TPU,
        interpreted elsewhere."""
        return "pallas" if self.backend == "tpu" else "pallas_interpret"

    # -- candidate enumeration ----------------------------------------------

    def candidates(self, family: str, *, L: int, nr: int,
                   mode: str = "l0_bidir", ratio: int = 1,
                   rows: Optional[int] = None,
                   max_tq: int = 512,
                   d: Optional[int] = None, dv: Optional[int] = None,
                   B: int = 1, G: int = 1, dtype: str = "float32",
                   vmem_budget: Optional[int] = None
                   ) -> List[Dict[str, Any]]:
        """Legal launch configs for one kernel family at one shape.

        Band/sub families enumerate power-of-two ``tq`` multiples of
        ``nr`` that divide L (the grid is ``L/tq`` query tiles); sub
        candidates carry the wide/deep layout implied by ``tq`` vs the
        ``nq = nr * ratio`` query block.  Decode families launch one
        program per cache row -- the grid is fixed by the batch, so the
        config space is the single ``(rows,)`` grid.

        With a head dim ``d``, each band/sub candidate is additionally
        sized against the static VMEM budget
        (``repro.analysis.vmem``): over-budget configs are dropped
        before any measurement and logged as ``rejected:vmem``;
        survivors carry their ``vmem_bytes`` estimate.
        """
        if family not in FAMILIES:
            raise ValueError(f"unknown kernel family {family!r}: "
                             f"allowed families are {FAMILIES}")
        if family.startswith("decode"):
            return [{"grid": (int(rows),) if rows is not None else "rows"}]
        out: List[Dict[str, Any]] = []
        nq = nr * ratio
        t = nr
        while t <= min(L, max_tq):
            if L % t == 0:
                if mode == _SUB:
                    out.append({"tq": t,
                                "layout": "wide" if nq <= t else "deep"})
                else:
                    out.append({"tq": t, "layout": "band"})
            t *= 2
        if d is None:
            return out
        from repro.analysis import vmem as vmem_mod
        budget = (vmem_mod.default_budget() if vmem_budget is None
                  else int(vmem_budget))
        key = table_key(L, nr, mode, ratio, dtype)
        kept: List[Dict[str, Any]] = []
        for cand in out:
            nbytes = vmem_mod.band_launch_bytes(
                family, L=L, nr=nr, mode=mode, ratio=ratio,
                tq=cand["tq"], d=d, dv=dv, B=B, G=G, dtype=dtype)
            if nbytes > budget:
                self._log(family, key, "rejected:vmem",
                          dict(cand, vmem_bytes=int(nbytes),
                               budget=int(budget),
                               reason=f"vmem {int(nbytes)} > "
                                      f"budget {int(budget)}"))
            else:
                kept.append(dict(cand, vmem_bytes=int(nbytes)))
        return kept

    # -- resolution: override > table > default ------------------------------

    def band_tq(self, *, L: int, nr: int, mode: str, ratio: int = 1,
                dtype: str = "float32", override: Optional[int] = None,
                family: Optional[str] = None) -> int:
        """The ``tq`` hint for one band launch.  An explicit caller
        ``override`` bypasses tuning (logged as such); otherwise the
        on-disk table entry for this shape bucket wins, then the
        committed defaults.  The caller still legalizes the hint via
        :func:`resolve_tq`."""
        if family is None:
            family = "sub_fwd" if mode == _SUB else "band_fwd"
        key = table_key(L, nr, mode, ratio, dtype)
        if override is not None:
            self._log(family, key, "override", {"tq": int(override)})
            return int(override)
        mk = (family, key)
        if mk in self._memo:
            cfg, src = self._memo[mk]
            self._log(family, key, src, cfg)
            return int(cfg["tq"])
        entries = self._entries(family)
        if key in entries and "tq" in entries[key]:
            cfg, src = {"tq": int(entries[key]["tq"])}, "table"
        else:
            cfg, src = {"tq": self._default_tq(family, mode)}, "default"
        self._memo[mk] = (cfg, src)
        self._log(family, key, src, cfg)
        return int(cfg["tq"])

    def note_launch(self, family: str, **config) -> None:
        """Record a launch whose config space is trivial (the decode
        kernels' one-program-per-row grid) so the decision log covers
        every kernel family, not just the tiled ones."""
        self._log(family, "grid", "default",
                  dict(config, grid=config.get("grid", "rows")))

    def _default_tq(self, family: str, mode: str) -> int:
        fam = self.defaults.get("tables", {}).get(family, {})
        ent = fam.get(f"mode:{mode}", fam.get("default", {}))
        return int(ent.get("tq", 128))

    # -- on-disk tables -----------------------------------------------------

    def _table_path(self, family: str) -> str:
        return os.path.join(self.cache_dir, self.backend, f"{family}.json")

    def _entries(self, family: str) -> Dict[str, Any]:
        if family in self._tables:
            return self._tables[family]
        path = self._table_path(family)
        entries: Dict[str, Any] = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    table = json.load(f)
                if not isinstance(table, dict):
                    raise ValueError("not a JSON object")
                if table.get("version") != TABLE_VERSION:
                    warnings.warn(
                        f"repro_tune: tuning table {path} has version "
                        f"{table.get('version')!r} != {TABLE_VERSION}; "
                        f"ignoring it (falling back to defaults)",
                        RuntimeWarning)
                elif table.get("backend") not in (None, self.backend):
                    warnings.warn(
                        f"repro_tune: tuning table {path} was measured on "
                        f"backend {table.get('backend')!r}, not "
                        f"{self.backend!r}; ignoring it (falling back to "
                        f"defaults)", RuntimeWarning)
                else:
                    entries = dict(table.get("entries", {}))
            except (OSError, ValueError) as e:
                warnings.warn(
                    f"repro_tune: corrupt tuning table {path} ({e}); "
                    f"falling back to defaults", RuntimeWarning)
        self._tables[family] = entries
        return entries

    def _save_table(self, family: str) -> Optional[str]:
        """Persist one family's tuning table.  An unwritable cache dir
        (read-only $REPRO_TUNE_CACHE, container filesystems) degrades
        to in-memory tables with a ``RuntimeWarning`` -- the autotune
        sweep keeps its measured entries for this process instead of
        aborting mid-sweep."""
        path = self._table_path(family)
        payload = {"version": TABLE_VERSION, "backend": self.backend,
                   "kernel": family,
                   "entries": self._tables.get(family, {})}
        tmp = path + ".tmp"
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except (OSError, ValueError) as e:
            # ValueError: embedded NUL from a cache_dir passed directly
            # to the constructor (the env override is sanitized there)
            warnings.warn(
                f"repro_tune: cannot persist tuning table {path} ({e}); "
                f"keeping measured entries in memory only", RuntimeWarning)
            return None
        return path

    # -- measured autotune pass ---------------------------------------------

    def autotune_band(self, *, L: int, nr: int, mode: str, ratio: int = 1,
                      d: int = 64, B: int = 1, G: int = 1,
                      impl: Optional[str] = None, iters: int = 2,
                      warmup: int = 1,
                      family: Optional[str] = None,
                      vmem_budget: Optional[int] = None) -> Dict[str, Any]:
        """Measure every legal candidate config for one band family at
        one shape bucket, persist the winner to the on-disk table, and
        return the entry.  A table hit returns without re-measuring
        (that is the point of the cache); autotuning never runs
        implicitly -- callers opt in.  Candidates whose static VMEM
        estimate exceeds the budget are rejected before measurement
        (``rejected:vmem`` in the decision log).
        """
        if family is None:
            family = "sub_fwd" if mode == _SUB else "band_fwd"
        key = table_key(L, nr, mode, ratio)
        entries = self._entries(family)
        if key in entries:
            cfg = {"tq": int(entries[key]["tq"])}
            self._memo[(family, key)] = (cfg, "table")
            self._log(family, key, "table", cfg)
            return dict(entries[key])
        impl = self.kernel_impl() if impl is None else \
            self.resolve_impl(impl, family)
        best: Optional[Tuple[Dict[str, Any], float]] = None
        for cand in self.candidates(family, L=L, nr=nr, mode=mode,
                                    ratio=ratio, d=d, B=B, G=G,
                                    vmem_budget=vmem_budget):
            fn = self._band_runner(cand["tq"], L=L, nr=nr, mode=mode,
                                   ratio=ratio, d=d, B=B, G=G, impl=impl,
                                   grad=family.endswith("bwd"))
            us = self._measure(fn, iters=iters, warmup=warmup)
            if best is None or us < best[1]:
                best = (cand, us)
        assert best is not None, (
            f"no measurable candidates for {family} {key} (all rejected? "
            f"see rejected:vmem decision-log entries)")
        entry = dict(best[0], us=round(best[1], 1), impl=impl,
                     source="measured")
        entries[key] = entry
        self._save_table(family)
        cfg = {"tq": int(entry["tq"])}
        self._memo[(family, key)] = (cfg, "measured")
        self._log(family, key, "measured", cfg)
        return dict(entry)

    def _band_runner(self, tq: int, *, L, nr, mode, ratio, d, B, G, impl,
                     grad: bool):
        import jax
        import jax.numpy as jnp
        from repro.kernels import ops

        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        Lk = L // ratio if mode == _SUB else L
        q = jax.random.normal(ks[0], (B, G, L, d))
        k = jax.random.normal(ks[1], (B, Lk, d))
        v = jax.random.normal(ks[2], (B, Lk, d))
        w = jnp.ones((B, Lk))

        def call(q, k, v, w):
            y, dn, m = ops.band_attention(q, k, v, w, nr=nr, mode=mode,
                                          ratio=ratio, impl=impl, tq=tq)
            return jnp.sum(y) + jnp.sum(dn) + jnp.sum(m)

        fn = jax.jit(jax.grad(call, argnums=(0, 1, 2))) if grad \
            else jax.jit(call)
        return lambda: fn(q, k, v, w)

    def _measure(self, fn, iters: int = 2, warmup: int = 1) -> float:
        """Median-free simple wall-clock: mean microseconds per call
        after ``warmup`` compile/warm calls.  Separated out so tests can
        count (or stub) measurements."""
        import jax
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(max(iters, 1)):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / max(iters, 1) * 1e6

    # -- observability ------------------------------------------------------

    def _log(self, family: str, key: str, source: str,
             config: Dict[str, Any]) -> None:
        self.decisions.append({"family": family, "key": key,
                               "source": source, "config": dict(config)})

    def tuning_digest(self) -> str:
        """Stable 12-hex digest over the committed defaults plus every
        readable on-disk table for the active backend.  BENCH_*.json
        payloads carry it so a baseline regenerated under different
        tuning state is visible in the diff."""
        tables: Dict[str, Any] = {}
        bdir = os.path.join(self.cache_dir, self.backend)
        if os.path.isdir(bdir):
            for f in sorted(os.listdir(bdir)):
                if f.endswith(".json"):
                    tables[f[:-5]] = self._entries(f[:-5])
        blob = {"version": TABLE_VERSION, "backend": self.backend,
                "defaults": self.defaults, "tables": tables}
        return hashlib.sha1(
            json.dumps(blob, sort_keys=True).encode()).hexdigest()[:12]


_POLICY: Optional[KernelPolicy] = None


def get_policy() -> KernelPolicy:
    """The process-wide launch policy (constructed on first use)."""
    global _POLICY
    if _POLICY is None:
        _POLICY = KernelPolicy()
    return _POLICY


def set_policy(policy: Optional[KernelPolicy]) -> Optional[KernelPolicy]:
    """Swap the process policy (tests, benchmarks).  Returns the
    previous one so callers can restore it."""
    global _POLICY
    prev, _POLICY = _POLICY, policy
    return prev


def _main(argv=None):  # pragma: no cover - CLI smoke (scripts/ci.sh)
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--autotune-smoke", action="store_true",
                    help="measured autotune round-trip on a tiny shape "
                         "(respects $REPRO_TUNE_CACHE)")
    ap.add_argument("--assert-cached", action="store_true",
                    help="assert a prior --autotune-smoke's table is "
                         "applied WITHOUT measuring (cross-process "
                         "round-trip; pair with the same "
                         "$REPRO_TUNE_CACHE)")
    ap.add_argument("--L", type=int, default=64)
    ap.add_argument("--nr", type=int, default=16)
    args = ap.parse_args(argv)
    p = KernelPolicy()
    print(f"backend={p.backend} cache_dir={p.cache_dir}")
    if args.assert_cached:
        # a fresh process over the same cache dir: the table must win
        # and no measurement may run
        p._measure = None  # any measurement attempt would TypeError
        tq = p.band_tq(L=args.L, nr=args.nr, mode="l0_causal")
        src = p.decisions[-1]["source"]
        assert src == "table", (src, list(p.decisions))
        print(f"cross-process round-trip OK: tq={tq} source={src}")
    if args.autotune_smoke:
        for family, mode, ratio in (("band_fwd", "l0_causal", 1),
                                    ("sub_fwd", "sub", 2)):
            e = p.autotune_band(L=args.L, nr=args.nr, mode=mode,
                                ratio=ratio, d=16)
            print(f"{family} {mode} r{ratio}: {e}")
        # reload in a fresh policy: the measured entry must win
        p2 = KernelPolicy(cache_dir=p.cache_dir)
        tq = p2.band_tq(L=args.L, nr=args.nr, mode="l0_causal")
        src = p2.decisions[-1]["source"]
        assert src == "table", (src, list(p2.decisions))
        print(f"round-trip OK: tq={tq} source={src}")
    print(f"tuning_digest={p.tuning_digest()}")


if __name__ == "__main__":  # pragma: no cover
    _main()
