"""Launch-contract subsystem: capture/recording, the static checker on
clean contracts, the seeded-mutation suite (an injected off-by-one index
map, double-written output block, out-of-range prefetch index, and alias
dtype mismatch must each be flagged), the checker-vs-runtime agreement
shim, and static VMEM rejection in the autotune candidate path."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.analysis import check, checker, vmem
from repro.analysis.contracts import (LaunchContract, Operand, capture,
                                      recent)
from repro.kernels import h1d_block, h1d_block_bwd, tuning
from repro.kernels.tuning import KernelPolicy, set_policy

F32 = "float32"


@pytest.fixture
def fresh_policy(tmp_path):
    p = KernelPolicy(cache_dir=str(tmp_path))
    prev = set_policy(p)
    yield p
    set_policy(prev)


def _band_shapes(L=256, d=16, ratio=1, B=1, G=2):
    Lk = L // ratio
    q = jax.ShapeDtypeStruct((B, G, L, d), F32)
    k = jax.ShapeDtypeStruct((B, Lk, d), F32)
    v = jax.ShapeDtypeStruct((B, Lk, d), F32)
    w = jax.ShapeDtypeStruct((B, Lk), F32)
    return q, k, v, w


@pytest.fixture(scope="module")
def band_c():
    """One clean band_fwd contract: L=256, nr=16, tq=64 -> grid (1,2,4)."""
    q, k, v, w = _band_shapes()
    with capture() as got:
        jax.eval_shape(lambda *a: h1d_block.band_attention_fwd(
            *a, nr=16, mode="l0_causal", tq=64), q, k, v, w)
    (c,) = got
    return c


@pytest.fixture(scope="module")
def decode_cs():
    """Every decode family's contracts at the checker CLI's geometry."""
    return check.decode_contracts(nr=4, d=8)


def _first(labeled, family):
    for _, c in labeled:
        if c.family == family:
            return c
    raise AssertionError(f"no {family} contract captured")


# ---------------------------------------------------------------------------
# capture + recording
# ---------------------------------------------------------------------------

def test_capture_records_launch(band_c):
    assert band_c.family == "band_fwd"
    assert band_c.grid == (1, 2, 4)
    assert [o.name for o in band_c.outputs] == ["y", "dn", "m"]
    assert band_c.inputs[0].name == "q"
    assert band_c.inputs[0].block == (1, 1, 64, 16)
    assert band_c.meta["mode"] == "l0_causal"
    assert band_c in recent("band_fwd")


# ---------------------------------------------------------------------------
# clean contracts pass
# ---------------------------------------------------------------------------

def test_band_contract_clean(band_c):
    assert checker.check_contract(band_c) == []


def test_all_decode_families_clean(decode_cs):
    fams = {c.family for _, c in decode_cs}
    assert {"decode_attend", "decode_update", "decode_attend_partial",
            "decode_update_partial", "decode_attend_paged",
            "decode_update_paged", "decode_attend_paged_quant",
            "decode_update_paged_quant"} <= fams
    for label, c in decode_cs:
        vs = checker.check_contract(c)
        assert vs == [], f"{label}: {[str(v) for v in vs]}"


def test_check_cli_main_passes():
    # tiny geometry so the in-test CLI run stays fast; the full default
    # sweep runs in scripts/ci.sh
    assert check.main(["--nr", "4", "--d", "8", "--samples", "1"]) == 0


# ---------------------------------------------------------------------------
# seeded-mutation suite: the checker must flag each injected defect
# ---------------------------------------------------------------------------

def _replace_input(c, i, **fields):
    ins = list(c.inputs)
    ins[i] = dataclasses.replace(ins[i], **fields)
    return dataclasses.replace(c, inputs=tuple(ins))


def _replace_output(c, o, **fields):
    outs = list(c.outputs)
    outs[o] = dataclasses.replace(outs[o], **fields)
    return dataclasses.replace(c, outputs=tuple(outs))


def test_mutation_off_by_one_index_map(band_c):
    """+1 on the q tile component walks past the last tile -> oob."""
    orig = band_c.inputs[0].index_map
    mut = _replace_input(
        band_c, 0,
        index_map=lambda b, g, i: (lambda t: t[:2] + (t[2] + 1, t[3]))(
            orig(b, g, i)))
    vs = checker.check_contract(mut)
    assert any(v.kind == "oob" and v.operand == "q" for v in vs), \
        [str(v) for v in vs]


def test_mutation_double_written_output(band_c):
    """Folding the y map onto half the tiles revisits blocks at
    non-contiguous grid steps AND leaves blocks unwritten."""
    mut = _replace_output(band_c, 0,
                          index_map=lambda b, g, i: (b, g, i % 2, 0))
    kinds = {v.kind for v in checker.check_contract(mut)}
    assert "double-write" in kinds, kinds
    assert "coverage-gap" in kinds, kinds


def test_mutation_out_of_range_prefetch(decode_cs):
    """Raising the page-table domain one past the pool's page count must
    surface as scalar-oob (a prefetch index outside the pool)."""
    c = _first(decode_cs, "decode_attend_paged")
    s = c.scalars[1]
    assert s.name == "bidx"
    mut = dataclasses.replace(
        c, scalars=(c.scalars[0],
                    dataclasses.replace(s, hi=np.asarray(s.hi) + 1)))
    vs = checker.check_contract(mut)
    assert any(v.kind == "scalar-oob" for v in vs), [str(v) for v in vs]


def test_mutation_alias_dtype_mismatch(decode_cs):
    """An aliased input whose dtype disagrees with its output must be
    flagged -- the in-place update would reinterpret the buffer."""
    c = _first(decode_cs, "decode_update_paged")
    assert c.aliases, "update_cache_paged must alias its pool operands"
    i, _ = c.aliases[0]
    mut = _replace_input(c, i, dtype="int8")
    vs = checker.check_contract(mut)
    assert any(v.kind == "alias-mismatch" for v in vs), [str(v) for v in vs]
    assert checker.summarize(vs)["by_kind"]["alias-mismatch"] >= 1


# ---------------------------------------------------------------------------
# checker-vs-runtime agreement: the contract IS what pallas_call gets
# ---------------------------------------------------------------------------

def test_contracts_agree_with_pallas_call(monkeypatch):
    """Shim ``pl`` inside the contracts module to record every live
    ``pallas_call``'s kwargs, trace one concrete shape per family, and
    assert the captured contract matches the call: grid, scalar-prefetch
    count, the very same BlockSpec index maps, block/array shapes, and
    the scalar-shifted alias dict."""
    from repro.analysis import contracts as C

    real_pl = C.pl
    recorded = []

    class _Shim:
        def __getattr__(self, name):
            return getattr(real_pl, name)

        def pallas_call(self, kernel, **kw):
            recorded.append(kw)
            return real_pl.pallas_call(kernel, **kw)

    monkeypatch.setattr(C, "pl", _Shim())

    q, k, v, w = _band_shapes(L=128)
    y = jax.ShapeDtypeStruct(q.shape, F32)
    r = jax.ShapeDtypeStruct(q.shape[:3], F32)
    qs, ks, vs, ws = _band_shapes(L=128, ratio=2)
    ys = jax.ShapeDtypeStruct(qs.shape, F32)
    rs = jax.ShapeDtypeStruct(qs.shape[:3], F32)
    with capture() as got:
        jax.eval_shape(lambda *a: h1d_block.band_attention_fwd(
            *a, nr=16, mode="l0_bidir", tq=64), q, k, v, w)
        jax.eval_shape(lambda *a: h1d_block_bwd.band_attention_bwd(
            *a, nr=16, mode="l0_bidir", tq=64),
            q, k, v, w, y, r, r, y, r, r)
        jax.eval_shape(lambda *a: h1d_block.band_attention_fwd(
            *a, nr=16, mode="sub", ratio=2, tq=64), qs, ks, vs, ws)
        jax.eval_shape(lambda *a: h1d_block_bwd.band_attention_bwd(
            *a, nr=16, mode="sub", ratio=2, tq=64),
            qs, ks, vs, ws, ys, rs, rs, ys, rs, rs)
        check.decode_contracts(nr=4, d=8)

    fams = {c.family for c in got}
    assert {"band_fwd", "band_bwd", "sub_fwd", "sub_bwd",
            "decode_attend", "decode_update", "decode_attend_partial",
            "decode_update_partial", "decode_attend_paged",
            "decode_update_paged", "decode_attend_paged_quant",
            "decode_update_paged_quant"} <= fams
    assert len(recorded) == len(got)

    for kw, c in zip(recorded, got):
        if "grid_spec" in kw:
            gs = kw["grid_spec"]
            assert tuple(gs.grid) == c.grid, c.family
            assert gs.num_scalar_prefetch == len(c.scalars), c.family
            in_specs, out_specs = list(gs.in_specs), gs.out_specs
        else:
            assert tuple(kw["grid"]) == c.grid, c.family
            assert not c.scalars, c.family
            in_specs, out_specs = list(kw["in_specs"]), kw["out_specs"]
        if not isinstance(out_specs, (list, tuple)):
            out_specs = [out_specs]
        out_shape = kw["out_shape"]
        if not isinstance(out_shape, (list, tuple)):
            out_shape = [out_shape]
        assert len(in_specs) == len(c.inputs), c.family
        for spec, op in zip(in_specs, c.inputs):
            assert tuple(spec.block_shape) == op.block, c.family
            assert spec.index_map is op.index_map, c.family
        assert len(out_specs) == len(c.outputs) == len(out_shape), c.family
        for spec, sh, op in zip(out_specs, out_shape, c.outputs):
            assert tuple(spec.block_shape) == op.block, c.family
            assert spec.index_map is op.index_map, c.family
            assert tuple(sh.shape) == op.shape, c.family
            assert str(sh.dtype) == op.dtype, c.family
        want = {len(c.scalars) + i: o for i, o in c.aliases}
        assert dict(kw.get("input_output_aliases") or {}) == want, c.family


# ---------------------------------------------------------------------------
# VMEM model + static rejection in the autotune candidate path
# ---------------------------------------------------------------------------

def test_contract_vmem_bytes_synthetic():
    op = Operand("x", (4, 8), F32, (1, 8), lambda i: (i, 0))
    c = LaunchContract("t", (4,), (), (op,), (op,), (), {})
    # 2 operands x (1*8 elements x 4 bytes) x double-buffering
    assert vmem.contract_vmem_bytes(c) == 2 * 8 * 4 * vmem.DOUBLE_BUFFER


def test_band_launch_bytes_monotonic_in_tq():
    sizes = [vmem.band_launch_bytes("band_fwd", L=256, nr=16,
                                    mode="l0_causal", tq=t, d=16)
             for t in (16, 64, 256)]
    assert sizes[0] < sizes[1] < sizes[2]


def test_vmem_budget_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "12345")
    assert vmem.default_budget() == 12345
    monkeypatch.delenv("REPRO_VMEM_BUDGET")
    assert vmem.default_budget() == int(vmem.VMEM_BYTES
                                        * vmem.DEFAULT_FRACTION)


def test_vmem_rejection_is_static_and_logged(fresh_policy):
    """Over-budget candidates are dropped BEFORE measurement, logged as
    ``rejected:vmem`` with bytes + reason, and enumeration alone leaves
    the tuning tables (digest) untouched."""
    p = fresh_policy
    budget = vmem.band_launch_bytes("band_fwd", L=256, nr=16,
                                    mode="l0_causal", tq=128, d=16) - 1
    d0 = p.tuning_digest()
    cands = p.candidates("band_fwd", L=256, nr=16, mode="l0_causal",
                         d=16, vmem_budget=budget)
    assert [c["tq"] for c in cands] == [16, 32, 64]
    assert all(c["vmem_bytes"] <= budget for c in cands)
    rej = [e for e in p.decisions if e["source"] == "rejected:vmem"]
    assert [e["config"]["tq"] for e in rej] == [128, 256]
    for e in rej:
        assert e["config"]["vmem_bytes"] > budget
        assert "budget" in e["config"] and "reason" in e["config"]
    assert p.tuning_digest() == d0  # pure enumeration writes no tables

    measured = []

    def fake_measure(fn, iters=2, warmup=1):
        measured.append(fn)
        return float(len(measured))

    p._measure = fake_measure
    entry = p.autotune_band(L=256, nr=16, mode="l0_causal", d=16,
                            vmem_budget=budget)
    assert len(measured) == 3        # ONLY the surviving candidates ran
    assert entry["tq"] == 16         # fake timer: first candidate wins
    assert entry["vmem_bytes"] <= budget


def test_vmem_all_rejected_names_the_reason(fresh_policy):
    fresh_policy._measure = lambda fn, iters=2, warmup=1: 1.0
    with pytest.raises(AssertionError, match="rejected:vmem"):
        fresh_policy.autotune_band(L=64, nr=16, mode="l0_causal", d=16,
                                   vmem_budget=1)


# ---------------------------------------------------------------------------
# check CLI: --json report schema, --family filter, section selection
# ---------------------------------------------------------------------------

def _report(tmp_path, argv):
    import json
    path = tmp_path / "report.json"
    rc = check.main(argv + ["--json", str(path)])
    with open(path) as f:
        return rc, json.load(f)


def test_check_json_report_schema(tmp_path, capsys):
    """Pin the machine-readable report's schema: tooling diffs these
    across PRs, so a key rename must fail loudly here."""
    rc, rep = _report(tmp_path, ["--pool", "--pool-states", "400"])
    capsys.readouterr()
    assert rc == 0
    assert set(rep) == {"sections", "contracts", "families", "violations",
                        "dist", "pool", "ok", "runtime_s"}
    assert rep["sections"] == ["pool"]
    assert rep["contracts"] == 0 and rep["families"] == {}
    assert rep["violations"] == [] and rep["ok"] is True
    assert rep["dist"] is None
    assert isinstance(rep["runtime_s"], float)
    pool = rep["pool"]
    assert pool["states"] >= 400
    assert pool["transitions"] > pool["states"] // 2
    assert isinstance(pool["coverage"], dict) and pool["coverage"]
    assert "counterexample" not in pool        # only present on failure


def test_check_json_kernels_section(tmp_path, capsys):
    """Kernel runs populate contracts/families; violations (none on the
    committed kernels) carry label + the Violation dataclass fields."""
    rc, rep = _report(tmp_path, ["--kernels", "--nr", "4", "--d", "8",
                                 "--samples", "1",
                                 "--family", "decode_update"])
    capsys.readouterr()
    assert rc == 0
    assert rep["contracts"] > 0
    assert rep["families"] and all(f.startswith("decode_update")
                                   for f in rep["families"])
    assert rep["pool"] is None and rep["dist"] is None


def test_check_family_filters_contracts(capsys):
    """--family SUBSTR restricts the kernel sweep to matching labels or
    contract families (and the run still passes)."""
    assert check.main(["--nr", "4", "--d", "8", "--samples", "1",
                       "--family", "band_fwd"]) == 0
    out = capsys.readouterr().out
    assert "band_fwd" in out
    assert "decode" not in out


def test_check_cli_pool_section_stdout(capsys):
    assert check.main(["--pool", "--pool-states", "300"]) == 0
    out = capsys.readouterr().out
    assert "pool:" in out and "states" in out
    assert "checked" not in out            # kernel summary suppressed


# ---------------------------------------------------------------------------
# env-override hardening (REPRO_VMEM_BUDGET / REPRO_TUNE_CACHE)
# ---------------------------------------------------------------------------

def test_vmem_budget_malformed_env_warns_and_defaults(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "lots")
    with pytest.warns(RuntimeWarning, match="REPRO_VMEM_BUDGET"):
        got = vmem.default_budget()
    assert got == int(vmem.VMEM_BYTES * vmem.DEFAULT_FRACTION)


def test_tune_cache_malformed_env_warns_and_defaults(monkeypatch,
                                                     tmp_path):
    """A blank or NUL-bearing REPRO_TUNE_CACHE cannot be a cache dir:
    the policy must warn and fall back to the default path instead of
    crashing on first table save."""
    import os
    for bad in ("   ", "a\0b"):
        # NUL bytes cannot pass through putenv, so patch the mapping
        monkeypatch.setattr(os, "environ", {"REPRO_TUNE_CACHE": bad})
        with pytest.warns(RuntimeWarning, match="REPRO_TUNE_CACHE"):
            p = KernelPolicy()
        assert p.cache_dir == tuning.DEFAULT_CACHE_DIR
    # a usable path passes through silently
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = KernelPolicy()
    assert p.cache_dir == str(tmp_path)


def test_save_table_bad_dir_degrades_gracefully(tmp_path):
    """An unusable cache_dir passed EXPLICITLY (bypassing the env
    sanitizer) must not crash tuning -- table persistence is best
    effort."""
    p = KernelPolicy(cache_dir="cache\0dir")
    p._tables["band_fwd"] = {"x": {"tq": 16}}
    with pytest.warns(RuntimeWarning, match="cannot persist"):
        assert p._save_table("band_fwd") is None   # kept in memory
