"""A configuration, its plain reference, its weight layout, its small
CPU shapes, a traffic mix and a per-layer metric added as new files
plus entries in BENCHMARK.json are found by name and run, with no
existing file of the harness edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# argv: checkout, the program's src, cell, 1 to run at the small shapes
# of bench/tests/small/
DRIVE = r'''
import json, sys, types
root, cell, small = sys.argv[1], sys.argv[3], sys.argv[4] == "1"
sys.path[:0] = [root, sys.argv[2]]
import jax
from bench import gen, harness, reduce_trace, run
assert harness.__file__.startswith(root)
if small:
    from bench.tests import tiny
    harness.config, gen.load = tiny.config, tiny.mix
harness.peaks = lambda kind: {"bf16_flops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e11}
reduce_trace.reduce = lambda path, devs=1: {
    "window_s": 1.0, "busy_s": 0.5,
    "breakdown": {"device_ops": [], "idle_gaps": []}}
out = {}
for trace in (0, 1):
    args = types.SimpleNamespace(workload=cell, seed=2**40 + 3,
                                 seconds=1.0, trace=trace)
    out[trace] = run.run_cell(args, jax.devices()[:1], harness.Clock(), root)
out["refs"] = sorted(m for m in sys.modules if m.startswith("bench.refs."))
out["layouts"] = {m: sys.modules[m].__file__ for m in sys.modules
                  if m.startswith("bench.layouts.")}
print(json.dumps(out))
'''

# A layout that is not the dense one: K and V drawn as one canonical
# leaf ``wkv``, so its leaves, their draws and their norms differ.
KV_LAYOUT = '''"""The dense decoder with K and V as one canonical leaf."""
import jax.numpy as jnp

from bench.layouts import dense

UNSTACKED = dense.UNSTACKED
matmul_params = dense.matmul_params
tick_weights = dense.tick_weights


def shapes(cfg):
    out = dict(dense.shapes(cfg))
    (n, d, hkv), scale = out.pop("wk")
    del out["wv"]
    out["wkv"] = ((n, d, 2 * hkv), scale)
    return out


def to_program(w, cfg):
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    w = dict(w)
    wkv = w.pop("wkv")
    return dense.to_program(dict(w, wk=wkv[..., :hkv], wv=wkv[..., hkv:]),
                            cfg)


def from_program(p, cfg):
    w = dense.from_program(p, cfg)
    wkv = jnp.concatenate([w.pop("wk"), w.pop("wv")], -1)
    return dict(w, wkv=wkv)
'''

KV_REF = '''"""The dense reference over weights with K and V as one leaf."""
from bench.refs import dense_h1d

adamw_step = dense_h1d.adamw_step


def _dense(w, cfg):
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    w = dict(w)
    wkv = w.pop("wkv")
    return dict(w, wk=wkv[..., :hkv], wv=wkv[..., hkv:])


def loss(w, cfg, tokens, prec="f32"):
    return dense_h1d.loss(_dense(w, cfg), cfg, tokens, prec)


def served_logits(w, cfg, seqs, prec="f32", pad_to=None):
    return dense_h1d.served_logits(_dense(w, cfg), cfg, seqs, prec, pad_to)
'''


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def copy_bench(tmp_path) -> dict:
    """A copy of BENCHMARK.json and bench/ in ``tmp_path``; returns the
    digest of bench/ as copied."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return digest(tmp_path / "bench")


def drive(root, cell, small) -> dict:
    """Run ``cell`` plain and traced in a fresh process from ``root``."""
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(root), os.path.join(ROOT, "src"),
         cell, "1" if small else "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_new_config_mix_and_metric_are_picked_up(tmp_path):
    before = copy_bench(tmp_path)

    b = tmp_path / "bench"
    with open(b / "configs" / "h1d-lm-144m.json") as f:
        cfg = json.load(f)
    cfg.update(name="tiny-lm", reference="tiny_ref", num_layers=2, d_model=64, num_heads=4,
               num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512, nr=8)
    (b / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    shutil.copy(b / "refs" / "dense_h1d.py", b / "refs" / "tiny_ref.py")
    (b / "traffic" / "tiny-pack.json").write_text(json.dumps({
        "kind": "train", "seq_len": 64, "batch": 2, "doc_median": 20,
        "doc_sigma": 1.0, "doc_min": 4, "doc_max": 64, "zipf_alpha": 1.1,
        "bigram": 0.2, "eos_id": 0}))
    (b / "limits" / "tiny-train.json").write_text(json.dumps(
        {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 1e-2}))
    (b / "metrics" / "steps_per_s.tiny.py").write_text(
        "def read(r):\n"
        "    return r['window']['steps'] / r['window']['seconds']\n")
    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": "tiny-lm", "source": "test",
                          "file": "bench/configs/tiny-lm.json",
                          "reduced": [], "why": "test"})
    bj["workloads"].append({"name": "tiny-train", "config": "tiny-lm",
                            "traffic": "tiny-pack", "chips": 1,
                            "why": "test"})
    for m in bj["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-train")
    bj["per_layer"].append({"name": "steps_per_s.tiny", "unit": "1/s",
                            "better": "higher", "source": "host_clock",
                            "layer": "model step",
                            "moves": "train_tokens_per_s",
                            "workloads": ["tiny-train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))

    out = drive(tmp_path, "tiny-train", small=False)
    plain, traced = out["0"], out["1"]
    assert out["refs"] == ["bench.refs.tiny_ref"]
    assert plain["correct"], plain["checks"]
    assert set(plain["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert traced["metrics"]["steps_per_s.tiny"]["value"] > 0
    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("base,cell,kind", [
    ("h1d-lm-144m", "lm144m-train-4k", "train"),
    ("yi-6b", "yi6b-decode-8k", "decode")])
def test_new_layout_is_picked_up(tmp_path, base, cell, kind):
    """A configuration whose layout, reference and small shapes exist
    only as new files runs a cell of each driver, plain and traced, with
    its work counted by its own layout."""
    before = copy_bench(tmp_path)
    b = tmp_path / "bench"
    name, new_cell = f"kv-{base}", f"kv-{cell}"
    cfg = json.loads((b / "configs" / f"{base}.json").read_text())
    cfg.update(name=name, layout="dense_kv", reference="kv_h1d")
    (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (b / "layouts" / "dense_kv.py").write_text(KV_LAYOUT)
    (b / "refs" / "kv_h1d.py").write_text(KV_REF)
    shutil.copy(b / "tests" / "small" / "configs" / f"{base}.json",
                b / "tests" / "small" / "configs" / f"{name}.json")
    shutil.copy(b / "limits" / f"{cell}.json",
                b / "limits" / f"{new_cell}.json")
    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    old = [w for w in bj["workloads"] if w["name"] == cell][0]
    bj["configs"].append({"name": name, "source": "test",
                          "file": f"bench/configs/{name}.json",
                          "reduced": [], "why": "test"})
    bj["workloads"].append(dict(old, name=new_cell, config=name))
    # no trace is read here: of the per-layer metrics only the
    # utilisation, which counts its work through the layout
    names = {f"mfu.{kind}"} | {m["name"] for m in bj["end_to_end"]}
    for m in bj["end_to_end"] + bj["per_layer"]:
        if m["name"] in names and cell in m.get("workloads", []):
            m["workloads"].append(new_cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))

    out = drive(tmp_path, new_cell, small=True)
    plain, traced = out["0"], out["1"]
    assert out["refs"] == ["bench.refs.dense_h1d", "bench.refs.kv_h1d"]
    assert out["layouts"]["bench.layouts.dense_kv"].startswith(str(b))
    assert plain["correct"], plain["checks"]
    assert traced["correct"], traced["checks"]
    assert traced["metrics"][f"mfu.{kind}"]["value"] > 0
    after = digest(b)
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("key,value", [("rms_norm_eps", 1e-5),
                                       ("mlp_bias", True)])
def test_config_key_the_program_cannot_honour_raises(key, value):
    from bench.drivers import train as T
    with open(os.path.join(ROOT, "bench", "configs", "yi-6b.json")) as f:
        cfg = json.load(f)
    T.model_config(cfg)
    with pytest.raises(ValueError, match=key):
        T.model_config(dict(cfg, **{key: value}))
