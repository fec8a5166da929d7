"""A configuration, its plain reference, a traffic mix and a per-layer
metric added as new files plus entries in BENCHMARK.json are found by
name and run, with no existing file of the harness edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

DRIVE = r'''
import json, sys, types
root = sys.argv[1]
sys.path[:0] = [root, sys.argv[2]]
import jax
from bench import harness, reduce_trace, run
assert harness.__file__.startswith(root)
harness.peaks = lambda kind: {"bf16_flops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e11}
reduce_trace.reduce = lambda path, devs=1: {
    "window_s": 1.0, "busy_s": 0.5,
    "breakdown": {"device_ops": [], "idle_gaps": []}}
out = {}
for trace in (0, 1):
    args = types.SimpleNamespace(workload="tiny-train", seed=2**40 + 3,
                                 seconds=1.0, trace=trace)
    out[trace] = run.run_cell(args, jax.devices()[:1], harness.Clock(), root)
out["refs"] = sorted(m for m in sys.modules if m.startswith("bench.refs."))
print(json.dumps(out))
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


def test_new_config_mix_and_metric_are_picked_up(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "bench")

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

    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(tmp_path),
         os.path.join(ROOT, "src")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    plain, traced = out["0"], out["1"]
    assert out["refs"] == ["bench.refs.tiny_ref"]
    assert plain["correct"], plain["checks"]
    assert set(plain["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert traced["metrics"]["steps_per_s.tiny"]["value"] > 0
    after = digest(tmp_path / "bench")
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
