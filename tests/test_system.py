"""End-to-end system behaviour: the paper's drop-in claim, H1D vs dense
quality signal, and the dry-run tooling units."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import ZipfLM
from repro.models import get_model
from repro.models.common import ModelConfig
from repro.train import TrainConfig, init_state, make_train_step


def test_h1d_is_drop_in_replacement():
    """Same config with attention=full vs h1d: identical param trees
    (the paper's drop-in claim, section 8)."""
    import dataclasses
    cfg_h = get_smoke_config("llama3.2-1b")
    cfg_f = dataclasses.replace(cfg_h, attention="full")
    fns = get_model(cfg_h)
    p1, s1 = fns.init(jax.random.PRNGKey(0), cfg_h)
    p2, s2 = fns.init(jax.random.PRNGKey(0), cfg_f)
    assert (jax.tree_util.tree_structure(p1)
            == jax.tree_util.tree_structure(p2))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_h1d_short_train_tracks_dense_attention():
    """Short LM training: H1D loss curve stays close to full attention
    (the quality claim at small scale)."""
    import dataclasses
    base = ModelConfig(name="tiny", family="dense", num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
                       d_ff=128, vocab_size=256, attention="h1d", nr=8,
                       tie_embeddings=True)
    data = ZipfLM(vocab_size=256, seq_len=128, batch_per_host=8, seed=0)
    finals = {}
    for attn in ("h1d", "full"):
        cfg = dataclasses.replace(base, attention=attn)
        tc = TrainConfig(peak_lr=3e-3, warmup=5, total_steps=80)
        state, _ = init_state(jax.random.PRNGKey(0), cfg, tc)
        step = jax.jit(make_train_step(cfg, tc))
        for i in range(80):
            state, m = step(state, jax.tree.map(jnp.asarray, data.batch(i)))
        finals[attn] = float(m["loss"])
    assert abs(finals["h1d"] - finals["full"]) < 0.35, finals


def test_parse_collectives_on_synthetic_hlo():
    from repro.launch.dryrun import parse_collectives
    hlo = """
  %all-reduce.1 = f32[1024,16]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[64,256]{1,0} all-gather(%y), replica_groups={{0,1},{2,3}}, dimensions={0}
  %cp = f32[128]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %nothing = f32[2,2]{1,0} add(%a, %b)
"""
    out = parse_collectives(hlo)
    assert out["all-reduce"]["count"] == 1
    assert out["all-reduce"]["result_bytes"] == 1024 * 16 * 4
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["result_bytes"] == 64 * 256 * 2
    assert out["collective-permute"]["result_bytes"] == 128 * 4
    # ring formula: AR with n=4 => 2*(3/4)*size
    assert abs(out["all-reduce"]["wire_bytes"]
               - 2 * 0.75 * 1024 * 16 * 4) < 1
    assert out["all-to-all"]["count"] == 0


def test_cache_shardings_heuristics():
    from repro.parallel import cache_shardings
    # spec-only: an abstract mesh needs no real devices
    mesh = jax.sharding.AbstractMesh(
        (2, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    big = jnp.zeros((8, 64, 4))       # batch-major, divisible by dp*tp
    small = jnp.zeros((3, 64, 4))     # not divisible -> replicated
    sh = cache_shardings(mesh, {"a": big, "b": small}, batch=8, kv_heads=1,
                         long_context=False)
    assert sh["a"].spec == jax.sharding.PartitionSpec(
        ("data",) + ("model",), None, None)
    assert sh["b"].spec == jax.sharding.PartitionSpec()
    # long-context: sequence axis shards over data
    seq = jnp.zeros((4, 128, 16))
    sh2 = cache_shardings(mesh, {"c": seq}, batch=1, kv_heads=4,
                          long_context=True)
    assert "data" in jax.tree_util.tree_leaves(
        [sh2["c"].spec[1]]) or sh2["c"].spec[1] == "data"


def test_input_specs_cover_all_cells():
    """Every (arch x shape) produces well-defined ShapeDtypeStructs."""
    from repro.configs import ARCH_IDS, SHAPES, get_smoke_config
    from repro.launch import specs as S
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        for shape, (seq, batch, kind) in SHAPES.items():
            seq_s, batch_s = 64, 2    # reduced sizes, same code path
            if kind == "train":
                specs = S.train_batch_specs(cfg, seq_s, batch_s)
                assert "tokens" in specs
            elif kind == "prefill":
                specs = S.prefill_batch_specs(cfg, seq_s, batch_s)
            else:
                caches, tok, t = S.decode_arg_specs(cfg, seq_s, batch_s)
                assert tok.shape == (batch_s,)
