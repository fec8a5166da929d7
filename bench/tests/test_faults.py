"""Each fault that a cell can have, planted under an otherwise whole run
on the CPU at a small size, makes ``correct`` come out false; the same
run without the fault comes out true."""
import jax
import jax.numpy as jnp
import pytest

from bench.drivers import serve as S
from bench.drivers import train as T
from bench.tests import tiny


@pytest.fixture(autouse=True)
def small(monkeypatch):
    tiny.patch(monkeypatch)


def test_train_sound_run_is_correct():
    r = tiny.run_cell("lm144m-train-4k")
    assert r["correct"], r["checks"]


def test_train_state_unchanged(monkeypatch):
    def frozen(self, batch):
        copy = jax.tree.map(jnp.copy, self.state)
        return self.step(copy, {"tokens": batch})[1]["loss"]
    monkeypatch.setattr(T.Program, "__call__", frozen)
    r = tiny.run_cell("lm144m-train-4k")
    assert not r["correct"], r["checks"]


def test_train_half_batch(monkeypatch):
    init, call = T.Program.__init__, T.Program.__call__

    def half_init(self, cfg, seed, shape):
        init(self, cfg, seed, (shape[0] // 2,) + tuple(shape[1:]))

    def half_call(self, batch):
        return call(self, batch[: batch.shape[0] // 2])
    monkeypatch.setattr(T.Program, "__init__", half_init)
    monkeypatch.setattr(T.Program, "__call__", half_call)
    r = tiny.run_cell("lm144m-train-4k")
    assert not r["correct"], r["checks"]


def _wrap_decode(monkeypatch, fault):
    init = S.Engine.__init__

    def patched(self, cfg, mix, seed):
        init(self, cfg, mix, seed)
        fn, eng = self.eng._decode, self.eng
        ticks = [0]

        def decode(*args):
            ticks[0] += 1
            return fault(fn, ticks[0], eng, *args)
        self.eng._decode = decode
    monkeypatch.setattr(S.Engine, "__init__", patched)


def test_serve_sound_run_is_correct():
    r = tiny.run_cell("yi6b-decode-8k")
    assert r["correct"], r["checks"]


def test_serve_token_altered(monkeypatch):
    def fault(fn, tick, eng, *args):
        logits, caches = fn(*args)
        if tick == 5:   # one slot's token, where it is produced
            logits = logits.at[0].set(-logits[0])
        return logits, caches
    _wrap_decode(monkeypatch, fault)
    r = tiny.run_cell("yi6b-decode-8k")
    assert not r["correct"], r["checks"]


def _donating(fn):
    """The tick as it runs with its caches donated: the input buffers
    are gone once it returns."""
    def tick(params, caches, *rest):
        out = fn(params, caches, *rest)
        for x in jax.tree.leaves(caches):
            x.delete()
        return out
    return tick


def _withhold(fn, tick, eng, params, caches, *rest):
    """The tick's logits with its input caches handed back unchanged,
    from a copy taken before the call, so that a tick that donates its
    caches still runs on."""
    keep = jax.tree.map(jnp.copy, caches)
    logits, _ = fn(params, caches, *rest)
    return logits, keep


def test_serve_state_unchanged(monkeypatch):
    _wrap_decode(monkeypatch, _withhold)
    r = tiny.run_cell("yi6b-decode-8k")
    assert not r["correct"], r["checks"]


def test_serve_state_unchanged_donated(monkeypatch):
    _wrap_decode(monkeypatch,
                 lambda fn, *args: _withhold(_donating(fn), *args))
    r = tiny.run_cell("yi6b-decode-8k")
    assert not r["correct"], r["checks"]
