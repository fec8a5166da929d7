"""The traffic generator and the weights: the same seed gives the same
inputs, any whole number up to and past 2**32 is a seed, and every seed
gives the same amount of work."""
import numpy as np

from bench import gen, weights


def test_train_batches_repeat_and_vary_by_seed():
    mix = dict(gen.load("packed-4k"), seq_len=256, batch=2, doc_median=32)
    a = gen.train_batches(mix, 32768, 2**40 + 5, 3)
    b = gen.train_batches(mix, 32768, 2**40 + 5, 3)
    c = gen.train_batches(mix, 32768, 2**40 + 6, 3)
    assert a.shape == c.shape == (3, 2, 256)
    assert (a == b).all() and not (a == c).all()
    assert len({r.tobytes() for r in a.reshape(-1, 256)}) == 6
    assert (a == mix["eos_id"]).any() and a.max() < 32768


def test_serve_requests_same_sizes_for_every_seed():
    mix = gen.load("decode-8k")
    a = gen.serve_requests(mix, 64000, 3)
    b = gen.serve_requests(mix, 64000, 2**35)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert not np.array_equal(a[0]["prompt"], b[0]["prompt"])


def test_weight_keys_from_large_seeds():
    k1 = weights.key_from_seed(2**33 + 1, 1)
    k2 = weights.key_from_seed(1, 1)
    assert k1.shape == (2,) and not (np.asarray(k1) == np.asarray(k2)).all()
