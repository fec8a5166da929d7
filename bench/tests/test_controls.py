"""The control of each cell -- the plain reference computed one
precision below the configuration's, in the program's place -- comes
out as not correct against the cell's limits, here at a small size on
the CPU (``bench/controls.py`` reads it on the chip at the cell's
size).  The program's own readings at that size stay inside them."""
import pytest

from bench import controls, harness
from bench.tests import tiny


@pytest.fixture(autouse=True)
def small(monkeypatch):
    tiny.patch(monkeypatch)


def fails(gaps, cell, scale=1.0):
    lim = harness.limits(cell)
    return any(gaps[k] > lim[k] * scale for k in lim)


def logit_scale(name):
    """``served_gap`` is in logits, whose spread under the benchmark's
    weights (head scale 0.02, unit-RMS final norm) grows as
    sqrt(d_model): the small model's gaps are smaller by that ratio."""
    return (tiny.WIDTHS[name]["d_model"]
            / tiny.full_config(name)["d_model"]) ** 0.5


@pytest.mark.parametrize("seed", [7, 2**33 + 1])
def test_train_control_fails_and_program_passes(seed):
    r = controls.train_readings(tiny.config("h1d-lm-144m"),
                                tiny.mix("packed-4k"), seed, True)
    assert fails(r["control"], "lm144m-train-4k"), r
    assert fails(r["half_batch"], "lm144m-train-4k"), r
    assert not fails(r["program"], "lm144m-train-4k"), r


@pytest.mark.parametrize("seed", [7, 2**33 + 1])
def test_serve_control_fails_and_program_passes(seed):
    r = controls.serve_readings(tiny.config("yi-6b"), tiny.mix("decode-8k"),
                                seed, True, seconds=1.0)
    scale = logit_scale("yi-6b")
    assert fails(r["control"], "yi6b-decode-8k", scale), r
    assert not fails(r["program"], "yi6b-decode-8k", scale), r
