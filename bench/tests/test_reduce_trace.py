"""The trace reduction: busy-interval union, per-operation times and the
attribution of idle gaps to host spans, on hand-made events and on a
small trace recorded on a TPU v5e."""
import os

import pytest

from bench import reduce_trace as R

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = ["loop"]


def test_union_merges_overlaps_and_touches():
    assert R.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        [0, 4], [5, 7], [10, 11]]


def test_reduce_events_by_hand():
    # window [0, 100] ns from the harness span; ops busy 10-30 (two
    # overlapping), 40-50 and 90-120 (clipped to 100)
    host = [("loop", 0, 100), ("wait", 30, 40), ("PjitFunction", 52, 60)]
    dev = {0: {"XLA Ops": [("fusion.1", 10, 25), ("_fwd_kernel.3", 20, 30),
                           ("_dq_kernel", 40, 50), ("fusion.1", 90, 120)],
               "XLA Modules": [("jit_step", 10, 30), ("jit_step", 40, 50)]}}
    r = R.reduce_events(dev, host, spans=SPANS)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((20 + 10 + 10) * 1e-9)
    assert r["op_seconds"]["_dq_kernel"] == pytest.approx(10e-9)
    assert r["op_seconds"]["fusion.1"] == pytest.approx(25e-9)
    # gaps: 0-10 (loop), 30-40 (wait), 50-90 (innermost at 70: loop)
    assert r["idle_by_span"] == pytest.approx(
        {"loop": 50e-9, "wait": 10e-9})
    assert r["breakdown"]["idle_gaps"][0] == ["loop", pytest.approx(40e-9)]
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"
    assert r["programs"]["count"] == 2
    assert r["programs"]["gaps"] == [pytest.approx(10e-9)]


def test_gap_label_is_innermost_span():
    spans = [("outer", 0, 100), ("inner", 40, 60)]
    assert [g[0] for g in R.label_gaps([(45, 55), (10, 20), (200, 210)],
                                       spans)] == [
        "inner", "outer", "no host span"]


def test_recorded_trace():
    # one second of lm144m-train-4k (7 train steps) on a TPU v5e
    r = R.reduce(os.path.join(DATA, "train_v5e.xplane.pb"))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] > 0.99 * r["window_s"]       # steps back to back
    assert r["programs"]["count"] == 7
    assert r["breakdown"]["device_ops"][0][1] > 0
    assert r["breakdown"]["idle_gaps"]
    labels = {g[0] for g in r["breakdown"]["idle_gaps"]}
    assert "train.wait" in labels
