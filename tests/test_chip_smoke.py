"""``chip_smoke.py``'s one-chip run, rehearsed on the CPU.

The chip run itself needs a TPU (``main`` refuses anything else); this
drives the same serve / parity / train phases at smoke size with the
kernels interpreted, so a broken phase, a family that stops being
traced, or a decision that resolves to the wrong impl fails here first.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys

import pytest

from repro.configs import get_smoke_config
from repro.models import set_mesh_axes

ROOT = os.path.join(os.path.dirname(__file__), "..")
IMPL = "pallas_interpret"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve through it
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("chip_smoke", None)


def _cfg(arch):
    return dataclasses.replace(get_smoke_config(arch), nr=16,
                               attn_impl=IMPL, decode_impl=IMPL)


def test_one_chip_phases_at_smoke_size(chip_smoke, capsys):
    plan = chip_smoke.Plan(
        serve_cfg=_cfg("llama3.2-1b"), train_cfg=_cfg("h1d-lm-144m"),
        impl=IMPL, slots=2, max_len=256, requests=3, prompt_range=(40, 120),
        parity_prompts=(96, 60), seq=128, batch=2, steps=2)
    try:
        chip_smoke.one_chip(plan)
    finally:
        set_mesh_axes(None)     # the train phase sets it, as launch/train
    out = capsys.readouterr().out
    for phase in ("serve", "parity", "train"):
        assert f"[chip_smoke] {phase} " in out


def test_main_refuses_a_host_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
