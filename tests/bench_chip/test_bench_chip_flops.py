"""FLOP and byte counts of the paper CNN from its layer shapes."""
import json

import pytest

import _chipbench_path  # noqa: F401
import flops
from _chipbench_path import HARNESS

MODEL = json.loads((HARNESS / "configs" / "paper-cnn-f32.json").read_text())[
    "model"]


def test_table_iii_forward_and_seed():
    assert flops.forward_flops(MODEL) == 50_006_528
    assert flops.backward_seed_flops(MODEL) == 50_006_528
    macs = [lyr.macs for lyr in flops.layers(MODEL)]
    assert macs == [884_736, 9_437_184, 4_718_592, 9_437_184, 524_288, 1_280]


def test_parameter_count_matches_layers():
    n = sum(lyr.k * lyr.k * lyr.cin * lyr.cout + lyr.cout
            for lyr in flops.layers(MODEL))
    assert n == 591_274


def test_request_flops():
    fp = flops.forward_flops(MODEL)
    assert flops.request_flops(MODEL, "predict", 0, False) == fp
    assert flops.request_flops(MODEL, "explain", 3, False) == 3 * fp
    assert flops.request_flops(MODEL, "explain", 1, True) == 2 * fp


def test_launch_bytes_and_roofline():
    conv1 = flops.layers(MODEL)[1]
    w = flops.conv_forward_launch(conv1, 8, "f32")
    assert w["flops"] == 2 * 8 * 9_437_184
    assert w["bytes"] == 4 * (8 * 32 * 32 * 64 + 9 * 32 * 32)
    assert flops.conv_forward_launch(conv1, 8, "fxp16")["bytes"] == (
        w["bytes"] // 2)
    b = flops.conv_backward_launch(conv1, 8, 3, "f32", "saliency")
    assert b["flops"] == 3 * w["flops"]
    grads = 4 * 3 * 8 * (16 * 16 * 32 + 32 * 32 * 32)
    masks = 8 * 32 * 32 * 32 / 8 + 8 * 16 * 16 * 32 * 2 / 8
    assert b["bytes"] == pytest.approx(grads + 4 * 9 * 32 * 32 + masks)
    no_mask = flops.conv_backward_launch(conv1, 8, 3, "f32", "deconvnet")
    assert b["bytes"] - no_mask["bytes"] == 8 * 32 * 32 * 32 / 8
    # compute bound at the bf16 peak, memory bound at a tiny peak
    assert flops.roofline_s(2e12, 1e6, 1e12, 1e9) == 2.0
    assert flops.roofline_s(2.0, 1e9, 1e12, 1e9) == 1.0
