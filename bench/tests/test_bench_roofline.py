"""Roofline counts of bench/roofline.py for VGG16@224, and the peaks table."""
import json

import pytest
from bench_tiny import REPO

from bench import roofline


def vgg16(numerics="q16"):
    return json.loads((REPO / "bench" / "configs" / f"vgg16-224-{numerics}.json").read_text())


def test_vgg16_totals_per_image():
    counts = roofline.layer_counts(vgg16(), batch=1)
    conv = sum(c["ops"] for c in counts if c["kind"] == "conv")
    fc = sum(c["ops"] for c in counts if c["kind"] == "fc")
    assert conv / 1e9 == pytest.approx(30.693, abs=5e-4)
    assert fc / 1e9 == pytest.approx(0.247, abs=5e-4)
    params = roofline.fc_params(vgg16())
    assert params / 1e6 == pytest.approx(123.64, abs=5e-3)
    assert 2 * params / 1e6 == pytest.approx(247.3, abs=0.05)  # q16: 2 B per value
    fc_bytes = sum(c["bytes"] for c in counts if c["kind"] == "fc")
    assert fc_bytes - 2 * params == 2 * (25088 + 2 * 4096 + 2 * 4096 + 1000)  # activations


def test_counts_scale_with_batch_and_storage_width():
    q1, q8 = roofline.layer_counts(vgg16(), 1), roofline.layer_counts(vgg16(), 8)
    assert [8 * a["ops"] for a in q1] == [b["ops"] for b in q8]
    f1 = roofline.layer_counts(vgg16("f32"), 1)
    assert [2 * a["bytes"] for a in q1] == [b["bytes"] for b in f1]


def test_conv_counts_use_the_logical_input_channels():
    conv0 = roofline.layer_counts(vgg16(), batch=1)[0]
    assert conv0["ops"] == 2 * 224 * 224 * 3 * 3 * 3 * 64  # Cin = 3, not 128 lanes
    elems = 224 * 224 * 3 + 3 * 3 * 3 * 64 + 64 + 224 * 224 * 64
    assert conv0["bytes"] == 2 * elems


def test_least_time_is_the_larger_bound():
    peaks = roofline.load_peaks("TPU v5 lite")
    fc0 = roofline.layer_counts(vgg16(), batch=1)[13]
    assert fc0["name"] == "fc0"
    t = roofline.least_time_s([fc0], peaks, "int8_ops_per_s")
    assert t == pytest.approx(fc0["bytes"] / 819e9)  # batch 1 FC: bandwidth bound
    conv8 = roofline.layer_counts(vgg16(), batch=8)[8]
    t = roofline.least_time_s([conv8], peaks, "int8_ops_per_s")
    assert t == pytest.approx(conv8["ops"] / 393e12)  # batch 8 conv: compute bound


def test_peaks_of_the_v5e_and_unknown_kinds():
    peaks = roofline.load_peaks("TPU v5 lite")
    assert peaks["bf16_ops_per_s"] == 197e12 and peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.load_peaks("TPU v9")
