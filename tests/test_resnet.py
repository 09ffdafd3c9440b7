"""The bottleneck residual form of ``models/cnn.py`` (ResNet-50 v1.5) through
the CNN entry points VGG16 uses, against the ``resnet`` form's plain
references (``bench/networks/resnet.py``, written without the program).

The tiny network is ResNet-50's shape at a 16th of its widths on 32x32
frames (``bench/tests/tiny_resnet.py:TINY_RESNET``): the 7x7/s2 stem, the
overlapping 3x3/s2 max pool, one bottleneck a stage, each with its 1x1
projection, three strided 3x3s and strided projections, the average pool.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import NumericsPolicy, QFormat, QTensor
from repro.core.template import default_template
from repro.kernels import ops, ref
from repro.models import cnn as C
from repro.runtime import spans

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench" / "tests"))
from tiny_resnet import resnet_config  # noqa: E402

from bench import forms  # noqa: E402

SEED = 2**32 + 17


def _spec(cfg):
    return C.CNNSpec(cfg["network"], cfg["input_hw"], cfg["input_ch"], cfg["n_classes"],
                     fcs=tuple(cfg["fcs"]), stem=tuple(cfg["stem"]),
                     stem_pool=tuple(cfg["stem_pool"]),
                     stages=tuple(tuple(s) for s in cfg["stages"]))


@pytest.fixture(scope="module")
def tiny():
    cfg = resnet_config("tinyres-q16")
    form = forms.find_form(cfg)
    weights = form.init_params(cfg, jax.random.PRNGKey(SEED % 2**31))
    x = np.random.default_rng(SEED).standard_normal((2, 32, 32, 3), dtype=np.float32)
    return cfg, form, _spec(cfg), weights, x


def test_the_tiny_residual_network_on_the_grid_equals_the_fixed_reference(tiny):
    cfg, form, spec, weights, x = tiny
    tpl = default_template("q16")
    policy = C.calibrate_cnn_policy(tpl, spec, weights, x, base=NumericsPolicy("q16"))
    params = C.quantize_cnn_params(tpl, spec, weights, policy)
    got = np.asarray(C.cnn_forward(tpl, spec, params, x, policy=policy))
    want = np.asarray(form.fixed_reference(cfg, weights, x, 16)(x))
    assert got.std() > 0.1  # the logits are not a saturated constant
    np.testing.assert_array_equal(got, want)
    # the program's own reference walks the same integer semantics
    np.testing.assert_array_equal(np.asarray(C.cnn_forward_ref(spec, params, x, policy=policy)),
                                  got)


def test_the_tiny_residual_network_in_float_tracks_the_float_reference(tiny):
    """Both sides contract in full float32 (Precision.HIGHEST) and differ
    only in summation order: the kernels' per-tap accumulation, the space-
    to-depth taps of the strided convs and XLA's conv.  Over 17 convs and
    one FC that leaves a few float32 ulps of the largest logit (3e-7 read
    on the CPU); the limit is the tiny benchmark network's, 2e-6, below
    the 4e-6 to 1.1e-5 that three bfloat16 passes read there."""
    cfg, form, spec, weights, x = tiny
    got = np.asarray(C.cnn_forward(default_template("pallas"), spec, weights, x))
    want = np.asarray(form.float_reference(cfg, weights, "highest")(x))
    assert np.abs(got - want).max() / np.abs(want).max() <= 2e-6


@pytest.mark.parametrize("total_bits", [16, 8])
def test_the_residual_add_saturates_at_both_ends_of_the_raw_range(total_bits):
    fmt = QFormat(4, total_bits - 4, total_bits)
    big, small = fmt.raw_max - 10, fmt.raw_min + 10
    a = QTensor(jnp.array([big, small, 5, -7], fmt.storage_dtype), fmt)
    b = QTensor(jnp.array([big, small, 6, 3], fmt.storage_dtype), fmt)
    out = C._residual_add(a, b)
    assert out.fmt == fmt and out.raw.dtype == fmt.storage_dtype
    # wrapped int arithmetic would give a negative sum (ReLU'd to 0) and a
    # positive one; saturation gives the top of the range and then 0
    np.testing.assert_array_equal(np.asarray(out.raw), [fmt.raw_max, 0, 11, 0])


def test_the_average_pool_rounds_half_up_on_the_raws():
    fmt = QFormat(4, 12, 16)
    raw = jnp.zeros((1, 7, 7, 3), jnp.int16).at[0, 0, 0].set(jnp.array([24, 25, 73], jnp.int16))
    out = C._avgpool(QTensor(raw, fmt))
    # (s + 24) // 49: 24/49 rounds down, 25/49 and 73/49 (1.49) to 1
    np.testing.assert_array_equal(np.asarray(out.raw), [[0, 1, 1]])


def _two_block_spec():
    return C.CNNSpec("twores", 16, 3, 10, stem=(4, 3, 1, 1), stem_pool=(3, 2, 1),
                     stages=((2, 4, 8, 1),))


@pytest.mark.parametrize("layer,match", [
    ("conv5", "add1"),  # block 1's input: the identity shortcut's grid
    ("conv4", "conv4"),  # block 0's projection reads block 0's input
])
def test_a_policy_that_splits_a_value_across_grids_is_an_error(layer, match):
    spec = _two_block_spec()
    assert C.cnn_layer_names(spec)[4:6] == ("conv4", "conv5")
    q16, q8 = QFormat(4, 12, 16), QFormat(4, 4, 8)
    policy = NumericsPolicy("mixed", fmt=q16, layer_fmts=((layer, q8),))
    tpl = default_template("q16")
    weights = C.init_cnn(jax.random.PRNGKey(0), spec)
    params = C.quantize_cnn_params(tpl, spec, weights, policy)
    x = jax.ShapeDtypeStruct((1, 16, 16, 3), jnp.float32)
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda p, x: C.cnn_forward(tpl, spec, p, x, policy=policy), params, x)
    # one grid everywhere, or the int8 rung everywhere, is sound
    for fmt in (q16, q8):
        uniform = dataclasses.replace(policy, layer_fmts=(), fmt=fmt)
        assert len(C._residual_grids(spec, uniform)) == 8


def test_a_spatial_plan_of_a_residual_network_is_an_error():
    with pytest.raises(ValueError, match="spatial"):
        C.plan_cnn(default_template("q16"), C.RESNET50, (1, 224, 224, 3), spatial=4)


@pytest.mark.parametrize("spec,shape,counts", [
    (C.RESNET50, (32, 224, 224, 3), (16, 36, 7)),
    (C.VGG16, (32, 224, 224, 3), (0, 0, 0)),
])
def test_the_plan_span_counts_adds_pointwise_and_strided_convs(spec, shape, counts):
    tpl = default_template("q16")
    plan = C.plan_cnn(tpl, spec, shape)
    attrs = [s for s in spans.spans() if s.name == "plan"][-1].attrs
    assert (attrs["residual_adds"], attrs["pointwise_convs"], attrs["strided_convs"]) == counts
    assert len(plan.convs) == len(C.cnn_layer_names(spec)) - len(spec.fcs) - 1
    # every strided conv, and only those, runs over its space-to-depth input
    assert sum(cp.space_to_depth for cp in plan.convs) == counts[2]
    assert all(cp.route == "direct" for cp in plan.convs)


def test_resnet50_layers_follow_table_1():
    convs, feat = C.conv_shapes(C.RESNET50)
    assert len(convs) == 53 and feat == 2048
    macs = sum(((h + 2 * p - k) // s + 1) ** 2 * k * k * cin * cout
               for h, _, cin, cout, k, s, p in convs)
    assert macs + 2048 * 1000 == 4_089_184_256
    assert C.cnn_layer_names(C.RESNET50)[-2:] == ("conv52", "fc0")
    # v1.5: the strided 3x3s and projections open stages 2-4
    strided = [i for i, c in enumerate(convs) if c[5] > 1]
    assert strided == [0, 12, 14, 25, 27, 44, 46]


@pytest.mark.parametrize("k,stride,pad,h", [
    (7, 2, 3, 17), (3, 2, 1, 12), (3, 2, 1, 13), (1, 2, 0, 12), (11, 4, 2, 27), (3, 3, 0, 14),
])
def test_a_strided_conv_equals_the_stride_1_conv_over_its_space_to_depth_input(k, stride, pad, h):
    x = jax.random.normal(jax.random.PRNGKey(h), (2, h, h + 1, 3))
    w = jax.random.normal(jax.random.PRNGKey(k), (k, k, 3, 5))
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    xs, ws = ops.space_to_depth_operands(xp, w, stride)
    assert ws.shape[:2] == (-(-k // stride),) * 2
    want = ref.conv2d_ref(x, w, stride=stride, padding=pad)
    got = ref.conv2d_ref(xs, ws, stride=1, padding=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-4)
    xq = jnp.round(x * 100).astype(jnp.int16)
    wq = jnp.round(w * 100).astype(jnp.int16)
    xqs, wqs = ops.space_to_depth_operands(jnp.pad(xq, ((0, 0), (pad, pad), (pad, pad), (0, 0))),
                                           wq, stride)
    np.testing.assert_array_equal(
        np.asarray(ref._conv_i32(xqs, wqs, 1)),
        np.asarray(ref._conv_i32(jnp.pad(xq, ((0, 0), (pad, pad), (pad, pad), (0, 0))), wq,
                                 stride)))
