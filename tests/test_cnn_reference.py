"""The CNN path against its plain references, plus the pieces the chip run
leans on: the int8-digit integer dot, the device-keyed chip specs, the
Auto-axis mesh and the compile-cache location.

``models.cnn.cnn_forward_ref`` is what ``chip_smoke.py`` checks the chip
against: a float32 ``jax.numpy`` network, and a fixed-point oracle of the
grid-resident forward built from the mixed-format op oracles.  Here the
engine's forward (Pallas kernels, interpreted on the CPU) must match it —
float to f32 rounding, q16 bit for bit — on LeNet and on VGG16's layer
stack at a 32×32 input.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import NumericsPolicy, QFormat, quantize_qtensor
from repro.core.template import default_template
from repro.core.tiling import TPU_V5E, device_spec, tpu_spec
from repro.kernels import ref
from repro.kernels.common import int_dot
from repro.models import cnn as C

NETS = {
    "lenet": C.LENET,
    "vgg16@32": dataclasses.replace(C.VGG16, input_hw=32),
}


def _net(name, batch=2):
    spec = NETS[name]
    key = jax.random.PRNGKey(3)
    params = C.init_cnn(key, spec, scale=2**0.5)
    hw = spec.input_hw
    x = jax.random.normal(jax.random.fold_in(key, 1), (batch, hw, hw, spec.input_ch))
    return spec, params, x


@pytest.mark.parametrize("name", sorted(NETS))
def test_float_forward_matches_reference(name):
    spec, params, x = _net(name)
    out = C.cnn_forward(default_template("pallas"), spec, params, x)
    want = C.cnn_forward_ref(spec, params, x)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=1e-5 * scale)


@pytest.mark.parametrize("name", sorted(NETS))
def test_q16_forward_bit_identical_to_oracle(name):
    spec, params, x = _net(name)
    tpl = default_template("q16")
    policy = NumericsPolicy("q16", fmt=QFormat(4, 12))
    qp = C.quantize_cnn_params(tpl, spec, params, policy)
    out = C.cnn_forward(tpl, spec, qp, x, policy=policy)
    want = C.cnn_forward_ref(spec, qp, x, policy=policy)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_grid_conv_matches_mixed_format_oracle():
    """Engine grid-resident conv == conv2d_qtensor_ref bit for bit, with the
    weight grid finer than the activation grid and bias + ReLU fused."""
    eng = default_template("q16").engine
    key = jax.random.PRNGKey(5)
    x = quantize_qtensor(jax.random.normal(key, (2, 9, 7, 5)) * 0.5, QFormat(4, 12))
    w = quantize_qtensor(jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 5, 6)) * 0.05)
    b = quantize_qtensor(jax.random.normal(jax.random.fold_in(key, 2), (6,)) * 0.1,
                         QFormat(4, 12))
    assert w.fmt.frac_bits > 12
    got = eng.conv2d(x, w, padding=1, bias=b, relu=True, qout=QFormat(3, 13))
    want = ref.conv2d_qtensor_ref(x, w, QFormat(3, 13), b, padding=1, relu=True)
    assert got.fmt == want.fmt
    np.testing.assert_array_equal(np.asarray(got.raw), np.asarray(want.raw))


@pytest.mark.parametrize("da,db", [(jnp.int16, jnp.int16), (jnp.int8, jnp.int16),
                                   (jnp.int16, jnp.int8), (jnp.int8, jnp.int8)])
def test_int_dot_is_exact_int32(da, db):
    """The int8-digit dot equals the int32 dot (mod 2^32) on full-range
    operands, extremes included."""
    rng = np.random.default_rng(0)
    ia, ib = np.iinfo(da), np.iinfo(db)
    a = rng.integers(ia.min, ia.max + 1, (16, 300)).astype(da)
    b = rng.integers(ib.min, ib.max + 1, (300, 24)).astype(db)
    a[0], a[1] = ia.max, ia.min
    b[:, 0], b[:, 1] = ib.min, ib.max
    want = a.astype(np.int64) @ b.astype(np.int64)
    want = ((want + 2**31) % 2**32 - 2**31).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(int_dot(jnp.asarray(a), jnp.asarray(b))), want)


def test_chip_specs_are_keyed_by_device_kind():
    assert tpu_spec("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no TpuSpec"):
        tpu_spec("TPU v99")
    # a CPU process plans for the chip its interpreted kernels stand in for
    assert device_spec() == TPU_V5E
    assert default_template("pallas").config.hw == TPU_V5E


def test_mesh_axes_are_auto():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    assert tuple(mesh.axis_types) == (AxisType.Auto,)


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    default = compile_cache.compile_cache_dir()
    assert default.endswith(".jax_compile_cache")
    assert default == compile_cache.compile_cache_dir()  # fixed, not per run
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/somewhere/else")
    assert compile_cache.compile_cache_dir() == "/somewhere/else"

