"""Cross-route differential harness: direct / im2col / xla / q16 must agree.

Property-based (hypothesis, or the conftest shim when it isn't installed):
the conv geometry (H, W, Cin, Cout, K, stride, padding, relu, bias) is
derived from a drawn seed so the suite sweeps every route — the untiled
direct kernel, the two-block row-tiled cases, the manual-DMA (𝒯, ℭ) tiled
cases (ISSUE 8), the im2col GEMM, and the xla lowering — and asserts they
are bitwise-close in float and within quantization tolerance in q16
(DESIGN.md §2, ISSUE 2).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dse
from repro.core.engine import Engine, reset_plan_caches
from repro.core.quantization import Q2_14, dequantize, quantize
from repro.core.template import TemplateConfig
from repro.core.tiling import TPU_V5E, ceil_div, space_to_depth_geometry
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _draw_case(seed: int):
    """Seed -> a conv case; every route (incl. tiled) is reachable."""
    rng = np.random.default_rng(seed)
    k = int(rng.choice([1, 3, 5]))
    stride = int(rng.choice([1, 2, 4]))
    pad = int(rng.choice([0, 1, max(1, k // 2)]))
    h = int(rng.integers(k + stride, 18))
    w_ = int(rng.integers(k + stride, 18))
    cin = int(rng.integers(1, 9))
    cout = int(rng.integers(1, 20))
    relu = bool(rng.integers(0, 2))
    use_bias = bool(rng.integers(0, 2))
    kx = jax.random.fold_in(KEY, seed)
    # clip to [-1, 1]: keeps the q16 bound below deterministic (|a|, |b| <= 1)
    x = jnp.clip(jax.random.normal(kx, (2, h, w_, cin)) * 0.25, -1, 1)
    w = jnp.clip(jax.random.normal(jax.random.fold_in(kx, 1), (k, k, cin, cout)) * 0.25, -1, 1)
    b = jnp.clip(jax.random.normal(jax.random.fold_in(kx, 2), (cout,)) * 0.1, -1, 1) if use_bias else None
    return x, w, b, k, stride, pad, relu


def _tile_rows_for(k: int, stride: int, ho: int) -> int:
    """A legal tile height that actually tiles (>= 2 tiles) when ho allows."""
    th = max(ceil_div(k, stride), ceil_div(ho, 3))
    return th if th < ho else 0


def _dma_tiles_for(ho: int, wo: int) -> tuple[int, int]:
    """A ragged-edged (𝒯, ℭ) tile for the DMA regime (no legality bound)."""
    return max(1, ceil_div(ho, 3)), max(1, ceil_div(wo, 2))


# ---------------------------------------------------------------------------
# float: direct (untiled + tiled) == im2col == xla, bitwise-close
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_float_routes_agree(seed):
    x, w, b, k, stride, pad, relu = _draw_case(seed)
    ho = (x.shape[1] + 2 * pad - k) // stride + 1
    want = ref.conv2d_fused_ref(x, w, b, stride=stride, padding=pad, relu=relu)
    kw = dict(bias=b, stride=stride, padding=pad, relu=relu)
    outs = {
        "direct": ops.conv2d(x, w, route="direct", tau=8, **kw),
        "im2col": ops.conv2d(x, w, route="im2col", **kw),
    }
    th = _tile_rows_for(k, stride, ho)
    if th:
        outs["tiled"] = ops.conv2d(x, w, route="direct", tau=8, tile_rows=th, **kw)
        outs["dma_rows"] = ops.conv2d(
            x, w, route="direct", tau=8, tile_rows=th, halo_mode="dma", **kw
        )
    wo_ = (x.shape[2] + 2 * pad - k) // stride + 1
    tr, tc = _dma_tiles_for(ho, wo_)
    outs["dma_rc"] = ops.conv2d(
        x, w, route="direct", tau=8, tile_rows=tr, tile_cols=tc,
        halo_mode="dma", **kw
    )
    eng = Engine(TemplateConfig(backend="xla"))
    outs["xla"] = eng.conv2d(x, w, stride=stride, padding=pad, bias=b, relu=relu)
    for name, out in outs.items():
        assert out.shape == want.shape, name
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4,
            err_msg=f"route {name} (seed {seed})",
        )


# ---------------------------------------------------------------------------
# q16: direct (untiled + tiled) == im2col bit-exact; vs float within one LSB
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_q16_routes_agree(seed):
    x, w, b, k, stride, pad, relu = _draw_case(seed)
    ho = (x.shape[1] + 2 * pad - k) // stride + 1
    xq, wq = quantize(x), quantize(w)
    bq = None if b is None else quantize(b)
    kw = dict(bias=bq, stride=stride, padding=pad, relu=relu)
    want = ref.conv2d_q16_ref(xq, wq, bq, stride=stride, padding=pad, relu=relu)
    routes = {
        "direct": ops.conv2d_q16(xq, wq, route="direct", tau=8, **kw),
        "im2col": ops.conv2d_q16(xq, wq, route="im2col", **kw),
    }
    th = _tile_rows_for(k, stride, ho)
    if th:
        routes["tiled"] = ops.conv2d_q16(xq, wq, route="direct", tau=8, tile_rows=th, **kw)
        routes["dma_rows"] = ops.conv2d_q16(
            xq, wq, route="direct", tau=8, tile_rows=th, halo_mode="dma", **kw
        )
    wo_ = (x.shape[2] + 2 * pad - k) // stride + 1
    tr, tc = _dma_tiles_for(ho, wo_)
    routes["dma_rc"] = ops.conv2d_q16(
        xq, wq, route="direct", tau=8, tile_rows=tr, tile_cols=tc,
        halo_mode="dma", **kw
    )
    for name, out in routes.items():
        # all q16 routes accumulate exactly in int32 -> bit-identical raw
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(want), err_msg=f"route {name} (seed {seed})"
        )
    # quantization tolerance vs the float compute on the *snapped* operands:
    # exact int32 accumulation leaves only the final round-shift (<= LSB/2)
    # and the output clip, so one Q2.14 LSB bounds the difference.
    xd, wd = dequantize(xq), dequantize(wq)
    bd = None if bq is None else dequantize(bq)
    fwant = ref.conv2d_fused_ref(xd, wd, bd, stride=stride, padding=pad, relu=relu)
    fwant = jnp.clip(fwant, Q2_14.min_val, Q2_14.max_val)
    err = float(jnp.abs(dequantize(want) - fwant).max())
    assert err <= Q2_14.resolution * 1.001, f"q16 vs float {err} (seed {seed})"


# ---------------------------------------------------------------------------
# spatially-tiled planner cases: oversized layers stay direct and match
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride,pad", [(1, 1), (2, 0), (2, "SAME")])
def test_oversized_layer_tiles_and_matches_im2col(stride, pad):
    """A layer whose untiled slab exceeds the budget stays direct, tiled.

    Budgets are scaled per backend (q16 slabs are half the bytes) so both
    backends are genuinely oversized-yet-tileable at this 32x32x32 layer.
    """
    kx = jax.random.fold_in(KEY, 7)
    x = jnp.clip(jax.random.normal(kx, (1, 32, 32, 32)) * 0.25, -1, 1)
    w = jnp.clip(jax.random.normal(jax.random.fold_in(kx, 1), (3, 3, 32, 16)) * 0.25, -1, 1)
    b = jax.random.normal(jax.random.fold_in(kx, 2), (16,)) * 0.1
    # budgets count the chip layout: Cin=32 occupies 128 lanes (4x)
    cases = (
        ("pallas", 4, 1024 * 1024, 1e-4),
        ("q16", 2, 512 * 1024, Q2_14.resolution * 1.001),
    )
    for backend, in_bytes, budget, tol in cases:
        hw = dataclasses.replace(TPU_V5E, vmem_bytes=budget)
        eng = Engine(TemplateConfig(backend=backend, hw=hw))
        plan = eng.plan_conv(x.shape, w.shape, stride=stride, padding=pad)
        hp, wp = 32 + 2 * plan.pad, 32 + 2 * plan.pad
        ho = (hp - 3) // stride + 1
        # the stride-1 conv the kernel runs (over the space-to-depth input)
        untiled = dse.direct_conv_vmem(
            *space_to_depth_geometry(hp, wp, 32, 3, 3, stride), ho, ho,
            plan.tau, in_bytes,
        )
        assert untiled > budget, backend  # it really was oversized
        assert plan.route == "direct", backend
        assert plan.spatial_tiles >= 2 or plan.col_tiles >= 2
        assert plan.tile_rows > 0 or plan.tile_cols > 0
        assert plan.halo_mode in ("two_block", "dma")
        assert plan.vmem_bytes <= budget
        p_gemm = eng.plan_conv(x.shape, w.shape, stride=stride, padding=pad, route="im2col")
        out_t = eng.conv2d(x, w, stride=stride, padding=pad, bias=b, relu=True, plan=plan)
        out_g = eng.conv2d(x, w, stride=stride, padding=pad, bias=b, relu=True, plan=p_gemm)
        err = float(jnp.abs(out_t - out_g).max())
        assert err <= tol, f"{backend}: tiled vs im2col {err}"


def test_acceptance_shape_plans_tiled_direct_on_default_hw():
    """ISSUE 2 acceptance: 3x3, Cin=64, 512x512 exceeds v5e VMEM untiled."""
    eng = Engine(TemplateConfig(backend="pallas"))
    plan = eng.plan_conv((1, 512, 512, 64), (3, 3, 64, 64), stride=1, padding=1)
    untiled = dse.direct_conv_vmem(514, 514, 64, 3, 3, 512, 512, plan.tau, 4)
    assert untiled > eng.config.hw.vmem_bytes
    assert plan.route == "direct"
    assert plan.spatial_tiles >= 2
    # ISSUE 8 acceptance: the extreme-width shape tiles as (𝒯, ℭ) blocks
    # under the manual-DMA halo — no im2col fallback, a real column tile
    assert plan.halo_mode == "dma" and plan.col_tiles >= 2 and plan.tile_cols > 0
    assert plan.vmem_bytes <= eng.config.hw.vmem_bytes
    # the whole VGG16 stack at 512x512 now stays on the direct route
    from repro.core.template import default_template
    from repro.models.cnn import CNN_ZOO, plan_cnn

    reset_plan_caches()
    net = plan_cnn(default_template("pallas"), CNN_ZOO["vgg16"], (1, 512, 512, 3))
    assert [cp.route for cp in net.convs] == ["direct"] * len(net.convs)
    assert any(cp.spatial_tiles >= 2 for cp in net.convs)
    assert all(cp.vmem_bytes <= TPU_V5E.vmem_bytes for cp in net.convs)
    assert len(net.describe()) == len(net.convs) + len(net.fcs)
    reset_plan_caches()


# ---------------------------------------------------------------------------
# the forced-fallback boundary: below the minimal tiled working set -> im2col
# ---------------------------------------------------------------------------


def test_forced_fallback_boundary():
    x_shape, w_shape = (1, 24, 24, 16), (3, 3, 16, 8)
    hp = wp = 24
    ho = wo = 22
    # the smallest config the DSE may pick: tau=8, minimal legal tile
    vmin = min(
        c.vmem_bytes
        for c in dse.explore_conv_spatial(
            hp, wp, 16, 3, 3, ho, wo, 8,
            dataclasses.replace(TPU_V5E, vmem_bytes=2**62), 4, top=1000,
        )
    )
    below = dataclasses.replace(TPU_V5E, vmem_bytes=vmin - 1)
    eng_below = Engine(TemplateConfig(backend="pallas", hw=below))
    plan = eng_below.plan_conv(x_shape, w_shape)
    assert plan.route == "im2col" and plan.block is not None
    with pytest.raises(ValueError):
        eng_below.plan_conv(x_shape, w_shape, route="direct")
    at = dataclasses.replace(TPU_V5E, vmem_bytes=vmin)
    eng_at = Engine(TemplateConfig(backend="pallas", hw=at))
    plan_at = eng_at.plan_conv(x_shape, w_shape)
    assert plan_at.route == "direct" and plan_at.vmem_bytes == vmin
    assert plan_at.spatial_tiles >= 2 or plan_at.col_tiles >= 2
    # both sides of the boundary compute the same numbers
    kx = jax.random.fold_in(KEY, 11)
    x = jax.random.normal(kx, x_shape) * 0.25
    w = jax.random.normal(jax.random.fold_in(kx, 1), w_shape) * 0.25
    out_below = eng_below.conv2d(x, w, plan=plan)
    out_at = eng_at.conv2d(x, w, plan=plan_at)
    np.testing.assert_allclose(
        np.asarray(out_at), np.asarray(out_below), atol=1e-4, rtol=1e-4
    )


# ---------------------------------------------------------------------------
# tiled kernel sweep: stride x padding x ragged tile boundaries vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("tile_rows", [0, 3, 5])
def test_tiled_direct_conv_vs_ref_sweep(stride, tile_rows):
    kx = jax.random.fold_in(KEY, 13 + stride)
    x = jax.random.normal(kx, (2, 15, 13, 4)) * 0.25
    w = jax.random.normal(jax.random.fold_in(kx, 1), (3, 3, 4, 10)) * 0.25
    b = jax.random.normal(jax.random.fold_in(kx, 2), (10,)) * 0.1
    out = ops.conv2d(
        x, w, bias=b, stride=stride, padding=1, tau=8, relu=True,
        tile_rows=tile_rows,
    )
    want = ref.conv2d_fused_ref(x, w, b, stride=stride, padding=1, relu=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)
    xq, wq, bq = quantize(x), quantize(w), quantize(b)
    outq = ops.conv2d_q16(
        xq, wq, bias=bq, stride=stride, padding=1, tau=8, relu=True,
        tile_rows=tile_rows,
    )
    wantq = ref.conv2d_q16_ref(xq, wq, bq, stride=stride, padding=1, relu=True)
    np.testing.assert_array_equal(np.asarray(outq), np.asarray(wantq))


def test_tile_rows_too_small_raises():
    """stride*tile_rows < kh cannot cover the tap window -> loud error."""
    x = jnp.zeros((1, 16, 16, 4))
    w = jnp.zeros((5, 5, 4, 8))
    with pytest.raises(ValueError, match="tap window"):
        ops.conv2d(x, w, tile_rows=2)


# ---------------------------------------------------------------------------
# manual-DMA halo regime (ISSUE 8): (𝒯, ℭ) tiles vs oracle, both dtypes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("tile", [(3, 0), (0, 4), (5, 3), (2, 2)])
def test_dma_halo_conv_vs_ref_sweep(stride, tile):
    """DMA-halo row/column/joint tiling with ragged edges matches the oracle.

    (2, 2) with stride 1 and k=3 is *illegal* under the two-block scheme
    (stride·tile_rows < kh) but fine under DMA — the fetched window always
    covers the tap extent, so the legality bound is gone.
    """
    tr, tc = tile
    kx = jax.random.fold_in(KEY, 17 + stride)
    x = jax.random.normal(kx, (2, 15, 13, 4)) * 0.25
    w = jax.random.normal(jax.random.fold_in(kx, 1), (3, 3, 4, 10)) * 0.25
    b = jax.random.normal(jax.random.fold_in(kx, 2), (10,)) * 0.1
    out = ops.conv2d(
        x, w, bias=b, stride=stride, padding=1, tau=8, relu=True,
        tile_rows=tr, tile_cols=tc, halo_mode="dma",
    )
    want = ref.conv2d_fused_ref(x, w, b, stride=stride, padding=1, relu=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)
    xq, wq, bq = quantize(x), quantize(w), quantize(b)
    outq = ops.conv2d_q16(
        xq, wq, bias=bq, stride=stride, padding=1, tau=8, relu=True,
        tile_rows=tr, tile_cols=tc, halo_mode="dma",
    )
    wantq = ref.conv2d_q16_ref(xq, wq, bq, stride=stride, padding=1, relu=True)
    np.testing.assert_array_equal(np.asarray(outq), np.asarray(wantq))


def test_column_tiling_requires_dma():
    """tile_cols under the two-block BlockSpec scheme is a loud error."""
    x = jnp.zeros((1, 16, 16, 4))
    w = jnp.zeros((3, 3, 4, 8))
    with pytest.raises(ValueError, match="dma"):
        ops.conv2d(x, w, tile_rows=4, tile_cols=4)


def test_dma_tile_smaller_than_tap_window_works():
    """The two-block legality bound does not apply to the DMA regime."""
    kx = jax.random.fold_in(KEY, 23)
    x = jax.random.normal(kx, (1, 16, 16, 4)) * 0.25
    w = jax.random.normal(jax.random.fold_in(kx, 1), (5, 5, 4, 8)) * 0.25
    out = ops.conv2d(
        x, w, stride=1, tau=8, tile_rows=2, tile_cols=3, halo_mode="dma",
    )
    want = ref.conv2d_fused_ref(x, w, None, stride=1, padding=0, relu=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_divisor_tile_ladder_offers_exact_tilings():
    """ISSUE 8 satellite: the ladder enumerates non-power-of-two divisors of
    the extent, so shapes like Ho=27 can tile exactly (9·3) instead of only
    via ragged halvings (27→14→7)."""
    assert 9 in dse._tile_ladder(27, 1) and 3 in dse._tile_ladder(27, 1)
    assert 5 in dse._tile_ladder(15, 1)
    assert dse._tile_ladder(8, 1) == [8, 4, 2, 1]
    # and the explored configs include an exact non-power-of-two tiling
    ranked = dse.explore_conv_spatial(
        29, 29, 8, 3, 3, 27, 27, 8,
        # Cin=8 occupies 128 lanes (16x) in the chip-layout VMEM model
        dataclasses.replace(TPU_V5E, vmem_bytes=1024 * 1024), 4, top=1000,
    )
    assert any(c.tile_rows == 9 and c.halo_mode == "dma" for c in ranked)
