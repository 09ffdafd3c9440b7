"""The unified compute unit: backend equivalence + tiling legality/DSE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dse import (default_block_for, explore_tpu_block,
                            gemm_vmem_bytes, skinny_time_s)
from repro.core.template import TemplateConfig, Template, default_template
from repro.core.tiling import MatmulBlock, TPU_V5E, ceil_div, clamp_block

KEY = jax.random.PRNGKey(7)


def test_backends_agree():
    x = jax.random.normal(KEY, (48, 100)) * 0.1
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (100, 36)) * 0.1
    ref = default_template("xla").matmul(x, w)
    pal = default_template("pallas").matmul(x, w)
    q16 = default_template("q16").matmul(x, w)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=1e-4, rtol=1e-4)
    # fixed point: bounded quantization error
    assert float(jnp.abs(q16 - ref).max()) < 0.01


def test_leading_dims_flattened():
    x = jax.random.normal(KEY, (2, 3, 5, 16))
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (16, 8))
    tpl = default_template("xla")
    out = tpl.matmul(x, w)
    assert out.shape == (2, 3, 5, 8)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x.reshape(-1, 16) @ w).reshape(2, 3, 5, 8),
        atol=1e-4, rtol=1e-4,
    )


def test_conv2d_matches_lax():
    x = jax.random.normal(KEY, (2, 10, 10, 3))
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (3, 3, 3, 8)) * 0.2
    tpl = default_template("xla")
    out = tpl.conv2d(x, w, stride=1, padding=1)
    want = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# tiling properties (hypothesis)
# ---------------------------------------------------------------------------


@given(
    st.integers(min_value=1, max_value=4096),
    st.integers(min_value=1, max_value=4096),
    st.integers(min_value=1, max_value=4096),
)
@settings(max_examples=100, deadline=None)
def test_clamp_block_always_legal_alignment(m, n, k):
    b = clamp_block(m, n, k, MatmulBlock(512, 512, 512))
    assert b.bm % TPU_V5E.sublane == 0
    assert b.bn % TPU_V5E.lane == 0
    assert b.bk % TPU_V5E.lane == 0
    assert b.vmem_bytes() <= MatmulBlock(512, 512, 512).vmem_bytes()


@given(
    st.integers(min_value=128, max_value=8192),
    st.integers(min_value=128, max_value=8192),
    st.integers(min_value=128, max_value=8192),
)
@settings(max_examples=30, deadline=None)
def test_dse_block_fits_vmem(m, n, k):
    blk = default_block_for(m, n, k)
    assert blk.vmem_bytes() <= TPU_V5E.vmem_bytes
    assert blk.aligned()


def test_dse_prefers_higher_intensity():
    ranked = explore_tpu_block(4096, 4096, 4096)
    assert len(ranked) >= 2
    scores = [s for _, s in ranked]
    assert scores == sorted(scores, reverse=True)
    best = ranked[0][0]
    # the best block for a big square GEMM should be MXU-saturating
    assert best.bm >= 256 and best.bn >= 256


def test_mxu_efficiency_penalizes_misalignment():
    good = MatmulBlock(256, 256, 256)
    assert good.mxu_efficiency() == 1.0


# ---------------------------------------------------------------------------
# skinny-M GEMM blocks (M below one MXU edge)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 8])
@pytest.mark.parametrize("n,k", [(4096, 25088), (4096, 4096), (1000, 4096)],
                         ids=["fc0", "fc1", "fc2"])
def test_skinny_fc_block(m, n, k):
    """VGG16's FC head at batch 1 and 8: one 8-row block, weight tiles that
    divide the weight (N = 1000 pads to 1024 as before, no further), fc0 in
    at most 200 grid steps, and a working set that fits VMEM at 4-byte
    operands with the kernels' temporaries."""
    blk = default_block_for(m, n, k)
    assert blk.bm == 8
    assert k % blk.bk == 0
    if n % TPU_V5E.lane == 0:
        assert n % blk.bn == 0
    else:
        assert ceil_div(n, blk.bn) * blk.bn == ceil_div(n, TPU_V5E.lane) * TPU_V5E.lane
    if k == 25088:
        assert (n // blk.bn) * (k // blk.bk) <= 200
    assert gemm_vmem_bytes(blk) <= TPU_V5E.vmem_bytes
    # the fallback block is what the model ranks below the choice
    old = MatmulBlock(8, 128, 128)
    assert skinny_time_s(blk, m, n, k) < skinny_time_s(old, m, n, k)


@pytest.mark.parametrize("k", [4096, 25088, 896, 100])
def test_skinny_bk_depends_on_k_alone(k):
    """Every skinny GEMM over one K reduces in the same bk steps, so a GEMM
    split over N (tensor-parallel decode) accumulates as the whole does."""
    bks = {default_block_for(m, n, k).bk for m in (1, 8, 64, 120)
           for n in (4096, 2048, 1000, 128)}
    assert len(bks) == 1


#: Blocks the planner chose before the skinny-M branch existed, for GEMMs
#: with M >= 128 (conv im2col, prefill ladders, large GEMMs): unchanged.
LARGE_M_BLOCKS = {
    (128, 128, 128): (128, 128, 128),
    (128, 1000, 4096): (128, 512, 128),
    (128, 4096, 25088): (128, 2048, 128),
    (196, 512, 4608): (128, 512, 128),
    (200, 300, 77): (128, 128, 128),
    (256, 4096, 4096): (256, 2048, 128),
    (512, 512, 1536): (512, 512, 128),
    (1568, 512, 4608): (512, 512, 128),
    (4096, 4096, 4096): (512, 512, 128),
    (12544, 64, 27): (1024, 128, 128),
    (50176, 64, 576): (1024, 128, 128),
    (65536, 4864, 896): (512, 512, 128),
    (65536, 27648, 5120): (512, 512, 128),
}


@pytest.mark.parametrize("shape", sorted(LARGE_M_BLOCKS), ids=str)
def test_large_m_blocks_unchanged(shape):
    b = default_block_for(*shape)
    assert (b.bm, b.bn, b.bk) == LARGE_M_BLOCKS[shape]
