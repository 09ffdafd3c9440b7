"""jit'd public wrappers around the Pallas kernels, with shape handling,
GQA folding, epilogue fusion, and explicit kernel routes.

These are the entry points the rest of the framework uses; ``ref.py`` holds
the oracles each one is tested against.  Route *selection* (direct conv vs
im2col GEMM, plan-cached DSE blocks) is the execution-plan engine's job
(``core/engine.py``, DESIGN.md §2); these wrappers execute whichever route
they are told.

This module also owns the single im2col implementation in the codebase
(:func:`im2col`) — the GEMM-lowering shared by the im2col conv route on
every backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantization import QFormat, Q2_14
from repro.core.tiling import MatmulBlock, clamp_block

from . import ref
from .conv2d import conv2d_pallas, conv2d_q16_pallas
from .flash_attention import flash_attention_pallas
from .matmul_fp import matmul_fp_pallas
from .matmul_q16 import matmul_q16_pallas

__all__ = [
    "im2col",
    "conv_gemm_weights",
    "matmul_fp",
    "matmul_q16",
    "conv2d",
    "conv2d_q16",
    "flash_attention",
]


# ---------------------------------------------------------------------------
# im2col lowering (the one implementation; paper Fig. 4's conv-as-GEMM)
# ---------------------------------------------------------------------------


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1):
    """Already-padded NHWC image -> GEMM rows.

    x: (N, H, W, Cin) -> cols (N*Ho*Wo, Cin*Kh*Kw) with features ordered
    (cin, kh, kw) to match :func:`conv_gemm_weights`.  Integer inputs are
    gathered in f32 (exact for int16 magnitudes < 2^24) and cast back, since
    the patch-extraction primitive is float-only.

    Returns (cols, ho, wo).
    """
    n, h, wd, cin = x.shape
    ho = (h - kh) // stride + 1
    wo = (wd - kw) // stride + 1
    cast = None
    xg = x
    if jnp.issubdtype(x.dtype, jnp.integer):
        cast = x.dtype
        xg = x.astype(jnp.float32)
    patches = jax.lax.conv_general_dilated_patches(
        xg.transpose(0, 3, 1, 2),  # NCHW for patch extraction
        filter_shape=(kh, kw),
        window_strides=(stride, stride),
        padding="VALID",
    )  # (N, Cin*Kh*Kw, Ho, Wo), features ordered (cin, kh, kw)
    cols = patches.transpose(0, 2, 3, 1).reshape(n * ho * wo, cin * kh * kw)
    if cast is not None:
        cols = cols.astype(cast)
    return cols, ho, wo


def conv_gemm_weights(w: jax.Array) -> jax.Array:
    """(K, K, Cin, Cout) conv weights -> (Cin*Kh*Kw, Cout) GEMM operand."""
    kh, kw, cin, cout = w.shape
    return w.transpose(2, 0, 1, 3).reshape(cin * kh * kw, cout)


# ---------------------------------------------------------------------------
# GEMM wrappers
# ---------------------------------------------------------------------------


def matmul_fp(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    relu: bool = False,
    qout: QFormat | None = None,
    block: MatmulBlock | None = None,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    m, k = x.shape
    n = w.shape[1]
    block = clamp_block(m, n, k, block or MatmulBlock(256, 256, 256))
    return matmul_fp_pallas(
        x, w, bias, block=block, relu=relu, qout=qout,
        vmem_limit_bytes=vmem_limit_bytes,
    )


def matmul_q16(
    xq: jax.Array,
    wq: jax.Array,
    *,
    bias: jax.Array | None = None,
    relu: bool = False,
    fmt: QFormat = Q2_14,
    shift: int | None = None,
    bias_shift: int | None = None,
    wide: bool = False,
    block: MatmulBlock | None = None,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    m, k = xq.shape
    n = wq.shape[1]
    block = clamp_block(m, n, k, block or MatmulBlock(256, 256, 256))
    return matmul_q16_pallas(
        xq, wq, bias, fmt=fmt, block=block, relu=relu, shift=shift,
        bias_shift=bias_shift, wide=wide, vmem_limit_bytes=vmem_limit_bytes,
    )


# ---------------------------------------------------------------------------
# conv wrappers (route chosen by the caller / engine)
# ---------------------------------------------------------------------------


def conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    stride: int = 1,
    padding: int = 0,
    tau: int = 128,
    relu: bool = False,
    qout: QFormat | None = None,
    route: str = "direct",
    block: MatmulBlock | None = None,
    tile_rows: int = 0,
    tile_cols: int = 0,
    halo_mode: str = "two_block",
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """NHWC conv on the unified compute unit, float path.

    route == "direct": the direct Pallas conv kernel — taps unrolled over the
    MXU, strided taps read strided slices of the resident image slab, and
    ``tile_rows`` / ``tile_cols`` > 0 tile the output (𝒯, ℭ) with
    halo-aware input fetches (``halo_mode``: blocked two-block reads or
    exact-window manual DMA) so oversized images stay on this route.
    route == "im2col": im2col + the Pallas matmul kernel — same unified-GEMM
    semantics; used when no direct (τ, tile_rows, tile_cols) config fits
    the VMEM budget (DESIGN.md §2).  Epilogue (bias/ReLU/quant) is fused on
    both routes.
    """
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    if route == "direct":
        return conv2d_pallas(
            x, w, bias, stride=stride, tau=tau, relu=relu, qout=qout,
            tile_rows=tile_rows, tile_cols=tile_cols, halo_mode=halo_mode,
            vmem_limit_bytes=vmem_limit_bytes,
        )
    assert route == "im2col", route
    n = x.shape[0]
    kh, kw, _, cout = w.shape
    cols, ho, wo = im2col(x, kh, kw, stride)
    out = matmul_fp(
        cols, conv_gemm_weights(w), bias=bias, relu=relu, qout=qout,
        block=block, vmem_limit_bytes=vmem_limit_bytes,
    )
    return out.reshape(n, ho, wo, cout)


def conv2d_q16(
    xq: jax.Array,
    wq: jax.Array,
    *,
    bias: jax.Array | None = None,
    stride: int = 1,
    padding: int = 0,
    tau: int = 128,
    relu: bool = False,
    fmt: QFormat = Q2_14,
    shift: int | None = None,
    bias_shift: int | None = None,
    route: str = "direct",
    block: MatmulBlock | None = None,
    tile_rows: int = 0,
    tile_cols: int = 0,
    halo_mode: str = "two_block",
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """NHWC conv, fixed-point path.  All tensors int16 raw Qm.n; ``shift`` /
    ``bias_shift`` carry mixed-format write-back gaps (see matmul_q16)."""
    if padding:
        xq = jnp.pad(xq, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    if route == "direct":
        return conv2d_q16_pallas(
            xq, wq, bias, stride=stride, tau=tau, relu=relu, fmt=fmt,
            shift=shift, bias_shift=bias_shift, tile_rows=tile_rows,
            tile_cols=tile_cols, halo_mode=halo_mode,
            vmem_limit_bytes=vmem_limit_bytes,
        )
    assert route == "im2col", route
    n = xq.shape[0]
    kh, kw, _, cout = wq.shape
    cols, ho, wo = im2col(xq, kh, kw, stride)
    out = matmul_q16(
        cols, conv_gemm_weights(wq), bias=bias, relu=relu, fmt=fmt,
        shift=shift, bias_shift=bias_shift, block=block,
        vmem_limit_bytes=vmem_limit_bytes,
    )
    return out.reshape(n, ho, wo, cout)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    bq: int = 256,
    bk: int = 256,
) -> jax.Array:
    """GQA-aware attention.  q: (B, Hq, Sq, D), k/v: (B, Hkv, Sk, D).

    The q-head group is folded into the query *rows* (not by repeating KV),
    so each kv head streams its KV exactly once: q is reshaped to
    (B*Hkv, G*Sq, D) with causal masking applied per original row index.
    For G > 1 with causal masks this needs per-row offsets, so we instead
    fold the group into the batch-head axis of q against *shared* kv blocks.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    # (B*Hkv*G, Sq, D) queries against (B*Hkv, Sk, D) kv, broadcast over G.
    qf = q.reshape(b, hkv, g, sq, d).reshape(b * hkv * g, sq, d)
    kf = jnp.broadcast_to(k[:, :, None], (b, hkv, g, sk, d)).reshape(b * hkv * g, sk, d)
    vf = jnp.broadcast_to(v[:, :, None], (b, hkv, g, sk, d)).reshape(b * hkv * g, sk, d)
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, q_offset=q_offset, bq=bq, bk=bk
    )
    return out.reshape(b, hq, sq, d)
