"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantization import (
    QFormat,
    Q2_14,
    QTensor,
    qmatmul_ref as _qmatmul_core,
    requantize_i32,
    requantize_i32_to_i16,
)

__all__ = [
    "matmul_ref",
    "matmul_fused_ref",
    "matmul_q16_ref",
    "matmul_q16_fused_ref",
    "conv2d_ref",
    "conv2d_fused_ref",
    "conv2d_q16_ref",
    "conv2d_qtensor_ref",
    "attention_ref",
]


def _fake_quant(x: jax.Array, fmt: QFormat) -> jax.Array:
    return jnp.clip(jnp.round(x * fmt.scale) / fmt.scale, fmt.min_val, fmt.max_val)


def matmul_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """f32-accumulated matmul, output in x.dtype."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def matmul_fused_ref(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    relu: bool = False,
    qout: QFormat | None = None,
) -> jax.Array:
    """Oracle for the float GEMM with fused epilogue (bias -> ReLU -> quant)."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    if qout is not None:
        y = _fake_quant(y, qout)
    return y.astype(x.dtype)


def matmul_q16_ref(xq: jax.Array, wq: jax.Array, fmt: QFormat = Q2_14) -> jax.Array:
    """int16 raw x int16 raw -> int16 raw (int32 accumulate, saturating shift)."""
    return _qmatmul_core(xq, wq, fmt)


def matmul_q16_fused_ref(
    xq: jax.Array,
    wq: jax.Array,
    bq: jax.Array | None = None,
    *,
    fmt: QFormat = Q2_14,
    relu: bool = False,
) -> jax.Array:
    """Fixed-point GEMM oracle with fused epilogue on the int32 accumulator."""
    acc = jnp.dot(
        xq.astype(jnp.int32), wq.astype(jnp.int32), preferred_element_type=jnp.int32
    )
    if bq is not None:
        acc = acc + (bq.astype(jnp.int32) << fmt.frac_bits)
    if relu:
        acc = jnp.maximum(acc, 0)
    return requantize_i32_to_i16(acc, fmt)


def conv2d_ref(x: jax.Array, w: jax.Array, stride: int = 1, padding: int = 0) -> jax.Array:
    """NHWC conv oracle via lax.conv_general_dilated.

    x: (N,H,W,Cin), w: (K,K,Cin,Cout).
    """
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ).astype(x.dtype)


def conv2d_fused_ref(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    stride: int = 1,
    padding: int = 0,
    relu: bool = False,
    qout: QFormat | None = None,
) -> jax.Array:
    """Conv oracle with fused epilogue (bias -> ReLU -> fake-quant)."""
    y = conv2d_ref(x, w, stride=stride, padding=padding).astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    if qout is not None:
        y = _fake_quant(y, qout)
    return y.astype(x.dtype)


def conv2d_q16_ref(
    xq: jax.Array,
    wq: jax.Array,
    bq: jax.Array | None = None,
    *,
    fmt: QFormat = Q2_14,
    stride: int = 1,
    padding: int = 0,
    relu: bool = False,
) -> jax.Array:
    """Fixed-point conv oracle: exact int32 tap-loop accumulation.

    xq: (N,H,W,Cin) int16 raw, wq: (K,K,Cin,Cout) int16 raw -> int16 raw.
    """
    if padding:
        xq = jnp.pad(xq, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    acc = _conv_i32(xq, wq, stride)
    if bq is not None:
        acc = acc + (bq.astype(jnp.int32) << fmt.frac_bits)
    if relu:
        acc = jnp.maximum(acc, 0)
    return requantize_i32_to_i16(acc, fmt)


def _conv_i32(xq: jax.Array, wq: jax.Array, stride: int) -> jax.Array:
    """Exact int32 VALID conv accumulator by the tap loop (no padding)."""
    n, h, wd, cin = xq.shape
    kh, kw, _, cout = wq.shape
    ho = (h - kh) // stride + 1
    wo = (wd - kw) // stride + 1
    acc = jnp.zeros((n, ho, wo, cout), jnp.int32)
    for i in range(kh):
        for j in range(kw):
            patch = xq[
                :,
                i : i + stride * (ho - 1) + 1 : stride,
                j : j + stride * (wo - 1) + 1 : stride,
                :,
            ].astype(jnp.int32)
            acc = acc + jnp.einsum(
                "nhwc,cd->nhwd", patch, wq[i, j].astype(jnp.int32)
            )
    return acc


def conv2d_qtensor_ref(
    x: QTensor,
    w: QTensor,
    out_fmt: QFormat,
    bias: QTensor | None = None,
    *,
    stride: int = 1,
    padding: int = 0,
    relu: bool = False,
) -> QTensor:
    """Mixed-format oracle for the grid-resident conv (DESIGN.md §8) — the
    conv twin of :func:`repro.core.quantization.qtensor_matmul_ref`.

    x: (N,H,W,Cin) Qa.fa, w: (K,K,Cin,Cout) Qb.fb -> (N,Ho,Wo,Cout) on
    ``out_fmt``: exact int32 tap-loop accumulation at scale 2^(fa+fb), the
    raw bias aligned onto it by ``fa + fb - fc``, ReLU on the accumulator,
    then the saturating round-shift write-back.
    """
    xq = x.raw
    if padding:
        xq = jnp.pad(xq, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    acc = _conv_i32(xq, w.raw, stride)
    acc_frac = x.fmt.frac_bits + w.fmt.frac_bits
    if bias is not None:
        acc = acc + (bias.raw.astype(jnp.int32) << (acc_frac - bias.fmt.frac_bits))
    if relu:
        acc = jnp.maximum(acc, 0)
    return QTensor(requantize_i32(acc, acc_frac - out_fmt.frac_bits, out_fmt), out_fmt)


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    q_offset: int = 0,
) -> jax.Array:
    """Dense softmax attention oracle.  q: (BH, Sq, D), k/v: (BH, Sk, D)."""
    sq, sk = q.shape[1], k.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        rows = q_offset + jnp.arange(sq)[:, None]
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(rows >= cols, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
