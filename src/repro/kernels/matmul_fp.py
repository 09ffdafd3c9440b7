"""The unified compute unit as a Pallas TPU kernel (float path).

This is the TPU realization of the paper's μ×τ dot-product array: a tiled
matmul where the BlockSpec tile (bm, bn, bk) plays the role of the paper's
loop-tiling factors and Pallas's revolving VMEM windows provide the
ping-pong double buffering (HBM->VMEM copies for grid step i+1 overlap the
MXU work of step i).

Grid layout: (m/bm, n/bn, k/bk) with the reduction axis innermost and marked
"arbitrary" (sequential) so the f32 VMEM scratch accumulator carries across
k-steps; m/n axes are "parallel".

The epilogue (bias add, ReLU, optional output fake-quantization to a Q
format) is fused into the final-k write-back so activations never round-trip
through HBM between the GEMM and the nonlinearity (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import QFormat
from repro.core.tiling import MatmulBlock

from .common import pallas

__all__ = ["matmul_fp_pallas"]


def _mm_kernel(*refs, relu, qout):
    # refs: (x, w[, bias], out, acc) — the bias operand only exists when the
    # caller fused one, so bias-free GEMMs pay nothing for the epilogue.
    if len(refs) == 5:
        x_ref, w_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs
        b_ref = None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: f32 operands contract at full f32 precision on the MXU (its
    # multi-pass mode) rather than rounded to one bf16 pass
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write_back():
        acc = acc_ref[...]
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)  # (1, bn) broadcast
        if relu:
            acc = jnp.maximum(acc, 0.0)
        if qout is not None:
            acc = jnp.clip(
                jnp.round(acc * qout.scale) / qout.scale, qout.min_val, qout.max_val
            )
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block", "relu", "qout", "out_dtype", "vmem_limit_bytes"),
)
def matmul_fp_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    block: MatmulBlock = MatmulBlock(256, 256, 256),
    relu: bool = False,
    qout: QFormat | None = None,
    out_dtype=None,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """x: (m, k) @ w: (k, n) -> (m, n). Pads to block multiples internally.

    ``bias``: (n,) fused into the last-k write-back; ``relu``/``qout``: fused
    nonlinearity and (fake-)quantization, applied after bias.
    ``vmem_limit_bytes``: the VMEM budget the block was planned against.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype

    bm, bn, bk = block.bm, block.bn, block.bk
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    operands = [x, w]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    if bias is not None:
        operands.append(jnp.pad(bias.astype(jnp.float32), (0, np_ - n)).reshape(1, np_))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))

    kernel = functools.partial(_mm_kernel, relu=relu, qout=qout)
    out = pallas(
        kernel,
        name="matmul_fp",
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes,
    )(*operands)
    return out[:m, :n]
