"""The paper's fixed-point compute unit as a Pallas kernel (int16 and int8).

int16/int8 x int16/int8 products accumulated in int32 (the MXU's int8
digit products, :func:`repro.kernels.common.int_dot`; TPU-native
accumulator width; the FPGA DSP48 cascade is 48-bit — difference documented
in DESIGN.md §2), then a saturating round-shift write-back onto the output
format's storage rung (Q2.14 int16, Q1.7/Q2.6 int8, ...), exactly matching
``repro.core.quantization.qmatmul_ref`` / ``qtensor_matmul_ref``.  Mixed
operand widths are legal — each side splits into as many int8 digits as
its width needs — and an int8-rung ``fmt`` with an int16-grid accumulator shift *is* the
mixed-boundary epilogue (DESIGN.md §11): the layer writes its successor's
grid directly, no float hop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import QFormat, Q2_14, shift_saturate_i32
from repro.core.tiling import MatmulBlock

from .common import int_dot, pallas

__all__ = ["matmul_q16_pallas"]


def _qmm_kernel(*refs, shift, bias_shift, raw_min, raw_max, relu, wide,
                out_dtype):
    # refs: (x, w[, bias], out, acc) — bias operand only present when fused.
    if len(refs) == 5:
        x_ref, w_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs
        b_ref = None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += int_dot(x_ref[...], w_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write_back():
        # bias raw (Qc.fc) aligns onto the accumulator scale 2^(fa+fb) by
        # bias_shift = fa+fb-fc, so the shifted add is bit-identical to
        # adding raw bias post-shift (fused epilogue, DESIGN.md §3/§8).
        acc = acc_ref[...]
        if b_ref is not None:
            acc = acc + (b_ref[...].astype(jnp.int32) << bias_shift)
        if relu:
            acc = jnp.maximum(acc, 0)
        if wide:
            # accumulator read-out (final logits boundary): no requantize —
            # the caller descales by 2^-(fa+fb) exactly, so the head never
            # saturates on logits outside the int16 grid's range.
            o_ref[...] = acc
            return
        o_ref[...] = shift_saturate_i32(acc, shift, raw_min, raw_max,
                                        out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fmt", "block", "relu", "shift", "bias_shift", "wide", "vmem_limit_bytes",
    ),
)
def matmul_q16_pallas(
    xq: jax.Array,
    wq: jax.Array,
    bias: jax.Array | None = None,
    *,
    fmt: QFormat = Q2_14,
    block: MatmulBlock = MatmulBlock(256, 256, 256),
    relu: bool = False,
    shift: int | None = None,
    bias_shift: int | None = None,
    wide: bool = False,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """xq: (m, k) raw @ wq: (k, n) raw -> (m, n) raw on ``fmt``'s rung.

    Operands are int16 or int8 raws (mixed widths are fine — the product is
    exact int32 either way) and the output is stored as ``fmt.storage_dtype``.
    ``bias``: (n,) int16/int8 raw, fused into the write-back; ``relu``:
    fused on the int32 accumulator before the saturating shift.  ``shift`` /
    ``bias_shift`` override the write-back scale gaps for mixed-format
    operands (default: same-format semantics, one ``fmt.frac_bits`` each);
    ``wide=True`` returns the raw int32 accumulator (no requantize) for the
    final-layer read-out.  ``vmem_limit_bytes``: the VMEM budget the block
    was planned against.
    """
    assert xq.dtype in (jnp.int8, jnp.int16) and wq.dtype in (jnp.int8, jnp.int16)
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2

    bm, bn, bk = block.bm, block.bn, block.bk
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk
    if (mp, kp) != (m, k):
        xq = jnp.pad(xq, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        wq = jnp.pad(wq, ((0, kp - k), (0, np_ - n)))
    operands = [xq, wq]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    if bias is not None:
        operands.append(jnp.pad(bias.astype(jnp.int16), (0, np_ - n)).reshape(1, np_))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))

    kernel = functools.partial(
        _qmm_kernel,
        shift=fmt.frac_bits if shift is None else shift,
        bias_shift=fmt.frac_bits if bias_shift is None else bias_shift,
        raw_min=fmt.raw_min,
        raw_max=fmt.raw_max,
        relu=relu,
        wide=wide,
        out_dtype=fmt.storage_dtype,
    )
    out = pallas(
        kernel,
        name="matmul_q16",
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (mp, np_), jnp.int32 if wide else fmt.storage_dtype
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes,
    )(*operands)
    return out[:m, :n]
