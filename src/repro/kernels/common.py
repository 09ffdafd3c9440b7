"""What every Pallas kernel of this package shares: the launch and the
integer dot.

:func:`pallas` is the one ``pallas_call`` site.  Whether a kernel is
compiled by Mosaic or interpreted is not an option: it follows the platform
the computation is lowered for (``jax.lax.platform_dependent``), so a TPU
always runs the compiled kernel and a CPU (tests, rehearsals) always runs
the interpreter.  Lowering for any other platform is an error.  The
compiler parameters (grid-axis semantics and the VMEM limit the DSE planned
against) travel with every kernel.

:func:`int_dot` is the fixed-point compute unit's MXU contraction.  The
TPU's MXU multiplies int8 operands into an int32 accumulator but has no
int16 or int32 matmul, so int16 operands are split into int8 digits and the
partial products recombined in int32 — exact (mod 2^32, which is also how
the int32 reference accumulator wraps).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int_dot", "pallas"]


def pallas(
    kernel,
    *,
    name: str,
    grid: tuple,
    in_specs,
    out_specs,
    out_shape,
    dimension_semantics: Sequence[str],
    scratch_shapes=(),
    vmem_limit_bytes: Optional[int] = None,
):
    """``pallas_call`` compiled on a TPU, interpreted on the CPU.

    ``dimension_semantics`` marks each grid axis "parallel" or "arbitrary"
    (sequential: an accumulator or a prefetch carries across it);
    ``vmem_limit_bytes`` is the planner's VMEM budget (None: the compiler's
    default scoped limit, for kernels no DSE plans).  ``name`` names the
    kernel in HLO and in profiler traces.
    """
    params = pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes,
    )

    def call(interpret: bool):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=params,
            interpret=interpret,
            name=name,
        )

    def run(*operands):
        return jax.lax.platform_dependent(
            *operands, cpu=call(True), tpu=call(False)
        )

    return run


def _int8_digits(v):
    """int8/int16 -> ([(int8 digit, shift), ...], offset) such that
    ``v == sum(digit << shift) + offset`` elementwise.

    int16 splits as ``256·hi + lo + 128`` with ``hi = v >> 8`` and
    ``lo = (v & 255) − 128``, both in int8 range for every int16 value.
    """
    if v.dtype == jnp.int8:
        return [(v, 0)], 0
    v32 = v.astype(jnp.int32)
    hi = (v32 >> 8).astype(jnp.int8)
    lo = ((v32 & 0xFF) - 128).astype(jnp.int8)
    return [(hi, 8), (lo, 0)], 128


def _wrap_i32(v: int) -> np.int32:
    return np.int32((v + 2**31) % 2**32 - 2**31)


def int_dot(a, b):
    """Exact int32 ``a @ b`` for int8/int16 operands on the int8 MXU.

    With ``a = A + oa`` and ``b = B + ob`` (A, B the int8-digit sums of
    :func:`_int8_digits`), ``Σ_k a·b = Σ_k A·B + ob·Σ_k a + oa·Σ_k b −
    K·oa·ob``: one int8 matmul per digit pair (1, 2 or 4), plus a row sum
    of ``a`` and a column sum of ``b`` when the other side carries an
    offset.  All terms are int32 and wrap mod 2^32 like the accumulator
    they reproduce.
    """
    da, oa = _int8_digits(a)
    db, ob = _int8_digits(b)
    acc = None
    for pa, sa in da:
        for pb, sb in db:
            d = jnp.dot(pa, pb, preferred_element_type=jnp.int32)
            if sa + sb:
                d = d << (sa + sb)
            acc = d if acc is None else acc + d
    if ob:
        acc = acc + ob * jnp.sum(a.astype(jnp.int32), axis=1, keepdims=True)
    if oa:
        acc = acc + oa * jnp.sum(b.astype(jnp.int32), axis=0, keepdims=True)
    if oa and ob:
        acc = acc - _wrap_i32(oa * ob * a.shape[1])
    return acc
