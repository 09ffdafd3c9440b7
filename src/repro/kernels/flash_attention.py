"""Streaming-softmax (flash) attention Pallas kernel — the prefill hot spot.

The attention score/value GEMMs are the dominant non-projection compute at
prefill_32k; this kernel keeps the running-max/denominator online-softmax
state and the output accumulator in VMEM while streaming KV blocks from HBM
(the same ping-pong structure as the matmul unit, applied to attention).

Layout: q/k/v are (BH, S, D) with batch*heads folded into the grid's first
(parallel) axis; GQA is handled in ops.py by folding the q-head group into
the query rows, so KV is never materialized per-q-head.

Grid: (BH, Sq/bq, Sk/bk), kv axis innermost/sequential.  Causal masking
compares global row/col indices; fully-masked kv blocks are skipped via
pl.when (no MXU work, no softmax update).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import pallas

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, bq, bk, scale, causal, q_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal block skip: the first kv row of this block vs the last q row.
    q_last = q_offset + (qi + 1) * bq - 1
    k_first = ki * bk
    live = (not causal) or (k_first <= q_last)

    @pl.when(live)
    def _update():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            rows = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _write_back():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "q_offset")
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 256,
    q_offset: int = 0,
) -> jax.Array:
    """q: (BH, Sq, D), k/v: (BH, Sk, D) -> (BH, Sq, D).

    ``q_offset`` is the global position of q row 0 (for decode-with-cache the
    query sits at the end of the key sequence).
    """
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    bq = min(bq, sq)
    bk = min(bk, sk)
    sqp, skp = -(-sq // bq) * bq, -(-sk // bk) * bk
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0)))
    if skp != sk:
        # padded kv columns are masked off via the causal/row-col comparison
        # only when causal; for non-causal we mask via a length guard below.
        k = jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0)))
        if not causal:
            raise ValueError("non-causal flash kernel requires sk % bk == 0")

    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _fa_kernel, bq=bq, bk=bk, scale=scale, causal=causal, q_offset=q_offset
    )
    out = pallas(
        kernel,
        name="flash_attention",
        grid=(bh, sqp // bq, skp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        # the streaming softmax carries (m, l, acc) across the kv axis
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(q, k, v)
    return out[:, :sq, :]
