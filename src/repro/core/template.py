"""The paper's primary contribution: a single templated compute unit that
every GEMM-bearing layer routes through.

The paper computes "convolutional and FC layers operations in vector
multiplication on a single on-chip compute unit" (§I contributions).  Here
:class:`Template` is that compute unit for TPU: conv, FC, attention
projections, MLP, MoE expert FFNs and vocab projections all call
:meth:`Template.matmul`, which dispatches to one of three backends:

  * ``"xla"``    — `jnp.dot`; the lowering used inside pjit/shard_map programs
                   (the multi-pod dry-run plane).  XLA's own MXU tiling is the
                   production path on real TPUs for the distributed graph.
  * ``"pallas"`` — the hand-tiled Pallas kernels (`kernels/matmul_fp.py`,
                   `kernels/conv2d.py`) with BlockSpec tiles chosen by the
                   DSE (`core/dse.py`); compiled on a TPU, interpreted on
                   the CPU (`kernels/common.py`).
  * ``"q16"``    — the paper's 16-bit Q2.14 fixed-point numerics
                   (`kernels/matmul_q16.py`), for paper-faithful inference.

``Template`` is the stable API; the actual plan-then-execute machinery —
memoized DSE block selection, direct-conv vs im2col routing, fused epilogues
— lives in :class:`repro.core.engine.Engine` (DESIGN.md).  The template also
carries the quantization format and the tile configuration, mirroring the
paper's "pre-trained weights + target hardware specification -> optimized
template" flow.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from .quantization import QFormat, Q2_14
from .tiling import MatmulBlock, TpuSpec, device_spec

__all__ = ["Template", "TemplateConfig", "default_template"]

Backend = Literal["xla", "pallas", "q16"]


@dataclasses.dataclass(frozen=True)
class TemplateConfig:
    """Hardware-specification half of the template (paper abstract:
    'takes pre-trained weights ... and target hardware specification').

    ``hw`` defaults to the spec of the chip this process computes on
    (:func:`~repro.core.tiling.device_spec`); whether Pallas kernels run
    compiled or interpreted follows the platform too (kernels/common.py).
    """

    backend: Backend = "xla"
    block: Optional[MatmulBlock] = None  # None => DSE picks per-shape (plan-cached)
    qformat: QFormat = Q2_14
    hw: TpuSpec = dataclasses.field(default_factory=device_spec)
    #: GEMM output dtype; None = match the input dtype.  The TPU MXU
    #: accumulates bf16 products in f32 internally either way — requesting a
    #: bf16 *result* halves dot-output HBM traffic and lets the FSDP
    #: all-gathers / TP all-reduces ride the wire at 2 bytes instead of 4
    #: (§Perf iteration 1).  Set jnp.float32 to force f32 results.
    accum_dtype: Optional[jnp.dtype] = None


@dataclasses.dataclass(frozen=True)
class Template:
    config: TemplateConfig = dataclasses.field(default_factory=TemplateConfig)

    # -- the execution-plan engine -------------------------------------------

    @functools.cached_property
    def engine(self):
        """The execution engine for this config (shares the global plan cache)."""
        from .engine import Engine

        return Engine(self.config)

    def block_for(self, m: int, n: int, k: int) -> MatmulBlock:
        return self.engine.block_for(m, n, k)

    # -- fixed-point residency (QTensor boundary ops, DESIGN.md §8) ----------

    def quant(self, x, fmt: Optional[QFormat] = None):
        """Float -> QTensor on the activation grid (counted island exit)."""
        return self.engine.quant(x, fmt)

    def dequant(self, q, fmt: Optional[QFormat] = None, dtype=jnp.float32):
        """QTensor / raw int16 -> float (counted island entry)."""
        return self.engine.dequant(q, fmt, dtype)

    # -- the unified compute unit ---------------------------------------------

    def matmul(self, x: jax.Array, w: jax.Array, **kw) -> jax.Array:
        """``x @ w`` where x: (..., k), w: (k, n).

        Leading dims of ``x`` are flattened into the GEMM M dimension — this
        is exactly the paper's unification: conv patches, tokens, and FC
        neurons are all just rows of one matrix multiply.  Keyword args
        (``bias``/``relu``/``qout``/``plan``) are fused-epilogue and plan
        controls forwarded to the engine.
        """
        return self.engine.matmul(x, w, **kw)

    def linear(
        self, x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, **kw
    ) -> jax.Array:
        return self.engine.linear(x, w, b, **kw)

    def conv2d(
        self,
        x: jax.Array,
        w: jax.Array,
        stride: int = 1,
        padding: str | int = 0,
        **kw,
    ) -> jax.Array:
        """NHWC conv on the unified compute unit (paper Fig. 4).

        x: (N, H, W, Cin), w: (K, K, Cin, Cout) -> (N, Ho, Wo, Cout).
        The engine routes to the direct Pallas conv kernel or the im2col
        GEMM per its plan (DESIGN.md §2).
        """
        return self.engine.conv2d(x, w, stride=stride, padding=padding, **kw)


def default_template(backend: Backend = "xla", **kw) -> Template:
    return Template(TemplateConfig(backend=backend, **kw))
