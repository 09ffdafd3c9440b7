"""Pallas TPU kernels for the compute hot spots the paper optimizes.

- matmul_fp.py        the unified mu x tau compute unit, float path
- matmul_q16.py       the paper's Q2.14 fixed-point path
- conv2d.py           direct conv (float + q16) on the same unit (paper Fig. 4)
- flash_attention.py  streaming-softmax attention (prefill hot spot)
- common.py           the shared launch (compiled vs interpreted by platform)
                      and the exact int8-digit integer dot
- ops.py              public jit'd wrappers (im2col, GQA folding, routes)
- ref.py              pure-jnp oracles

All kernels fuse the layer epilogue (bias / ReLU / output quantization) into
the accumulator write-back; route selection between the direct conv kernel
and the im2col GEMM is the execution-plan engine's job (core/engine.py,
DESIGN.md).

Kernels target TPU (pallas_call + BlockSpec, MXU-aligned tiles).  Each is
compiled by Mosaic when lowered for a TPU and interpreted when lowered for
the CPU (common.py), which is how the tests validate them.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
