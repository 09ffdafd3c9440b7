"""Loop-tiling transformation (paper §III.B) — tile legality and footprints.

Two planes:

* **FPGA plane** (paper-faithful): conv tiles (𝒯, ℭ, μ, τ) and FC tiles
  (λ, Ω) determine BRAM buffer footprints and the per-invocation fixed
  computation of the μ×τ compute unit.  Used by ``fpga_model`` and ``dse``.

* **TPU plane** (hardware adaptation): Pallas BlockSpec tiles (bm, bn, bk)
  determine the VMEM working set and MXU alignment.  Used by the Pallas
  kernels and the TPU-side DSE.

Both are *the same transformation* — convert variable layer loops into fixed
blocks sized to on-chip memory — instantiated for two memory hierarchies.
"""
from __future__ import annotations

import dataclasses
import math

__all__ = [
    "ConvTiling",
    "FCTiling",
    "MatmulBlock",
    "REHEARSAL_DEVICE_KIND",
    "TPU_SPECS",
    "TPU_V5E",
    "TpuSpec",
    "CONV_CHUNK_M",
    "ceil_div",
    "conv_chunk_rows",
    "device_spec",
    "dma_window_cols",
    "padded_bytes",
    "tpu_spec",
]


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# FPGA plane
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ConvTiling:
    """Conv loop-tiling factors (paper notation: 𝒯, ℭ, μ, τ)."""

    t_r: int  # output-row tile 𝒯
    t_c: int  # output-col tile ℭ
    mu: int  # input-channel tile μ  (compute-unit input width)
    tau: int  # output-channel tile τ (compute-unit output width)

    def eff_spatial(self, r: int, c: int) -> tuple[int, int]:
        """HLS templates bound the tile loop by min(tile, layer dim)."""
        return min(self.t_r, r), min(self.t_c, c)

    def num_invocations(self, r: int, c: int, p: int, q: int) -> int:
        """Tile invocations to cover an output of r x c x q from p channels."""
        tr, tc = self.eff_spatial(r, c)
        return (
            ceil_div(r, tr)
            * ceil_div(c, tc)
            * ceil_div(p, self.mu)
            * ceil_div(q, self.tau)
        )

    def compute_cycles_per_invocation(self, k: int, r: int = None, c: int = None) -> int:
        """Fig. 4 dataflow: one μ×τ MAC wave per (spatial, tap) position.

        II=1 pipeline over 𝒯'·ℭ'·K² positions (effective tile).
        """
        tr, tc = self.eff_spatial(r or self.t_r, c or self.t_c)
        return tr * tc * k * k

    def input_tile_elems(self, k: int, stride: int = 1) -> int:
        h = stride * self.t_r + k - stride
        w = stride * self.t_c + k - stride
        return h * w * self.mu

    def weight_tile_elems(self, k: int) -> int:
        return self.mu * self.tau * k * k

    def output_tile_elems(self) -> int:
        return self.t_r * self.t_c * self.tau


@dataclasses.dataclass(frozen=True)
class FCTiling:
    """FC loop-tiling factors (paper notation: λ, Ω) over the same μ×τ unit.

    λ/Ω are the BRAM-resident vector tiles; the compute unit consumes them in
    (μ, τ) sub-blocks (paper Fig. 5).
    """

    lam: int  # input-neuron tile λ
    omega: int  # output-neuron tile Ω
    mu: int
    tau: int

    def num_invocations(self, p: int, q: int) -> int:
        return ceil_div(p, self.lam) * ceil_div(q, self.omega)

    def compute_cycles_per_invocation(self) -> int:
        # (λ/μ)·(Ω/τ) sub-blocks, each one MAC wave per μ-element column.
        return ceil_div(self.lam, self.mu) * ceil_div(self.omega, self.tau)

    def input_tile_elems(self) -> int:
        return self.lam

    def weight_tile_elems(self) -> int:
        return self.lam * self.omega

    def output_tile_elems(self) -> int:
        return self.omega


# ---------------------------------------------------------------------------
# TPU plane
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TpuSpec:
    """Per-chip TPU hardware description used by tiling/DSE/roofline.

    ``vmem_bytes`` is the VMEM budget the DSE tiles against *and* the
    ``vmem_limit_bytes`` every planned kernel is compiled with, so the plan
    the compiler sees is the plan the DSE chose.
    """

    name: str = "tpu_v5e"
    peak_bf16_flops: float = 197e12  # FLOP/s
    hbm_bw: float = 819e9  # bytes/s
    ici_bw: float = 50e9  # bytes/s per link
    vmem_bytes: int = 64 * 1024 * 1024  # usable VMEM budget we tile against
    mxu_dim: int = 128  # systolic array edge
    lane: int = 128  # last-dim register lane count
    sublane: int = 8  # second-minor dim granularity (f32)
    # the skinny-M GEMM model (core/dse.py), fitted to a chip sweep of the
    # GEMM kernels (benchmarks/skinny_gemm_sweep.py): the fixed cost of one
    # Pallas grid step, and the q16 kernel's work per weight element at
    # M = 8 (int_dot's digit dots and splits; each weight tile is latched
    # in the MXU for a few rows)
    grid_step_s: float = 0.25e-6
    skinny_weight_s: float = 2.7e-12


#: TPU v5e peaks: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s HBM).  VMEM: 128 MiB per core, of which the planner uses half.
TPU_V5E = TpuSpec()

#: Chip specs keyed by ``jax.Device.device_kind``.  A kind missing here is an
#: error (:func:`tpu_spec`), never a silent default.
TPU_SPECS = {"TPU v5 lite": TPU_V5E}

#: The chip a CPU process rehearses: it plans for this kind and interprets
#: the kernels (kernels/common.py).
REHEARSAL_DEVICE_KIND = "TPU v5 lite"


def tpu_spec(device_kind: str) -> TpuSpec:
    """The spec of one TPU device kind; raises for a kind the table lacks."""
    try:
        return TPU_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no TpuSpec for device kind {device_kind!r}; known kinds: "
            f"{sorted(TPU_SPECS)} (add its peaks and VMEM to TPU_SPECS)"
        ) from None


def device_spec(device=None) -> TpuSpec:
    """The spec of the chip a process computes on (default: the first JAX
    device).  A TPU is looked up by its device kind; a CPU process plans
    for :data:`REHEARSAL_DEVICE_KIND`, the chip its interpreted kernels
    stand in for.  Any other platform is an error."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return tpu_spec(REHEARSAL_DEVICE_KIND)
    if device.platform != "tpu":
        raise ValueError(f"no TPU plan target for platform {device.platform!r}")
    return tpu_spec(device.device_kind)


def padded_bytes(rows: int, cols: int, itemsize: int, spec: TpuSpec = TPU_V5E) -> int:
    """Bytes a (rows, cols) slab occupies on the chip: the minor dim pads to
    whole 128-lane tiles and the second-minor dim to whole sublane tiles
    (8 rows of 32-bit words; 16-bit and 8-bit values pack 2 and 4 rows per
    word, so their tiles are 16 and 32 rows).  A Cin=3 image costs 128 lanes."""
    sub = spec.sublane * max(1, 4 // itemsize)
    return (ceil_div(rows, sub) * sub) * (ceil_div(cols, spec.lane) * spec.lane) * itemsize


def dma_window_cols(cols: int, itemsize: int, spec: TpuSpec = TPU_V5E) -> int:
    """Columns a manual-DMA conv window copies: packed (16/8-bit) windows
    move whole 8-column groups, so their width rounds up to a multiple of
    8; 32-bit windows copy exactly ``cols``."""
    if itemsize >= 4:
        return cols
    return ceil_div(cols, spec.sublane) * spec.sublane


#: Output pixels (GEMM rows) one in-kernel chunk of the direct conv
#: contracts per tap (kernels/conv2d.py): it bounds the live tap operand and
#: accumulator values of a grid step whatever the tile size.
CONV_CHUNK_M = 256


def conv_chunk_rows(th: int, tw: int) -> int:
    """Output rows per in-kernel chunk of a (th, tw) conv tile: the largest
    divisor of ``th`` whose ``rows × tw`` GEMM stays within
    :data:`CONV_CHUNK_M` (at least one row)."""
    best = 1
    for r in range(1, th + 1):
        if th % r == 0 and r * tw <= CONV_CHUNK_M:
            best = r
    return best


@dataclasses.dataclass(frozen=True)
class MatmulBlock:
    """Pallas BlockSpec tile for the unified matmul compute unit.

    This is the TPU analogue of the paper's (μ, τ) compute-unit config:
    ``bm`` plays μ's role (inputs consumed per wave), ``bn`` plays τ's
    (outputs produced per wave), ``bk`` is the reduction tile streamed from
    HBM (the paper streams K² taps).
    """

    bm: int = 512
    bn: int = 512
    bk: int = 512

    def vmem_bytes(self, in_dtype_bytes: int = 2, acc_bytes: int = 4) -> int:
        # x-tile + w-tile (double-buffered by the Pallas pipeline: x2) +
        # f32 accumulator + output tile.
        x = self.bm * self.bk * in_dtype_bytes * 2
        w = self.bk * self.bn * in_dtype_bytes * 2
        acc = self.bm * self.bn * acc_bytes
        out = self.bm * self.bn * in_dtype_bytes * 2
        return x + w + acc + out

    def aligned(self, spec: TpuSpec = TPU_V5E) -> bool:
        return (
            self.bm % spec.sublane == 0
            and self.bn % spec.lane == 0
            and self.bk % spec.lane == 0
        )

    def mxu_efficiency(self, spec: TpuSpec = TPU_V5E) -> float:
        """Fraction of MXU issue slots doing useful work for this tile."""

        def frac(dim: int) -> float:
            return dim / (ceil_div(dim, spec.mxu_dim) * spec.mxu_dim)

        return frac(self.bm) * frac(self.bn) * frac(self.bk)

    def arithmetic_intensity(self, in_dtype_bytes: int = 2) -> float:
        """FLOPs per HBM byte for one grid step (higher = more compute bound)."""
        flops = 2 * self.bm * self.bn * self.bk
        bytes_moved = (self.bm * self.bk + self.bk * self.bn) * in_dtype_bytes
        return flops / bytes_moved

    def legal(self, m: int, n: int, k: int, spec: TpuSpec = TPU_V5E) -> bool:
        return (
            self.aligned(spec)
            and self.vmem_bytes() <= spec.vmem_bytes
            and self.bm <= max(m, spec.sublane)
            and self.bn <= max(n, spec.lane)
            and self.bk <= max(k, spec.lane)
        )


def clamp_block(m: int, n: int, k: int, block: MatmulBlock, spec: TpuSpec = TPU_V5E) -> MatmulBlock:
    """Shrink a block to fit a (possibly small) problem, keeping alignment."""

    def shrink(dim: int, b: int, gran: int) -> int:
        b = min(b, max(gran, math.ceil(dim / gran) * gran))
        return max(gran, b - b % gran)

    return MatmulBlock(
        bm=shrink(m, block.bm, spec.sublane),
        bn=shrink(n, block.bn, spec.lane),
        bk=shrink(k, block.bk, spec.lane),
    )
