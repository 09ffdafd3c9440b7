"""Design-space exploration (paper §III.E "Scalability and Efficiency").

The paper uses trial-based exploration: sample template parameters, simulate,
keep configurations that meet resources/latency.  We make the same search
analytic and exhaustive over a quantized grid:

* :func:`explore_board` — FPGA plane: enumerate (μ, τ, 𝒯, ℭ, λ, Ω) within a
  board's DSP/BRAM/LUT/FF envelope and rank by modeled GOP/s on a target
  network.  Reproduces the paper's per-board compute-unit choices and the
  "τ ≈ 2μ" finding.

* :func:`explore_tpu_block` — TPU plane: enumerate Pallas (bm, bn, bk) blocks
  within the VMEM budget and rank by a roofline score (MXU occupancy ×
  min(1, intensity/ridge)).  This picks the compute-unit configuration the
  Pallas kernels use.  A skinny M (below one MXU edge: an FC head at batch
  1-8, a decode step) takes :func:`explore_skinny_block` instead: one
  sublane-rounded row block, a reduction tile set by K alone, and output
  tiles that divide N, ranked by modeled time — grid steps × (fixed
  per-step cost + the longer of the tile's copy and its MXU/VPU work) plus
  the unoverlapped first copy and last work, with constants from a chip
  sweep (``TpuSpec.grid_step_s``, ``TpuSpec.skinny_weight_s``).

* :func:`explore_conv_spatial` — TPU plane, direct conv: enumerate the
  direct-conv kernel's (τ, tile_rows, tile_cols, halo_mode) grid —
  output-channel tile × the paper's 𝒯/ℭ spatial tiles × input-halo regime
  (untiled / two-block / manual-DMA) — inside the VMEM working-set model
  (:func:`direct_conv_vmem`) and rank by the HBM-traffic score
  (:func:`direct_conv_hbm_traffic`).  This is what lets oversized layers
  (ZynqNet-style large early-layer feature maps) stay on the direct route
  instead of spilling to im2col.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Optional, Sequence

from .fpga_model import Board, LayerSpec, TemplateInstance, evaluate_network
from .tiling import (
    ConvTiling,
    FCTiling,
    MatmulBlock,
    TPU_V5E,
    TpuSpec,
    ceil_div,
    conv_chunk_rows,
    dma_window_cols,
    padded_bytes,
)

__all__ = [
    "DseResult",
    "ConvTileChoice",
    "choose_precision",
    "conv_choice_from_doc",
    "conv_choice_to_doc",
    "explore_board",
    "explore_tpu_block",
    "explore_conv_spatial",
    "default_block_for",
    "default_conv_tile_for",
    "explore_skinny_block",
    "gemm_vmem_bytes",
    "skinny_m",
    "skinny_time_s",
    "direct_conv_vmem",
    "direct_conv_hbm_traffic",
    "direct_conv_ideal_traffic",
    "direct_conv_input_traffic",
]


@dataclasses.dataclass
class DseResult:
    instance: TemplateInstance
    gops: float
    latency_ms: float

    @property
    def mu(self) -> int:
        return self.instance.conv.mu

    @property
    def tau(self) -> int:
        return self.instance.conv.tau


def explore_board(
    board: Board,
    layers: Sequence[LayerSpec],
    name: str = "net",
    mu_range: Sequence[int] = (4, 8, 12, 16, 20, 24, 28, 32),
    tau_range: Sequence[int] = (8, 12, 16, 20, 24, 30, 36, 44, 55, 64),
    spatial_tiles: Sequence[int] = (13, 14, 26, 27, 28),
    fc_tiles: Sequence[tuple[int, int]] = ((1024, 64), (2048, 128), (4096, 256)),
    top: int = 10,
) -> list[DseResult]:
    """Exhaustive grid search over the template parameter space for a board."""
    results: list[DseResult] = []
    for mu, tau in itertools.product(mu_range, tau_range):
        if mu * tau > board.dsp:
            continue
        for t_spatial in spatial_tiles:
            conv = ConvTiling(t_r=t_spatial, t_c=t_spatial, mu=mu, tau=tau)
            for lam, omega in fc_tiles:
                fc = FCTiling(lam=lam, omega=omega, mu=mu, tau=tau)
                inst = TemplateInstance(board=board, conv=conv, fc=fc)
                if not inst.fits():
                    continue
                rep = evaluate_network(name, layers, inst)
                results.append(
                    DseResult(instance=inst, gops=rep.gops, latency_ms=rep.latency_ms)
                )
    results.sort(key=lambda r: -r.gops)
    return results[:top]


# ---------------------------------------------------------------------------
# TPU plane
# ---------------------------------------------------------------------------


def _block_score(
    block: MatmulBlock, m: int, n: int, k: int, spec: TpuSpec, dtype_bytes: int = 2
) -> float:
    """Roofline score for one grid step of the tiled matmul.

    peak-normalized throughput = MXU efficiency x min(1, AI / ridge) x
    quantization-waste factor from ceil-division of the problem dims
    (the TPU analogue of the paper's ceil(p/μ)·ceil(q/τ) waste).
    """
    ridge = spec.peak_bf16_flops / spec.hbm_bw  # FLOP/byte to be compute bound
    ai = block.arithmetic_intensity(dtype_bytes)
    waste = (
        (m / (max(1, -(-m // block.bm)) * block.bm))
        * (n / (max(1, -(-n // block.bn)) * block.bn))
        * (k / (max(1, -(-k // block.bk)) * block.bk))
    )
    return block.mxu_efficiency(spec) * min(1.0, ai / ridge) * waste


def explore_tpu_block(
    m: int,
    n: int,
    k: int,
    spec: TpuSpec = TPU_V5E,
    dtype_bytes: int = 2,
    bm_range: Sequence[int] = (128, 256, 512, 1024),
    bn_range: Sequence[int] = (128, 256, 512, 1024, 2048),
    bk_range: Sequence[int] = (128, 256, 512, 1024, 2048),
    top: int = 5,
) -> list[tuple[MatmulBlock, float]]:
    """Enumerate legal Pallas blocks for an (m, n, k) GEMM; rank by score.

    A skinny M (:func:`skinny_m`) takes :func:`explore_skinny_block`
    instead, whose score is the weight-streaming floor over the modeled
    time; the ranges apply to the large-M search only.
    """
    if skinny_m(m, spec):
        return explore_skinny_block(m, n, k, spec, dtype_bytes, top)
    out: list[tuple[MatmulBlock, float]] = []
    for bm, bn, bk in itertools.product(bm_range, bn_range, bk_range):
        block = MatmulBlock(bm=bm, bn=bn, bk=bk)
        if not block.legal(m, n, k, spec):
            continue
        out.append((block, _block_score(block, m, n, k, spec, dtype_bytes)))
    out.sort(key=lambda t: -t[1])
    return out[:top]


def default_block_for(m: int, n: int, k: int, spec: TpuSpec = TPU_V5E) -> MatmulBlock:
    """Best-scoring legal block, with a safe fallback for tiny problems."""
    ranked = explore_tpu_block(m, n, k, spec)
    if ranked:
        return ranked[0][0]
    from .tiling import clamp_block

    return clamp_block(m, n, k, MatmulBlock(128, 128, 128), spec)


# ---------------------------------------------------------------------------
# TPU plane: skinny-M GEMMs (an FC head at batch 1-8, a decode step)
# ---------------------------------------------------------------------------

#: Deepest reduction tile of a skinny-M block.  The chip sweep behind the
#: model (PERF.md §6) found no gain from deeper tiles at equal tile size,
#: and a bk set by K alone keeps every GEMM over one K accumulating in one
#: order whatever its M and N: a GEMM sharded over N stays bitwise equal to
#: the whole one (DESIGN.md §9).
SKINNY_BK_MAX = 1024

#: Bytes of in-kernel temporaries per operand element, the larger of the
#: two GEMM kernels': ``int_dot`` widens an int16 tile to int32 (4) and
#: splits it into two int8 digits (2); the float kernel's HIGHEST dot
#: splits each f32 into three bf16 terms (6).
GEMM_TEMP_BYTES = 6


def skinny_m(m: int, spec: TpuSpec = TPU_V5E) -> bool:
    """M below one MXU edge: the GEMM streams every weight once for a few
    rows, so its time is the weight stream plus a fixed cost per grid
    step, and its blocks are planned by :func:`explore_skinny_block`."""
    return m < spec.mxu_dim


def _lane_divisors(dim: int, spec: TpuSpec = TPU_V5E) -> list[int]:
    """Lane-aligned tiles that divide ``dim`` rounded up to whole lanes,
    smallest first: a kernel pads its operand to a multiple of the tile,
    so with one of these it pads no more than the lane round-up (none
    when ``dim`` is lane-aligned, 1000 -> 1024 otherwise)."""
    units = ceil_div(dim, spec.lane)
    return [spec.lane * d for d in range(1, units + 1) if units % d == 0]


def gemm_vmem_bytes(block: MatmulBlock, spec: TpuSpec = TPU_V5E) -> int:
    """VMEM of one grid step of either GEMM kernel at 4-byte operands, as
    the chip lays them out: the double-buffered x, weight, bias and output
    tiles, the int32/f32 accumulator, and :data:`GEMM_TEMP_BYTES` per x
    and weight element.  The plan registry keys a block by shape alone, so
    the q16 and float kernels share it and it must fit the wider one."""
    bm, bn, bk = block.bm, block.bn, block.bk
    tiles = 2 * (padded_bytes(bm, bk, 4, spec) + padded_bytes(bk, bn, 4, spec)
                 + padded_bytes(1, bn, 4, spec) + padded_bytes(bm, bn, 4, spec))
    acc = padded_bytes(bm, bn, 4, spec)
    return tiles + acc + (bm * bk + bk * bn) * GEMM_TEMP_BYTES


def skinny_time_s(
    block: MatmulBlock, m: int, n: int, k: int, spec: TpuSpec = TPU_V5E,
    dtype_bytes: int = 2,
) -> float:
    """Modeled seconds of a skinny-M GEMM on ``block``: every grid step
    costs the fixed ``spec.grid_step_s`` plus the longer of its tile's copy
    from HBM and its MXU + VPU work (the pipeline overlaps the two), and
    the first tile's copy and the last tile's work, which nothing
    overlaps, are paid once more."""
    steps = ceil_div(m, block.bm) * ceil_div(n, block.bn) * ceil_div(k, block.bk)
    copy_s = (block.bm * block.bk + block.bk * block.bn) * dtype_bytes / spec.hbm_bw
    work_s = block.bk * block.bn * spec.skinny_weight_s
    return steps * (spec.grid_step_s + max(copy_s, work_s)) + copy_s + work_s


def _skinny_bk(k: int, spec: TpuSpec = TPU_V5E) -> int:
    """The reduction tile of a skinny-M block: the largest of
    :func:`_lane_divisors` of K not above :data:`SKINNY_BK_MAX`."""
    return max(d for d in _lane_divisors(k, spec) if d <= SKINNY_BK_MAX)


def explore_skinny_block(
    m: int, n: int, k: int, spec: TpuSpec = TPU_V5E, dtype_bytes: int = 2,
    top: int = 5,
) -> list[tuple[MatmulBlock, float]]:
    """Blocks for a skinny-M GEMM, ranked by :func:`skinny_time_s`.

    ``bm`` is M rounded up to a sublane and ``bk`` is :func:`_skinny_bk`
    of K; ``bn`` ranges over :func:`_lane_divisors` of N, so the kernel
    never pads the weight beyond the lane round-up, within
    :func:`gemm_vmem_bytes` <= the VMEM budget.  The score is the
    weight-streaming floor (K·N weights over HBM bandwidth) over the
    modeled time.
    """
    bm = ceil_div(m, spec.sublane) * spec.sublane
    bk = _skinny_bk(k, spec)
    floor_s = k * n * dtype_bytes / spec.hbm_bw
    out: list[tuple[MatmulBlock, float]] = []
    for bn in _lane_divisors(n, spec):
        block = MatmulBlock(bm=bm, bn=bn, bk=bk)
        if gemm_vmem_bytes(block, spec) > spec.vmem_bytes:
            continue
        out.append((block, floor_s / skinny_time_s(block, m, n, k, spec, dtype_bytes)))
    out.sort(key=lambda t: -t[1])
    return out[:top]


# ---------------------------------------------------------------------------
# TPU plane: direct-conv spatial tiling (the paper's 𝒯/ℭ tiles)
# ---------------------------------------------------------------------------


def _eff_tiles(ho: int, wo: int, tile_rows: int, tile_cols: int):
    """Normalize a (tile_rows, tile_cols) request to effective tile dims."""
    th = tile_rows if 0 < tile_rows < ho else ho
    tw = tile_cols if 0 < tile_cols < wo else wo
    return th, tw


def _infer_halo_mode(ho: int, wo: int, th: int, tw: int, halo_mode) -> str:
    """Default regime for legacy callers that don't pass ``halo_mode``:
    column tiling forces DMA; row-only tiling keeps the PR 2 two-block
    scheme; no tiling is the untiled whole-slab regime."""
    if halo_mode is not None:
        return halo_mode
    if tw < wo:
        return "dma"
    return "two_block" if th < ho else "none"


def _dma_window(th: int, tw: int, kh: int, kw: int, stride: int, in_bytes: int):
    """(rows, cols) of the input window one DMA-regime tile copies — exactly
    what kernels/conv2d.py allocates and fetches."""
    return stride * th + kh - stride, dma_window_cols(stride * tw + kw - stride, in_bytes)


def _lanes(c: int) -> int:
    return ceil_div(c, TPU_V5E.lane) * TPU_V5E.lane


def direct_conv_vmem(
    hp: int, wp: int, cin: int, kh: int, kw: int, ho: int, wo: int, tau: int,
    in_bytes: int, acc_bytes: int = 4, *, stride: int = 1, tile_rows: int = 0,
    tile_cols: int = 0, halo_mode: Optional[str] = None,
) -> int:
    """VMEM working set of one direct-conv grid step (double-buffered I/O).

    Every buffer is counted as the chip lays it out
    (:func:`~repro.core.tiling.padded_bytes`): channels pad to 128 lanes and
    widths to whole sublane tiles, so a Cin=3 image slab costs 128 lanes.
    Three regimes (``halo_mode``, inferred from the tile dims when omitted):

    * ``"none"`` — untiled: the whole padded image slab is resident
      (double-buffered).
    * ``"two_block"`` — row-tiled with blocked successor reads: each step
      holds *two* adjacent ``stride·tile_rows``-row full-width input blocks
      (the tile plus the successor supplying the ``kh − stride`` halo rows)
      plus the same-sized buffer the kernel stitches them into — a ~6×
      tile-rows residency.
    * ``"dma"`` — (𝒯, ℭ)-tiled with manual async copies: exactly the
      ``stride·tile_rows + kh − stride`` × ``stride·tile_cols + kw −
      stride`` input window a tile reads, double-buffered (×2) for the
      prefetch pipeline — roughly half the two-block residency at equal
      tile_rows, and the only regime that tiles the width.

    Besides the input: the (K², Cin, τ) weight block and the (1, τ) bias
    (double-buffered), the (tile_rows, tile_cols, τ) output block
    (double-buffered), and the values of one in-kernel chunk — the tap
    operand (and its int8 digits on the fixed-point path), the product and
    the accumulator (:data:`~repro.core.tiling.CONV_CHUNK_M` rows).
    """
    th, tw = _eff_tiles(ho, wo, tile_rows, tile_cols)
    mode = _infer_halo_mode(ho, wo, th, tw, halo_mode)
    if mode == "none":
        x = 2 * hp * padded_bytes(wp, cin, in_bytes)
    elif mode == "two_block":
        if tw < wo:
            raise ValueError("two_block halo cannot tile columns (use 'dma')")
        # two double-buffered input blocks + the in-kernel stitch buffer
        x = 3 * 2 * stride * th * padded_bytes(wp, cin, in_bytes)
    elif mode == "dma":
        rows_in, cols_in = _dma_window(th, tw, kh, kw, stride, in_bytes)
        x = 2 * rows_in * padded_bytes(cols_in, cin, in_bytes)
    else:
        raise ValueError(f"unknown halo_mode {mode!r}")
    w = 2 * kh * kw * padded_bytes(cin, tau, in_bytes)
    bias = 2 * padded_bytes(1, tau, 4)
    out = 2 * th * padded_bytes(tw, tau, in_bytes)
    m = conv_chunk_rows(th, tw) * tw
    chunk = 2 * padded_bytes(m, cin, in_bytes) + 3 * padded_bytes(m, tau, acc_bytes)
    return x + w + bias + out + chunk


def direct_conv_hbm_traffic(
    hp: int, wp: int, cin: int, kh: int, kw: int, ho: int, wo: int, cout: int,
    stride: int, tau: int, in_bytes: int, *, tile_rows: int = 0,
    tile_cols: int = 0, halo_mode: Optional[str] = None,
) -> int:
    """Modeled HBM bytes one forward pass of the layer actually moves.

    The cost model behind the conv DSE score (and the bench table's
    HBM-traffic column).  The image's channels count as whole 128-lane
    tiles (its layout on the chip), in every regime:

    * the image streams once per τ-way (ceil(cout/τ) output-channel tiles);
      the two-block regime additionally re-streams every full-width block
      ~2× (each block is also its predecessor's halo), while the DMA regime
      fetches each tile's exact window once — only the ``kh/kw − stride``
      overlap between neighbouring windows is paid twice,
    * the τ-wide weight slab is re-fetched once per spatial tile,
    * padded output tiles (tiles·th ≥ ho etc.) and padded channels
      (coutp ≥ cout) are wasted write-back traffic.
    """
    th, tw = _eff_tiles(ho, wo, tile_rows, tile_cols)
    mode = _infer_halo_mode(ho, wo, th, tw, halo_mode)
    coutp = ceil_div(cout, tau) * tau
    ways = coutp // tau
    tiles_r = ceil_div(ho, th)
    tiles_c = ceil_div(wo, tw)
    tiles = tiles_r * tiles_c
    lanes = _lanes(cin)
    if mode == "none":
        x_traffic = ways * hp * wp * lanes
    elif mode == "two_block":
        x_traffic = ways * tiles_r * 2 * stride * th * wp * lanes
    elif mode == "dma":
        rows_in, cols_in = _dma_window(th, tw, kh, kw, stride, in_bytes)
        x_traffic = ways * tiles * min(hp, rows_in) * min(wp, cols_in) * lanes
    else:
        raise ValueError(f"unknown halo_mode {mode!r}")
    w_traffic = tiles * kh * kw * cin * coutp
    out_traffic = tiles * th * tw * coutp
    return (x_traffic + w_traffic + out_traffic) * in_bytes


def direct_conv_input_traffic(
    hp: int, wp: int, cin: int, kh: int, kw: int, ho: int, wo: int, cout: int,
    stride: int, tau: int, in_bytes: int, *, tile_rows: int = 0,
    tile_cols: int = 0, halo_mode: Optional[str] = None,
) -> int:
    """The input-stream component of :func:`direct_conv_hbm_traffic` alone.

    This is the term the halo regime actually changes (weights and output
    write-back move identically under either scheme at equal tile dims), so
    it is what the bench table's ≤ 0.6× DMA-vs-two-block gate compares.
    """
    full = direct_conv_hbm_traffic(
        hp, wp, cin, kh, kw, ho, wo, cout, stride, tau, in_bytes,
        tile_rows=tile_rows, tile_cols=tile_cols, halo_mode=halo_mode,
    )
    th, tw = _eff_tiles(ho, wo, tile_rows, tile_cols)
    coutp = ceil_div(cout, tau) * tau
    tiles = ceil_div(ho, th) * ceil_div(wo, tw)
    w_out = tiles * (kh * kw * cin * coutp + th * tw * coutp) * in_bytes
    return full - w_out


def direct_conv_ideal_traffic(
    hp: int, wp: int, cin: int, kh: int, kw: int, ho: int, wo: int, cout: int,
    in_bytes: int,
) -> int:
    """Lower-bound HBM bytes: image + weights + output each touched once."""
    return (hp * wp * cin + kh * kw * cin * cout + ho * wo * cout) * in_bytes


@dataclasses.dataclass(frozen=True)
class ConvTileChoice:
    """One legal direct-conv compute-unit configuration (τ, 𝒯, ℭ, regime).

    ``tile_rows``/``tile_cols`` are output rows/columns per grid step (== the
    full extent when untiled on that axis); ``halo_mode`` names the input
    regime ("none" | "two_block" | "dma", see :func:`direct_conv_vmem`).
    The defaults on the PR 8 fields keep hand-built pre-column-tiling
    choices constructible (row-tiled two-block or untiled semantics).
    """

    tau: int
    tile_rows: int  # output rows per grid step (== ho when untiled)
    spatial_tiles: int  # ceil(ho / tile_rows)
    vmem_bytes: int
    score: float
    tile_cols: int = 0  # output cols per grid step (0/== wo: untiled axis)
    col_tiles: int = 1  # ceil(wo / tile_cols)
    halo_mode: str = ""  # "" on legacy choices: infer from the tile dims


def conv_choice_to_doc(choice: ConvTileChoice) -> dict:
    """JSON-serializable form of a ConvTileChoice (plan-store schema)."""
    return dataclasses.asdict(choice)


def conv_choice_from_doc(doc: dict) -> ConvTileChoice:
    """Inverse of :func:`conv_choice_to_doc`; bit-identical round-trip."""
    return ConvTileChoice(
        tau=int(doc["tau"]),
        tile_rows=int(doc["tile_rows"]),
        spatial_tiles=int(doc["spatial_tiles"]),
        vmem_bytes=int(doc["vmem_bytes"]),
        score=float(doc["score"]),
        tile_cols=int(doc.get("tile_cols", 0)),
        col_tiles=int(doc.get("col_tiles", 1)),
        halo_mode=str(doc.get("halo_mode", "")),
    )


def _conv_tile_score(
    tau: int, th: int, tw: int, halo_mode: str, hp: int, wp: int, cin: int,
    kh: int, kw: int, ho: int, wo: int, cout: int, stride: int, spec: TpuSpec,
    in_bytes: int,
) -> float:
    """Compute-unit utilization of one (τ, 𝒯, ℭ, regime) configuration.

    Traffic-based: ideal HBM bytes over the bytes the grid actually moves
    (:func:`direct_conv_hbm_traffic`) — the TPU analogue of the paper's
    ceil(p/μ)·ceil(q/τ) invocation-waste terms — times the MXU row occupancy
    of the per-step (th·tw, cin) GEMM.  Untiled pays no halo or weight
    refetch, so it wins whenever it fits; among tiled configs DMA beats
    two-block at equal tile dims (strictly less input re-streaming), and
    squarer (𝒯, ℭ) windows beat full-width strips of the same area because
    the two-sided halo overlap shrinks with the perimeter-to-area ratio.
    """
    traffic = direct_conv_hbm_traffic(
        hp, wp, cin, kh, kw, ho, wo, cout, stride, tau, in_bytes,
        tile_rows=th, tile_cols=tw, halo_mode=halo_mode,
    )
    ideal = direct_conv_ideal_traffic(hp, wp, cin, kh, kw, ho, wo, cout, in_bytes)
    rows = th * min(tw, wo)
    m_eff = rows / (ceil_div(rows, spec.mxu_dim) * spec.mxu_dim)
    return ideal / traffic * m_eff


def _tile_ladder(extent: int, lo: int) -> list[int]:
    """Candidate tile sizes for one spatial axis, largest first.

    The halving ladder (extent, ⌈extent/2⌉, …, lo) gives geometric coverage;
    every divisor of the extent in [lo, extent] is added so exact tilings —
    no ragged final tile, no padded write-back waste — are always
    enumerable (e.g. Ho=27 offers 9 and 3, not just 27→14→7→4).
    """
    lo = max(1, min(lo, extent))
    vals = {d for d in range(lo, extent + 1) if extent % d == 0}
    t = extent
    while t > lo:
        vals.add(t)
        t = ceil_div(t, 2)
    vals.add(lo)
    return sorted(vals, reverse=True)


def explore_conv_spatial(
    hp: int,
    wp: int,
    cin: int,
    kh: int,
    kw: int,
    ho: int,
    wo: int,
    cout: int,
    stride: int,
    spec: TpuSpec = TPU_V5E,
    in_bytes: int = 4,
    top: int = 5,
) -> list[ConvTileChoice]:
    """Enumerate legal (τ, tile_rows, tile_cols, halo_mode) configs; rank by
    the HBM-traffic score.

    τ ladder: min(lane, cout) halved down to 8 (same ladder the engine used
    pre-tiling).  Tile ladders (:func:`_tile_ladder`): halving steps plus
    every exact divisor of the extent.  Three regimes are enumerated:
    untiled whole-slab, row-tiled two-block (legality: stride·tile_rows ≥
    kh so the successor block covers the tap window), and (𝒯, ℭ)-tiled
    manual-DMA — whose window always covers the taps and which is the only
    regime that tiles the width, so extreme-width layers stay direct
    instead of falling back to im2col.  Its one legality bound is the
    chip's: a packed (16/8-bit) window starts on an 8-column group, so a
    column tile must advance a multiple of 8 input columns.
    """
    tau0 = min(spec.lane, cout)
    taus = []
    t = tau0
    while True:
        taus.append(t)
        if t <= 8:
            break
        t //= 2
    th_two_min = max(1, ceil_div(kh, stride))
    configs: list[tuple[int, int, str]] = [(ho, wo, "none")]
    for th in _tile_ladder(ho, th_two_min):
        if th < ho and stride * th >= kh:
            configs.append((th, wo, "two_block"))
    for th in _tile_ladder(ho, 1):
        for tw in _tile_ladder(wo, 1):
            if th >= ho and tw >= wo:
                continue  # the untiled regime already covers the whole slab
            if tw < wo and in_bytes < 4 and (stride * tw) % spec.sublane:
                # packed (16/8-bit) windows must start on an 8-column group
                continue
            configs.append((th, tw, "dma"))
    out: list[ConvTileChoice] = []
    for tau, (th, tw, mode) in itertools.product(taus, configs):
        vmem = direct_conv_vmem(
            hp, wp, cin, kh, kw, ho, wo, tau, in_bytes, stride=stride,
            tile_rows=th, tile_cols=tw, halo_mode=mode,
        )
        if vmem > spec.vmem_bytes:
            continue
        score = _conv_tile_score(
            tau, th, tw, mode, hp, wp, cin, kh, kw, ho, wo, cout, stride,
            spec, in_bytes,
        )
        out.append(
            ConvTileChoice(
                tau=tau,
                tile_rows=th,
                spatial_tiles=ceil_div(ho, th),
                vmem_bytes=vmem,
                score=score,
                tile_cols=tw,
                col_tiles=ceil_div(wo, tw),
                halo_mode=mode,
            )
        )
    # deterministic rank: score, then wider τ, then taller/wider tiles, then
    # regime name — ties between symmetric (𝒯, ℭ) transposes resolve to the
    # taller tile
    out.sort(
        key=lambda c: (-c.score, -c.tau, -c.tile_rows, -c.tile_cols, c.halo_mode)
    )
    return out[:top]


def default_conv_tile_for(
    hp: int,
    wp: int,
    cin: int,
    kh: int,
    kw: int,
    ho: int,
    wo: int,
    cout: int,
    stride: int,
    spec: TpuSpec = TPU_V5E,
    in_bytes: int = 4,
) -> Optional[ConvTileChoice]:
    """Best-scoring legal direct-conv config, or None (→ im2col fallback)."""
    ranked = explore_conv_spatial(
        hp, wp, cin, kh, kw, ho, wo, cout, stride, spec, in_bytes
    )
    return ranked[0] if ranked else None


# ---------------------------------------------------------------------------
# per-layer precision assignment (drift-aware DSE, DESIGN.md §11)
# ---------------------------------------------------------------------------


def choose_precision(
    drift: dict,
    budget: float,
    base_fmt,
    low_fmt,
) -> dict:
    """Assign each layer the cheapest activation grid meeting ``budget``.

    ``drift`` maps layer name -> measured *solo-flip* argmax agreement (the
    network's end-to-end agreement vs the all-``base_fmt`` reference when
    only that layer drops to ``low_fmt``; from the extended drift sweep in
    ``benchmarks/precision_drift.py``).  A layer gets ``low_fmt`` (int8 —
    half the activation/KV bytes) iff its solo-flip agreement is >= the
    network accuracy budget; everything else keeps ``base_fmt``.  Pure and
    deterministic: the engine pins the result in the PlanRegistry with
    ``source: measured`` provenance and the per-layer drift attached, so a
    warm restart replays the exact assignment with zero sweeps.
    """
    if not 0.0 <= budget <= 1.0:
        raise ValueError(f"precision budget must be in [0, 1], got {budget}")
    return {
        layer: low_fmt if agreement >= budget else base_fmt
        for layer, agreement in drift.items()
    }
