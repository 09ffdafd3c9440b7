"""Direct convolution on the unified compute unit, as Pallas kernels.

The paper's key move is computing conv as vector multiplication on the same
μ×τ unit used for FC layers (Fig. 4): for each spatial position and each of
the K² taps, a μ-wide input-channel vector is dotted with a μ×τ weight slab.

TPU adaptation: instead of one (spatial, tap) position per cycle, each grid
step keeps an image slab in VMEM and runs K² *matmuls* of shape
(rows·Wo, Cin) x (Cin, τ) — the tap loop is unrolled (K is static) and each
tap is an MXU-shaped GEMM, which is how the μ×τ wave generalizes to a 128×128
systolic array.  Inside a grid step the output rows are walked in chunks of
at most ``CONV_CHUNK_M`` GEMM rows (a ``fori_loop``; core/tiling.py), each
chunk accumulating its K² tap products in registers before the fused
epilogue writes it back, so the compiled kernel's size does not grow with
the tile.

The kernels read unit-stride windows only: Mosaic loads no strided window
of 16/8-bit data, nor of 32-bit data wider than 128 lanes, so the
``kernels/ops.py`` wrappers run a strided conv as the stride-1 conv over
its space-to-depth input (``space_to_depth_operands``).

The float and fixed-point kernels are one kernel with two contractions: f32
taps at full f32 MXU precision, or int16/int8 taps through
:func:`repro.kernels.common.int_dot` (exact int32).

Spatial tiling (the paper's 𝒯/ℭ loop tiles, §III.B): when the whole image
slab exceeds the VMEM budget, ``tile_rows`` adds an output-row tile axis to
the grid, in one of two halo regimes (DESIGN.md §2):

* ``halo_mode="two_block"`` (PR 2, row tiling only): each grid step reads
  the tile's ``tile_rows``-row input block plus its *successor*
  block as ordinary blocked BlockSpecs and concatenates them in-kernel —
  the second block supplies the ``kh - 1`` halo rows a tap window
  reads past the tile boundary.  Legality: ``tile_rows ≥ kh`` so one
  successor block always covers the halo.  Residency tax: ~2× the tile's
  input rows live in VMEM, and every input block streams from HBM twice
  (once as a tile, once as its predecessor's halo).

* ``halo_mode="dma"``: the input stays an unblocked HBM/ANY operand and the
  kernel issues an explicit async copy of *exactly* the window a tile
  reads — ``tile_rows + kh − 1`` input rows (and, when ``tile_cols``
  also tiles the width, ``tile_cols + kw − 1`` columns) — into a
  double-buffered VMEM scratch; the next tile's window prefetches while
  the current one computes.  No successor block, no concat copy, no
  ``tile_rows ≥ kh`` legality bound, and each input byte streams from HBM
  once per τ-way plus the (kh−1)-row overlap.
  ``tile_cols`` adds the paper's ℭ column-tile axis so extreme-width
  layers tile as (𝒯, ℭ) blocks instead of spilling to im2col.

The im2col + matmul fallback remains only for layers where no
(τ, tile_rows, tile_cols) fits the VMEM budget — the routing decision lives
in ``core/engine.py`` (DESIGN.md §2).

Both kernels fuse the layer epilogue (bias add, ReLU, and — float path —
output quantization) into the accumulator write-back, so activations never
round-trip through HBM between the GEMM and the nonlinearity (DESIGN.md §3).

Grid: (N, ceil(Ho/tile_rows), Cout/τ) for the blocked regimes, with a
ceil(Wo/tile_cols) axis inserted before the τ axis in the DMA regime; tile
axes are 1 when untiled.  τ is the fastest axis so a DMA'd input window is
fetched once and reused by every output-channel way.  The weights are laid
out (K², Cin, Cout) so each tap's (Cin, τ) slab is a leading-axis index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import QFormat, Q2_14, shift_saturate_i32
from repro.core.tiling import conv_chunk_rows, dma_window_cols

from .common import int_dot, pallas

__all__ = ["conv2d_pallas", "conv2d_q16_pallas"]


def _float_dot(lhs, rhs):
    return jnp.dot(lhs, rhs, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _float_epilogue(acc, b_ref, *, relu, qout):
    """Fused bias/ReLU/fake-quant on the f32 accumulator (DESIGN.md §3)."""
    if b_ref is not None:
        acc = acc + b_ref[...].astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    if qout is not None:
        acc = jnp.clip(jnp.round(acc * qout.scale) / qout.scale, qout.min_val, qout.max_val)
    return acc


def _q16_epilogue(acc, b_ref, *, relu, shift, bias_shift, raw_min, raw_max,
                  out_dtype=jnp.int16):
    """Fused bias/ReLU/saturating-requantize on the i32 accumulator."""
    if b_ref is not None:
        acc = acc + (b_ref[...].astype(jnp.int32) << bias_shift)
    if relu:
        acc = jnp.maximum(acc, 0)
    return shift_saturate_i32(acc, shift, raw_min, raw_max, out_dtype)


def _conv_tile(src, lead, w_ref, b_ref, o_ref, *, kh, kw, th, tw, dot,
               epilogue):
    """Direct conv of one (th, tw) output tile.

    ``src[lead]``: the (rows, cols, Cin) input window of the tile (``lead``
    indexes the leading axes of ``src``; the kernel indexes the full ref
    rather than taking a view of it, which Mosaic refuses for windows whose
    minor dims are not tile-aligned); ``w_ref``: (K², Cin, τ); ``o_ref``:
    (1, th, tw, τ).  Output rows are walked in
    :func:`~repro.core.tiling.conv_chunk_rows` chunks; per chunk every tap is
    one (rows·tw, Cin) × (Cin, τ) GEMM read straight from ``src``.
    """
    rc = conv_chunk_rows(th, tw)
    cin = src.shape[-1]

    def chunk(c, carry):
        r0 = c * rc
        acc = None
        for i in range(kh):
            for j in range(kw):
                lhs = src[(*lead, pl.ds(r0 + i, rc), pl.ds(j, tw), slice(None))]
                d = dot(lhs.reshape(rc * tw, cin), w_ref[i * kw + j])
                acc = d if acc is None else acc + d
        out = epilogue(acc, b_ref)
        o_ref[0, pl.ds(r0, rc), :, :] = out.reshape(rc, tw, -1).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, th // rc, chunk, 0)


def _conv_block_kernel(*refs, halo, fused_bias, **tile):
    """Blocked regimes: refs = x1 (1, rows, Wp, Cin) image block [, x2 the
    same-shape successor block], w, [bias,] out [, stitch scratch]."""
    refs = list(refs)
    x1 = refs.pop(0)
    x2 = refs.pop(0) if halo else None
    w_ref = refs.pop(0)
    b_ref = refs.pop(0) if fused_bias else None
    o_ref = refs.pop(0)
    if not halo:
        _conv_tile(x1, (0,), w_ref, b_ref, o_ref, **tile)
        return
    # the tap window of the last output row in this tile reads up to
    # th - 1 + kh - 1 < 2*th rows (th >= kh), so the
    # pair of adjacent row blocks, stitched into one buffer, always covers it
    (xs,) = refs
    rows = x1.shape[1]
    xs[:rows] = x1[0]
    xs[rows:] = x2[0]
    _conv_tile(xs, (), w_ref, b_ref, o_ref, **tile)


# ---------------------------------------------------------------------------
# manual-DMA halo regime (double-buffered (𝒯, ℭ) windows)
# ---------------------------------------------------------------------------


def _conv_dma_kernel(*refs, fused_bias, **tile):
    """(𝒯, ℭ)-tiled direct conv with a manual-DMA input halo.

    The input operand lives in HBM (``memory_space=ANY``); each (r, c) tile
    copies exactly its ``th + kh − 1`` × ``tw + kw − 1`` input window into
    one slot of a double-buffered VMEM scratch.
    The copy for tile k+1 is started on tile k's last τ-way, so the fetch
    overlaps the K² tap GEMMs of the current tile (the classic
    prefetch/compute pipeline); the τ axis is innermost, so each window is
    DMA'd once and reused by every output-channel way.
    """
    refs = list(refs)
    x_hbm = refs.pop(0)  # (N, Hp', Wp', Cin), unblocked, HBM-resident
    w_ref = refs.pop(0)
    b_ref = refs.pop(0) if fused_bias else None
    o_ref, xs_ref, sem = refs
    th, tw = tile["th"], tile["tw"]
    b = pl.program_id(0)
    r = pl.program_id(1)
    c = pl.program_id(2)
    t = pl.program_id(3)
    tiles_c = pl.num_programs(2)
    ways = pl.num_programs(3)
    tile_ix = r * tiles_c + c
    total = pl.num_programs(1) * tiles_c
    rows_in, cols_in = xs_ref.shape[1], xs_ref.shape[2]

    def fetch(ix, slot):
        rr = ix // tiles_c
        cc = ix % tiles_c
        return pltpu.make_async_copy(
            x_hbm.at[
                b,
                pl.ds(rr * th, rows_in),
                pl.ds(cc * tw, cols_in),
                :,
            ],
            xs_ref.at[slot],
            sem.at[slot],
        )

    # warm-up: the first tile of each image has no predecessor to prefetch it
    @pl.when((tile_ix == 0) & (t == 0))
    def _():
        fetch(tile_ix, tile_ix % 2).start()

    # wait for this tile's window, once per tile (way 0)
    @pl.when(t == 0)
    def _():
        fetch(tile_ix, tile_ix % 2).wait()

    # prefetch the next tile's window into the other slot while computing
    @pl.when((t == ways - 1) & (tile_ix + 1 < total))
    def _():
        fetch(tile_ix + 1, (tile_ix + 1) % 2).start()

    _conv_tile(xs_ref, (tile_ix % 2,), w_ref, b_ref, o_ref, **tile)


def _halo_mode_for(tile_rows, tile_cols, ho, wo, halo_mode):
    """Validate/normalize the halo regime for a (tile_rows, tile_cols) pair."""
    row_tiled = 0 < tile_rows < ho
    col_tiled = 0 < tile_cols < wo
    if not (row_tiled or col_tiled):
        return "untiled"
    if col_tiled and halo_mode != "dma":
        raise ValueError(
            f"tile_cols={tile_cols} requires halo_mode='dma' (the two-block "
            f"BlockSpec scheme only tiles output rows), got {halo_mode!r}"
        )
    if halo_mode == "dma":
        return "dma"
    if halo_mode in ("two_block", "none"):
        # "none" is the untiled plans' sentinel; a tiled call with it keeps
        # the legacy two-block behaviour for back-compat
        return "two_block"
    raise ValueError(f"unknown halo_mode {halo_mode!r}")


def _conv_call(
    x, w, bias_row, *, tau, tile_rows, tile_cols, halo_mode, dot, epilogue,
    out_dtype, vmem_limit_bytes,
):
    """Shared pallas_call plumbing of the float and fixed-point convs.

    Pads Cout to whole τ-ways, pads x so every tile's input (blocked
    successor or DMA window) is in-bounds — zero rows/cols past the image
    contribute zero products, so ragged edges stay exact — pads the output
    grid to whole tiles, and slices both back to (Ho, Wo, Cout).
    """
    n, h, wdt, cin = x.shape
    kh, kw, cin2, cout = w.shape
    assert cin == cin2
    ho = h - kh + 1
    wo = wdt - kw + 1
    tau = min(tau, cout)
    coutp = -(-cout // tau) * tau
    if coutp != cout:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, coutp - cout)))
        if bias_row is not None:
            bias_row = jnp.pad(bias_row, ((0, 0), (0, coutp - cout)))
    # tap-major (K², Cin, Cout): tap (i, j)'s slab is w[i*kw + j]
    wtaps = w.reshape(kh * kw, cin, coutp)
    mode = _halo_mode_for(tile_rows, tile_cols, ho, wo, halo_mode)
    th = tile_rows if 0 < tile_rows < ho else ho
    tw = tile_cols if 0 < tile_cols < wo else wo
    tiles_r = -(-ho // th)
    tiles_c = -(-wo // tw)
    tile = dict(kh=kh, kw=kw, th=th, tw=tw, dot=dot, epilogue=epilogue)
    fused_bias = bias_row is not None
    if mode == "dma":
        # window copies move whole 128-lane channel tiles: zero channels
        # (and zero weight rows) pad Cin up to one, exactly
        cinp = -(-cin // 128) * 128
        if cinp != cin:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cinp - cin)))
            wtaps = jnp.pad(wtaps, ((0, 0), (0, cinp - cin), (0, 0)))
            cin = cinp
        rows_in = th + kh - 1
        cols_in = dma_window_cols(tw + kw - 1, x.dtype.itemsize)
        need_h = th * (tiles_r - 1) + rows_in
        need_w = tw * (tiles_c - 1) + cols_in
        if need_h > h or need_w > wdt:
            x = jnp.pad(
                x, ((0, 0), (0, max(0, need_h - h)), (0, max(0, need_w - wdt)), (0, 0))
            )
        x_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        w_index = lambda b, r, c, t: (0, 0, t)  # noqa: E731
        b_index = lambda b, r, c, t: (0, t)  # noqa: E731
        grid = (n, tiles_r, tiles_c, coutp // tau)
        out_index = lambda b, r, c, t: (b, r, c, t)  # noqa: E731
        kernel = functools.partial(_conv_dma_kernel, fused_bias=fused_bias, **tile)
        scratch = [
            pltpu.VMEM((2, rows_in, cols_in, cin), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        # the prefetch pipeline carries state from tile to tile
        semantics = ("parallel", "arbitrary", "arbitrary", "arbitrary")
        operands = [x]
    else:
        halo = mode == "two_block"
        if halo:
            row_in = th  # input rows consumed per output-row tile
            if row_in < kh:
                raise ValueError(
                    f"tile_rows={th} too small: it must cover the {kh}-row tap "
                    f"window for the two-block halo scheme"
                )
            # tile r reads blocks r and r+1; the last tile (and its ragged
            # output rows) must see zeros past the real image
            need = (tiles_r + 1) * row_in
            if need > h:
                x = jnp.pad(x, ((0, 0), (0, need - h), (0, 0), (0, 0)))
            x_specs = [
                pl.BlockSpec((1, row_in, wdt, cin), lambda b, r, t: (b, r, 0, 0)),
                pl.BlockSpec((1, row_in, wdt, cin), lambda b, r, t: (b, r + 1, 0, 0)),
            ]
            scratch = [pltpu.VMEM((2 * row_in, wdt, cin), x.dtype)]
            operands = [x, x]
        else:
            x_specs = [pl.BlockSpec((1, h, wdt, cin), lambda b, r, t: (b, 0, 0, 0))]
            scratch = []
            operands = [x]
        w_index = lambda b, r, t: (0, 0, t)  # noqa: E731
        b_index = lambda b, r, t: (0, t)  # noqa: E731
        grid = (n, tiles_r, coutp // tau)
        out_index = lambda b, r, t: (b, r, 0, t)  # noqa: E731
        kernel = functools.partial(
            _conv_block_kernel, halo=halo, fused_bias=fused_bias, **tile
        )
        semantics = ("parallel", "parallel", "parallel")
    in_specs = x_specs + [pl.BlockSpec((kh * kw, cin, tau), w_index)]
    operands.append(wtaps)
    if fused_bias:
        in_specs.append(pl.BlockSpec((1, tau), b_index))
        operands.append(bias_row)
    out = pallas(
        kernel,
        name=f"conv_{mode}",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, th, tw, tau), out_index),
        out_shape=jax.ShapeDtypeStruct(
            (n, tiles_r * th, tiles_c * tw, coutp), out_dtype
        ),
        scratch_shapes=scratch,
        dimension_semantics=semantics,
        vmem_limit_bytes=vmem_limit_bytes,
    )(*operands)
    return out[:, :ho, :wo, :cout]


@functools.partial(
    jax.jit,
    static_argnames=(
        "tau", "relu", "qout", "tile_rows", "tile_cols", "halo_mode",
        "vmem_limit_bytes",
    ),
)
def conv2d_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    tau: int = 128,
    relu: bool = False,
    qout: QFormat | None = None,
    tile_rows: int = 0,
    tile_cols: int = 0,
    halo_mode: str = "two_block",
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """NHWC VALID conv, stride 1.  x: (N,H,W,Cin), w: (K,K,Cin,Cout).

    ``bias``: (Cout,) fused into the write-back; ``relu``/``qout``: fused
    nonlinearity and (fake-)quantization to a Q format, applied after bias.
    ``tile_rows`` / ``tile_cols``: output rows/columns per grid step (0 =
    untiled on that axis); ``halo_mode`` picks the tiled input regime —
    "two_block" (blocked successor reads, rows only) or "dma" (exact-window
    async copies, required for column tiling).  The engine picks all three
    so the working set fits ``vmem_limit_bytes`` (DESIGN.md §2).
    """
    bias_row = None if bias is None else bias.astype(jnp.float32).reshape(1, -1)
    return _conv_call(
        x, w, bias_row, tau=tau, tile_rows=tile_rows,
        tile_cols=tile_cols, halo_mode=halo_mode, dot=_float_dot,
        epilogue=functools.partial(_float_epilogue, relu=relu, qout=qout),
        out_dtype=x.dtype, vmem_limit_bytes=vmem_limit_bytes,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tau", "relu", "fmt", "shift", "bias_shift", "tile_rows",
        "tile_cols", "halo_mode", "vmem_limit_bytes",
    ),
)
def conv2d_q16_pallas(
    xq: jax.Array,
    wq: jax.Array,
    bias: jax.Array | None = None,
    *,
    tau: int = 128,
    relu: bool = False,
    fmt: QFormat = Q2_14,
    shift: int | None = None,
    bias_shift: int | None = None,
    tile_rows: int = 0,
    tile_cols: int = 0,
    halo_mode: str = "two_block",
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """Fixed-point NHWC VALID conv, stride 1.  int16/int8 raw Qm.n tensors.

    ``tile_rows`` / ``tile_cols`` / ``halo_mode`` tile the output exactly as
    in :func:`conv2d_pallas`; zero-padded halo rows/columns contribute zero
    products and integer accumulation is order-exact, so every tiling (and
    both halo regimes) is bit-identical to the untiled kernel.  Mixed operand
    widths are legal (the tap products are exact int32 either way) and the
    output is stored on ``fmt.storage_dtype``; ``shift`` / ``bias_shift``
    override the write-back scale gaps for mixed-format operands (default:
    same-format Qm.n semantics) — an int8-rung ``fmt`` with an int16-grid
    ``shift`` is the mixed-boundary epilogue of DESIGN.md §11.
    """
    assert xq.dtype in (jnp.int8, jnp.int16) and wq.dtype in (jnp.int8, jnp.int16)
    bias_row = None if bias is None else bias.astype(jnp.int16).reshape(1, -1)
    epilogue = functools.partial(
        _q16_epilogue, relu=relu,
        shift=fmt.frac_bits if shift is None else shift,
        bias_shift=fmt.frac_bits if bias_shift is None else bias_shift,
        raw_min=fmt.raw_min, raw_max=fmt.raw_max,
        out_dtype=fmt.storage_dtype,
    )
    return _conv_call(
        xq, wq, bias_row, tau=tau, tile_rows=tile_rows,
        tile_cols=tile_cols, halo_mode=halo_mode, dot=int_dot,
        epilogue=epilogue, out_dtype=fmt.storage_dtype,
        vmem_limit_bytes=vmem_limit_bytes,
    )
