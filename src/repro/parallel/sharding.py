"""Logical-axis sharding: DP / FSDP / TP / EP / SP from one rule table.

Model code annotates tensors with *logical* axis names ("batch", "embed",
"heads", "mlp", "vocab", "experts", ...).  A :class:`ShardingRules` table maps
logical names to mesh axes; :func:`constrain` applies
``with_sharding_constraint`` only when a mesh context is active, so the same
model code runs unsharded on one CPU device and fully sharded on a 512-chip
multi-pod mesh.

Rules follow the MaxText convention; the defaults implement:
  * batch            -> ("pod", "data")   data parallel across pods + pod axis
  * embed/ffn params -> "model"           tensor parallel
  * fsdp dim         -> "data"            ZeRO-3 parameter sharding (training)
  * experts          -> "model"           expert parallel (MoE)
  * kv_heads         -> "model"           GSPMD pads when not divisible
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "TRAIN_RULES",
    "SERVE_RULES",
    "DECODE_RULES",
    "SpatialHalo",
    "column_parallel_shardings",
    "use_mesh",
    "active_mesh",
    "axis_size",
    "local_dim",
    "local_gemm_shape",
    "local_conv_shapes",
    "logical_to_spec",
    "constrain",
    "constrain_slabs",
    "map_slabs",
    "on_every_device",
    "named_sharding",
    "tree_shardings",
    "plan_spatial_halo",
    "spatial_shards",
    "halo_exchange",
    "spatial_halo_bytes",
    "spatial_gather_bytes",
]

MeshAxes = Union[str, tuple, None]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Mapping from logical axis name -> mesh axis (or tuple, or None)."""

    rules: tuple = ()

    def get(self, name: Optional[str]) -> MeshAxes:
        if name is None:
            return None
        for k, v in self.rules:
            if k == name:
                return v
        return None

    def with_overrides(self, **overrides) -> "ShardingRules":
        kept = tuple((k, v) for k, v in self.rules if k not in overrides)
        return ShardingRules(rules=kept + tuple(overrides.items()))


def _mk(rules: dict) -> ShardingRules:
    return ShardingRules(rules=tuple(rules.items()))


#: Training: FSDP over "data" + TP over "model"; batch over every data-ish axis.
TRAIN_RULES = _mk(
    {
        "batch": ("pod", "data"),
        "seq": None,
        # sequence parallelism for the residual stream / remat stash: shards
        # per-layer saved activations 16x and keeps norm/add seq-local
        # (default ON for training since §Perf iteration 2)
        "seq_act": "model",
        "seq_kv": "model",  # decode KV-cache seq dim (flash-decoding style)
        "embed": "data",  # FSDP shard dim of 2D params
        "embed_tp": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "qkv": "model",  # flattened heads*head_dim param dim
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "expert_mlp": None,
        "expert_cap": None,  # capacity-dim EP variant (see moe.py / §Perf B)
        "ssm_inner": "model",  # mamba2 inner dim (heads*headdim + BC groups)
        "rec": "model",  # RG-LRU recurrent width
        "rec_in": None,  # gate matrix input dim (dense dr x dr)
        "conv_io": None,
        "state": None,
        "ctx": None,  # cross-attention context length (frames / image tokens)
        "act_heads": "model",
        "act_embed": None,
    }
)

#: Serving: params replicated over "data" (no FSDP), TP over "model";
#: batch over data axes.
SERVE_RULES = _mk(
    {
        "batch": ("pod", "data"),
        "seq": None,
        "seq_act": None,
        "seq_kv": "model",
        "embed": None,
        "embed_tp": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "qkv": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "expert_mlp": None,
        "expert_cap": None,
        "ssm_inner": "model",
        "rec": "model",
        "rec_in": None,
        "conv_io": None,
        "state": None,
        "ctx": None,
        "act_heads": "model",
        "act_embed": None,
    }
)


#: Bitwise-reproducible tensor-parallel decode (PR 7).  Serving replicas must
#: produce token streams byte-identical to a single-device run, so every
#: contraction (GEMM K) dimension stays shard-local: params are sharded
#: *column-parallel only* (their final/output dim over "model", see
#: :func:`column_parallel_shardings`) and activations are gathered back to
#: replicated at the existing ``constrain`` seams between GEMMs.  Each shard
#: then computes its output columns with the same left operand and the same
#: reduction order as the unsharded program — no psum reduction whose
#: float reassociation could flip low bits.  Batch (the per-slot KV cache
#: slot dim) still shards over the data-ish axes; vocab stays sharded until
#: the logits constraint gathers it for sampling.
DECODE_RULES = _mk(
    {
        "batch": ("pod", "data"),
        "seq": None,
        "seq_act": None,
        "seq_kv": None,
        "embed": None,
        "embed_tp": None,
        "heads": None,
        "kv_heads": None,
        "head_dim": None,
        "qkv": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": None,
        "expert_mlp": None,
        "expert_cap": None,
        "ssm_inner": None,
        "rec": None,
        "rec_in": None,
        "conv_io": None,
        "state": None,
        "ctx": None,
        "act_heads": None,
        "act_embed": None,
    }
)


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[ShardingRules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: ShardingRules):
    """Activate a mesh + rule table for ``constrain``/``named_sharding``."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        with mesh:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _present_axes(mesh, axes: MeshAxes) -> MeshAxes:
    """Drop mesh axes the given mesh does not have (e.g. "pod" on the
    single-pod mesh), collapsing a surviving 1-tuple to its string.  The one
    implementation of the drop rule — shared by :func:`logical_to_spec` and
    the local-shape planners below."""
    if axes is None or mesh is None:
        return None
    present = set(mesh.axis_names)
    if isinstance(axes, str):
        return axes if axes in present else None
    kept = tuple(a for a in axes if a in present)
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else kept


def axis_size(mesh, axes: MeshAxes) -> int:
    """Total shard count over ``axes``, ignoring axes the mesh lacks."""
    return _axis_size(mesh, _present_axes(mesh, axes))


def local_dim(dim: int, mesh, axes: MeshAxes) -> int:
    """Per-shard extent of ``dim`` sharded over ``axes``.

    One drop rule, shared with :func:`logical_to_spec` (ISSUE 9): a dim
    that does not divide the shard count stays **replicated** (returns
    ``dim``), because the param/jit-boundary shardings built by
    :func:`tree_shardings`/:func:`column_parallel_shardings` drop exactly
    those mappings — a planner that ceil-divided here would plan a local
    Cout/batch shape that never executes.
    """
    s = axis_size(mesh, axes)
    if s <= 1 or dim < s or dim % s:
        return dim
    return dim // s


def _resolve_partition(mesh, partition):
    """The (M, N[, K]) partition to plan against: the caller's, or the
    mesh's canonical :func:`repro.launch.mesh.gemm_partition` default."""
    if partition is not None:
        return partition
    from repro.launch.mesh import gemm_partition

    return gemm_partition(mesh)


def local_gemm_shape(m: int, n: int, k: int, *, mesh, partition=None) -> tuple:
    """Per-shard (m, n, k) of a logical GEMM under a mesh partition.

    ``partition`` is a PartitionSpec over (M, N[, K]) — M typically over the
    data-ish axes, N over "model" (K only for reduce-scattered contractions).
    Defaults to :func:`repro.launch.mesh.gemm_partition` for the mesh.
    """
    partition = _resolve_partition(mesh, partition)
    axes = tuple(partition) + (None,) * (3 - len(tuple(partition)))
    return tuple(
        local_dim(d, mesh, a) for d, a in zip((m, n, k), axes[:3])
    )


def local_conv_shapes(x_shape, w_shape, *, mesh, partition=None,
                      spatial=None, stride: int = 1, padding: int = 0):
    """Per-shard (NHWC x, KKIO w) of a conv layer under a mesh partition.

    Default (batch/Cout) mode: the conv's GEMM M scales with batch and its
    N is Cout, so the same (M, N) partition applies: batch over the M axes,
    output channels over the N axes; spatial dims and Cin stay shard-local
    (the layer's input activations are gathered over channels between
    layers).

    Spatial mode (ISSUE 9): ``spatial`` — a shard count, a mesh axis name,
    or a pre-planned :class:`SpatialHalo` — partitions **H** instead: each
    shard owns an H slab of the feature map and the per-shard x shape is the
    *halo-augmented* local slab (the ``(lo−1)·stride + kh`` input-row window
    its output rows consume, width pre-padded), with batch and Cout staying
    shard-local — the data-ish mesh axes carry H, not batch.  ``stride`` /
    ``padding`` are required to size the halo window.
    """
    n, h, w, c = x_shape
    kh, kw, cin, cout = w_shape
    if spatial is not None:
        hs = spatial if isinstance(spatial, SpatialHalo) else plan_spatial_halo(
            h, kh, stride, padding, *spatial_shards(spatial, mesh)
        )
        return (n, hs.win, w + 2 * padding, c), w_shape
    p = tuple(_resolve_partition(mesh, partition)) + (None, None)
    batch_axes, cout_axes = p[0], p[1]
    return (
        (local_dim(n, mesh, batch_axes), h, w, c),
        (kh, kw, cin, local_dim(cout, mesh, cout_axes)),
    )


# ---------------------------------------------------------------------------
# cross-chip spatial (H) sharding with halo exchange (ISSUE 9, DESIGN.md §10)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpatialHalo:
    """Plan for one spatially-sharded conv/pool layer seam.

    Each of ``shards`` shards owns a contiguous H slab of the activation in
    the *slab-major* layout ``(S, N, lx, W, C)``: buffer row ``r`` of slab
    ``s`` always holds global row ``s·lx + r`` (zero when that row is beyond
    the global extent — the invariant every spatial op re-establishes by
    masking its ragged tail shard).  Before the op, each shard receives
    ``up`` rows from the shard above and ``dn`` rows from the shard below —
    the only cross-shard movement of the layer, ``kh − stride`` rows at an
    aligned seam — and slices its ``win``-row input window at ``offsets[s]``
    inside the extended buffer.  Zero fill at the mesh edges doubles as the
    conv's spatial zero padding (``pad`` is re-applied to W explicitly).
    """

    shards: int  # S
    axis: Optional[str]  # mesh axis the slab dim shards over (None = local)
    h: int  # global input rows
    ho: int  # global output rows
    lx: int  # slab buffer rows of the incoming layout
    lo: int  # output rows each shard computes (= ceil(ho / S))
    win: int  # input rows of each shard's window: (lo − 1)·stride + kh
    up: int  # halo rows received from the shard above
    dn: int  # halo rows received from the shard below
    offsets: tuple  # per-shard window start inside the (up + lx + dn) buffer
    valid_out: tuple  # per-shard valid output rows (ragged tail < lo)
    pad: int  # the conv's spatial zero padding (W is pre-padded by this)

    @property
    def ragged(self) -> bool:
        return any(v != self.lo for v in self.valid_out)


def spatial_shards(spatial, mesh=None) -> tuple:
    """Resolve a ``spatial=`` option to ``(shards, axis_name_or_None)``.

    An int is a plain shard count (slab-major simulation on however many
    devices the arrays land on); a str names the mesh axis whose size is
    the shard count and over which the slab dim is sharded.
    """
    if isinstance(spatial, str):
        mesh = mesh if mesh is not None else _CTX.mesh
        if mesh is None or spatial not in mesh.axis_names:
            raise ValueError(
                f"spatial mesh axis {spatial!r} needs an active mesh that "
                f"has it (mesh={None if mesh is None else mesh.axis_names})"
            )
        return int(mesh.shape[spatial]), spatial
    s = int(spatial)
    if s < 1:
        raise ValueError(f"spatial shard count must be >= 1, got {s}")
    return s, None


def plan_spatial_halo(
    h: int, kh: int, stride: int, pad: int, shards: int,
    axis: Optional[str] = None, lx: Optional[int] = None,
) -> SpatialHalo:
    """Plan the halo exchange for one conv/pool seam (all static Python ints).

    ``h`` rows arrive laid out as ``shards`` slabs of ``lx`` buffer rows
    (default: ceil-div — the layout :func:`plan_spatial_halo` itself assigns
    to the *previous* layer's output, so chained calls pass ``lx=prev.lo``).
    Shard ``s`` computes output rows ``[s·lo, s·lo + lo)`` of the
    ``ho = (h + 2·pad − kh)//stride + 1`` global output rows, for which it
    needs input rows ``[s·lo·stride − pad, …)`` — ``up``/``dn`` are the
    worst-case per-seam row counts that window reaches into the neighbor
    slabs.  At an aligned seam (``lo·stride == lx``) that is exactly the
    paper's ``kh − stride`` halo rows.  Raises when a slab is too thin to
    serve its neighbor's halo from one hop away (shards > what H supports).
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if h < 1 or kh < 1 or stride < 1 or pad < 0:
        raise ValueError(f"bad conv geometry h={h} kh={kh} stride={stride} pad={pad}")
    ho = (h + 2 * pad - kh) // stride + 1
    if ho < 1:
        raise ValueError(f"conv produces no output rows (h={h}, kh={kh}, pad={pad})")
    lx = -(-h // shards) if lx is None else int(lx)
    if lx * shards < h:
        raise ValueError(f"slab layout lx={lx} x {shards} shards cannot hold h={h}")
    lo = -(-ho // shards)
    win = (lo - 1) * stride + kh
    up = dn = 0
    offsets, valid_out = [], []
    for s in range(shards):
        g = s * lo * stride - pad  # global row of this shard's window start
        up = max(up, s * lx - g)
        dn = max(dn, (g + win) - (s + 1) * lx)
        offsets.append(g - s * lx)  # relative to own slab start; += up below
        valid_out.append(max(0, min(lo, ho - s * lo)))
    up, dn = max(0, up), max(0, dn)
    if up > lx or dn > lx:
        raise ValueError(
            f"spatial halo needs {up}/{dn} rows from a {lx}-row neighbor "
            f"slab: h={h} is too thin for {shards} shards at kh={kh}, "
            f"stride={stride} (halo exchange is single-hop)"
        )
    return SpatialHalo(
        shards=shards, axis=axis, h=h, ho=ho, lx=lx, lo=lo, win=win,
        up=up, dn=dn, offsets=tuple(o + up for o in offsets),
        valid_out=tuple(valid_out), pad=pad,
    )


def halo_exchange(v: jax.Array, hs: SpatialHalo) -> jax.Array:
    """The neighbor collective + window select of one spatial layer seam.

    ``v``: slab-major raw array ``(S, N, lx, W, C)`` -> the per-shard input
    windows ``(S, N, win, W, C)``.  Only the ``up``/``dn`` halo *rows* move
    between shards — the slices along the (sharded) slab axis lower to a
    neighbor collective-permute under GSPMD, and the mesh-edge shards
    receive zeros, which doubles as the conv's H zero padding.
    """
    if v.ndim != 5 or v.shape[0] != hs.shards or v.shape[2] != hs.lx:
        raise ValueError(
            f"expected slab-major (S={hs.shards}, N, lx={hs.lx}, W, C), "
            f"got {v.shape}"
        )
    # Neighbor movement is jnp.roll on the slab axis — the one shift pattern
    # GSPMD reliably lowers to a collective-permute of just the rolled rows
    # (slice+concat *along the sharded axis* miscompiles under the CPU SPMD
    # partitioner) — with the wrapped-around mesh-edge slab masked to zero,
    # which doubles as the conv's H zero padding.  Everything else (the row
    # concat, the window select) happens on the unsharded row axis.
    sidx = jax.lax.broadcasted_iota(jnp.int32, (hs.shards, 1, 1, 1, 1), 0)
    parts = []
    if hs.up:
        above = jnp.roll(v, 1, axis=0)[:, :, hs.lx - hs.up:]
        parts.append(jnp.where(sidx > 0, above, jnp.zeros_like(above)))
    parts.append(v)
    if hs.dn:
        below = jnp.roll(v, -1, axis=0)[:, :, :hs.dn]
        parts.append(
            jnp.where(sidx < hs.shards - 1, below, jnp.zeros_like(below))
        )
    ext = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=2)
    if len(set(hs.offsets)) == 1:
        o = hs.offsets[0]
        return ext[:, :, o:o + hs.win]
    # misaligned seams (lo·stride != lx): per-shard window starts differ, so
    # gather each shard's rows in place — indices stay within the shard's
    # extended buffer, no extra communication
    rows = (
        jnp.asarray(hs.offsets, jnp.int32)[:, None]
        + jnp.arange(hs.win, dtype=jnp.int32)[None, :]
    )
    return jnp.take_along_axis(ext, rows[:, None, :, None, None], axis=2)


def mask_slab_rows(v: jax.Array, hs: SpatialHalo) -> jax.Array:
    """Zero the ragged tail shard's invalid output rows (the slab invariant:
    buffer rows beyond the global extent hold zeros, so the *next* seam's
    zero fill and halo reads stay exact)."""
    if not hs.ragged:
        return v
    rows = jax.lax.broadcasted_iota(jnp.int32, (hs.shards, 1, hs.lo, 1, 1), 2)
    ok = rows < jnp.asarray(hs.valid_out, jnp.int32).reshape(-1, 1, 1, 1, 1)
    return jnp.where(ok, v, jnp.zeros_like(v))


def constrain_slabs(v: jax.Array, axis: Optional[str]) -> jax.Array:
    """Keep a slab-major array's leading (slab) dim sharded over ``axis``.

    No-op without an active mesh, when ``axis`` is absent from it, or when
    the slab count does not divide the axis (the module's one drop rule).
    """
    mesh = _CTX.mesh
    if axis is None or mesh is None or axis not in mesh.axis_names:
        return v
    if v.shape[0] % mesh.shape[axis]:
        return v
    return jax.lax.with_sharding_constraint(
        v, NamedSharding(mesh, P(axis))
    )


def map_slabs(fn, v: jax.Array, *consts, axis: Optional[str]):
    """Run ``fn(v_local, *consts)`` on each device's own slabs.

    ``v`` is slab-major with its leading (slab) dim sharded over ``axis``
    of the active mesh; ``consts`` (weights, bias) are replicated.  A Pallas
    kernel is a custom call GSPMD cannot partition (Mosaic refuses to
    compile one in a multi-device program outside a ``shard_map``), so the
    map is what puts each slab's conv on the device that holds the slab.  Plain
    ``fn(v, *consts)`` without a mesh that has ``axis`` or when the slab
    count does not divide it (the module's one drop rule).
    """
    mesh = _CTX.mesh
    if (axis is None or mesh is None or axis not in mesh.axis_names
            or v.shape[0] % mesh.shape[axis]):
        return fn(v, *consts)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(axis),) + (P(),) * len(consts),
        out_specs=P(axis), check_vma=False,
    )(v, *consts)


def on_every_device(fn, *args):
    """Run ``fn(*args)`` whole on every device of the active mesh, inputs
    and output replicated.  GSPMD cannot partition a Pallas kernel, so a
    kernel in a multi-device program is placed explicitly — here, or per
    shard by :func:`map_slabs`.  Plain ``fn(*args)`` without a mesh of more
    than one device."""
    mesh = _CTX.mesh
    if mesh is None or mesh.size == 1:
        return fn(*args)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
        check_vma=False,
    )(*args)


def spatial_halo_bytes(hs: SpatialHalo, n: int, w: int, c: int,
                       itemsize: int) -> int:
    """Modeled bytes the halo exchange moves between shards for one seam:
    every interior seam carries ``up`` rows downward and ``dn`` rows upward,
    each a full-width (N, rows, W, C) strip."""
    return (hs.shards - 1) * (hs.up + hs.dn) * n * w * c * itemsize


def spatial_gather_bytes(h: int, n: int, w: int, c: int, shards: int,
                         itemsize: int) -> int:
    """Modeled bytes of the alternative the halo exchange replaces: a ring
    all-gather of the whole (N, H, W, C) activation onto every shard before
    each conv ((S−1)/S of the tensor received per shard, S shards)."""
    return (shards - 1) * n * h * w * c * itemsize


def logical_to_spec(
    logical: Sequence[Optional[str]],
    *,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
    dim_sizes: Optional[Sequence[int]] = None,
    require_divisible: bool = False,
) -> P:
    """Translate logical axis names to a PartitionSpec.

    If ``dim_sizes`` is given, a mapping whose dim is smaller than — or not
    divisible by — the mesh axis product is dropped (replicated).  This is
    the **one** drop rule of the module, shared with :func:`local_dim`
    (ISSUE 9): it used to apply only under ``require_divisible=True`` (the
    jit-boundary callers), which let `plan_conv(mesh=...)` ceil-div a ragged
    Cout that `column_parallel_shardings` would silently replicate — a
    planned local shape that never executed.  ``require_divisible`` is kept
    for API compatibility but divisibility is now always enforced.
    """
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    if rules is None:
        return P()
    out = []
    for i, name in enumerate(logical):
        axes = rules.get(name)
        if axes is not None and mesh is not None:
            axes = _present_axes(mesh, axes)
        if axes is not None and mesh is not None and dim_sizes is not None:
            s = _axis_size(mesh, axes)
            if dim_sizes[i] < s or dim_sizes[i] % s:
                axes = None
        out.append(axes)
    # a mesh axis may appear at most once: keep its first (leftmost) use.
    # (e.g. with sequence parallelism seq_act->model, a logits constraint
    # (batch, seq_act, vocab) would map "model" twice)
    seen: set = set()
    for i, axes in enumerate(out):
        if axes is None:
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        kept = tuple(a for a in tup if a not in seen)
        seen.update(kept)
        if not kept:
            out[i] = None
        elif len(kept) == 1:
            out[i] = kept[0]
        else:
            out[i] = kept
    # trailing Nones are implicit
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    spec = logical_to_spec(logical, dim_sizes=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def named_sharding(
    mesh: Mesh,
    rules: ShardingRules,
    logical: Sequence[Optional[str]],
    dim_sizes: Optional[Sequence[int]] = None,
    require_divisible: bool = False,
) -> NamedSharding:
    return NamedSharding(
        mesh,
        logical_to_spec(
            logical, mesh=mesh, rules=rules, dim_sizes=dim_sizes,
            require_divisible=require_divisible,
        ),
    )


def _is_axes_leaf(x) -> bool:
    return x is None or (
        isinstance(x, tuple)
        and len(x) > 0
        and all(e is None or isinstance(e, str) for e in x)
    )


def tree_shardings(mesh: Mesh, rules: ShardingRules, shapes_tree, axes_tree):
    """Build a NamedSharding pytree from a ShapeDtypeStruct tree and a parallel
    tree of logical-axis tuples (None leaf => replicated).

    Mapped over ``axes_tree`` first so tuple leaves are not traversed as
    subtrees.
    """

    def one(axes_leaf, shape_leaf):
        if axes_leaf is None:
            return NamedSharding(mesh, P())
        return named_sharding(
            mesh, rules, axes_leaf, dim_sizes=shape_leaf.shape,
            require_divisible=True,
        )

    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=_is_axes_leaf)


def column_parallel_shardings(mesh: Mesh, rules: ShardingRules, params_tree,
                              axes_tree):
    """Param shardings that keep every GEMM contraction shard-local.

    Masks each logical-axes leaf down to its *final* (output/N) dimension
    before resolving against ``rules`` — e.g. wq ("embed", "qkv") becomes
    (None, "qkv") — so a parameter is only ever split along the columns it
    *produces*.  Combined with :data:`DECODE_RULES` (activations replicated
    at the constrain seams) this yields a tensor-parallel step whose every
    partial product is computed with the full K extent in the original
    reduction order: bitwise-equal to the single-device step, float and q16.

    ``params_tree`` may be the float param tree or the quantized exec tree
    (QTensor leaves expose ``.shape``); 1-D leaves (biases, norm scales)
    keep their single logical name and shard iff the rules map it.
    """

    def one(axes_leaf, param_leaf):
        if axes_leaf is None:
            return NamedSharding(mesh, P())
        masked = (None,) * (len(axes_leaf) - 1) + (axes_leaf[-1],)
        return named_sharding(
            mesh, rules, masked, dim_sizes=param_leaf.shape,
            require_divisible=True,
        )

    return jax.tree.map(one, axes_tree, params_tree, is_leaf=_is_axes_leaf)
