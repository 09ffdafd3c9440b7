"""The paper's own case-study networks (AlexNet / VGG16 / LeNet) built on the
unified compute unit.

Per the paper's HW/SW partitioning: conv + FC layers run on the "PL plane"
(the Template compute unit — direct Pallas conv / im2col GEMM / Q2.14 fixed
point), while pooling, flatten and softmax are "PS plane" XLA ops.  Bias and
ReLU are fused into the compute unit's write-back (DESIGN.md §3).
``quantized=True`` inference reproduces the deployed numerics: weights and
activations fake- or fully-quantized to Q2.14 around every GEMM.

Following the paper's plan-then-execute flow, :func:`plan_cnn` compiles the
whole network's kernel routes and Pallas blocks **once** per (template
config, spec, input shape) and every ``cnn_forward`` step reuses that plan —
no per-call DSE, no per-call routing.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.engine import ConvPlan, GemmPlan, register_plan_store, validate_policy
from repro.core.quantization import (
    NumericsPolicy,
    Q2_14,
    QFormat,
    QTensor,
    fake_quant_fmt,
)
from repro.core.template import Template
from repro.core.tiling import ceil_div
from repro.runtime.spans import layer

__all__ = [
    "CNNSpec",
    "ALEXNET",
    "VGG16",
    "LENET",
    "CNN_ZOO",
    "NetworkPlan",
    "init_cnn",
    "plan_cnn",
    "cnn_layer_names",
    "quantize_cnn_params",
    "calibrate_cnn_policy",
    "calibrate_cnn_precision",
    "cnn_forward",
    "cnn_forward_ref",
]


@dataclasses.dataclass(frozen=True)
class CNNSpec:
    name: str
    input_hw: int
    input_ch: int
    n_classes: int
    # conv stages: (out_ch, k, stride, pad, pool) — pool is maxpool window (0 = none)
    convs: tuple
    # fc widths (excluding the final classifier)
    fcs: tuple


ALEXNET = CNNSpec(
    "alexnet", 224, 3, 1000,
    convs=(
        (64, 11, 4, 2, 3),
        (192, 5, 1, 2, 3),
        (384, 3, 1, 1, 0),
        (256, 3, 1, 1, 0),
        (256, 3, 1, 1, 3),
    ),
    fcs=(4096, 4096),
)

VGG16 = CNNSpec(
    "vgg16", 224, 3, 1000,
    convs=(
        (64, 3, 1, 1, 0), (64, 3, 1, 1, 2),
        (128, 3, 1, 1, 0), (128, 3, 1, 1, 2),
        (256, 3, 1, 1, 0), (256, 3, 1, 1, 0), (256, 3, 1, 1, 2),
        (512, 3, 1, 1, 0), (512, 3, 1, 1, 0), (512, 3, 1, 1, 2),
        (512, 3, 1, 1, 0), (512, 3, 1, 1, 0), (512, 3, 1, 1, 2),
    ),
    fcs=(4096, 4096),
)

LENET = CNNSpec(
    "lenet", 32, 1, 10,
    convs=((6, 5, 1, 0, 2), (16, 5, 1, 0, 2)),
    fcs=(120, 84),
)

CNN_ZOO = {c.name: c for c in (ALEXNET, VGG16, LENET)}


def _maxpool(x, w: int):
    """NHWC max pool, window w, stride w (PS-plane op).

    QTensor inputs pool on the integer raws directly (int16 or int8 per the
    grid's rung): dequantization is monotone, so max-of-raw == raw-of-max
    and the activation never leaves the fixed-point grid for pooling
    (DESIGN.md §8).
    """
    if isinstance(x, QTensor):
        init = jnp.array(jnp.iinfo(x.raw.dtype).min, x.raw.dtype)
        return QTensor(
            jax.lax.reduce_window(
                x.raw, init, jax.lax.max, (1, w, w, 1), (1, w, w, 1), "VALID"
            ),
            x.fmt,
        )
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, w, w, 1), (1, w, w, 1), "VALID"
    )


# -- spatial (H-slab) sharding helpers (DESIGN.md §10) -----------------------


def _on_raw(x, f):
    """Apply ``f`` to a float array or to a QTensor's int16 raws (layout ops
    are grid-transparent)."""
    return QTensor(f(x.raw), x.fmt) if isinstance(x, QTensor) else f(x)


def _to_slabs(x, shards: int):
    """NHWC -> slab-major (S, N, lx, W, C) with ``lx = ceil(H / S)`` and a
    zero tail — the layout every spatial op preserves (buffer row ``r`` of
    slab ``s`` holds global row ``s·lx + r``, zero beyond H)."""

    def f(v):
        n, h, w, c = v.shape
        lx = -(-h // shards)
        vp = jnp.pad(v, ((0, 0), (0, shards * lx - h), (0, 0), (0, 0)))
        return jnp.moveaxis(vp.reshape(n, shards, lx, w, c), 1, 0)

    return _on_raw(x, f)


def _gather_slabs(x, h: int):
    """Slab-major (S, N, l, W, C) -> NHWC (N, h, W, C): the conv→FC flatten
    seam.  Correct even for a ragged tail shard by the slab invariant — the
    buffer rows past the global extent are zeros and land past row ``h``."""

    def f(v):
        s, n, l = v.shape[0], v.shape[1], v.shape[2]
        return jnp.moveaxis(v, 0, 1).reshape(n, s * l, *v.shape[3:])[:, :h]

    return _on_raw(x, f)


def _maxpool_spatial(x, w: int, ph):
    """Spatially-sharded max pool: a pool is just a halo op with ``kh = w``,
    ``stride = w``, ``pad = 0`` — exchange the (up, dn) rows the seam needs,
    pool each shard's window, and re-zero the ragged tail rows so the next
    seam's halo reads stay exact."""
    from repro.parallel import sharding as sh

    def f(v):
        v = sh.constrain_slabs(v, ph.axis)
        ext = sh.halo_exchange(v, ph)  # (S, N, win, W, C)
        init = (
            jnp.array(jnp.iinfo(v.dtype).min, v.dtype)
            if jnp.issubdtype(v.dtype, jnp.integer)
            else jnp.array(-jnp.inf, v.dtype)
        )
        out = jax.lax.reduce_window(
            ext, init, jax.lax.max, (1, 1, w, w, 1), (1, 1, w, w, 1), "VALID"
        )
        return sh.constrain_slabs(sh.mask_slab_rows(out, ph), ph.axis)

    return _on_raw(x, f)


def init_cnn(key, spec: CNNSpec, dtype=jnp.float32, scale: float = 0.5):
    """He-style init, scaled into the Q2.14 representable range [-2, 2)."""
    params = {"convs": [], "fcs": []}
    ch = spec.input_ch
    hw = spec.input_hw
    keys = jax.random.split(key, len(spec.convs) + len(spec.fcs) + 1)
    ki = 0
    for (cout, k, stride, pad, pool) in spec.convs:
        fan_in = k * k * ch
        w = jax.random.normal(keys[ki], (k, k, ch, cout)) * (scale * fan_in ** -0.5)
        b = jnp.zeros((cout,))
        params["convs"].append({"w": w.astype(dtype), "b": b.astype(dtype)})
        ki += 1
        hw = (hw + 2 * pad - k) // stride + 1
        if pool:
            hw //= pool
        ch = cout
    feat = hw * hw * ch
    widths = (*spec.fcs, spec.n_classes)
    fan = feat
    for wd in widths:
        w = jax.random.normal(keys[ki], (fan, wd)) * (scale * fan ** -0.5)
        b = jnp.zeros((wd,))
        params["fcs"].append({"w": w.astype(dtype), "b": b.astype(dtype)})
        ki += 1
        fan = wd
    return params


@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """Compiled per-layer execution plan for one CNN (plan-then-execute)."""

    convs: tuple  # ConvPlan per conv stage
    fcs: tuple  # GemmPlan per FC layer
    # spatial (H-slab) sharding, DESIGN.md §10 — shards == 1 means unsharded
    spatial: int = 1  # H-slab shard count S
    spatial_axis: Optional[str] = None  # mesh axis the slab dim shards over
    pool_halos: tuple = ()  # per conv stage: SpatialHalo of its pool, or None
    feat_h: int = 0  # global H entering the conv→FC flatten gather

    def describe(self) -> list[str]:
        """One line per layer: route, τ, halo regime, spatial tiles and the
        modeled VMEM bytes of a grid step (the budget the kernel is compiled
        with must hold them).

        The human-readable face of the plan — ``benchmarks/kernel_table.py``
        and ``chip_smoke.py`` print it so route/tile regressions show up in
        benchmark diffs between PRs.
        """
        lines = []
        for i, cp in enumerate(self.convs):
            # (𝒯, ℭ) tile grid and per-tile output dims, e.g.
            # "tiles=2x4(256rx128c)"; 0 = the whole extent on that axis
            tiling = ""
            if cp.spatial_tiles > 1 or cp.col_tiles > 1:
                tiling = (
                    f" tiles={cp.spatial_tiles}x{cp.col_tiles}"
                    f"({cp.tile_rows}rx{cp.tile_cols}c)"
                )
            halo = ""
            if cp.halo is not None:
                halo = (
                    f" halo=S{cp.halo.shards}"
                    f"(up{cp.halo.up},dn{cp.halo.dn},win{cp.halo.win})"
                )
            mode = cp.halo_mode if cp.route == "direct" else "-"
            lines.append(
                f"conv{i}: route={cp.route} tau={cp.tau} halo_mode={mode}"
                f"{tiling} vmem={cp.vmem_bytes}B "
                f"({cp.vmem_bytes / 2**20:.1f}MiB) gemm={cp.gemm}{halo}"
            )
        for i, gp in enumerate(self.fcs):
            blk = steps = None
            if gp.block:
                b = gp.block
                blk = (b.bm, b.bn, b.bk)
                steps = ceil_div(gp.m, b.bm) * ceil_div(gp.n, b.bn) * ceil_div(gp.k, b.bk)
            lines.append(f"fc{i}: m={gp.m} n={gp.n} k={gp.k} block={blk} steps={steps}")
        return lines


_NETWORK_PLANS: dict = {}
register_plan_store(_NETWORK_PLANS)


def plan_cnn(
    tpl: Template,
    spec: CNNSpec,
    input_shape: Sequence[int],
    *,
    force_route: Optional[str] = None,
    mesh=None,
    partition=None,
    spatial=None,
) -> NetworkPlan:
    """Compile the network's kernel routes and Pallas blocks once.

    Memoized per (template config, spec, input shape, mesh topology):
    repeated calls — and every training/serving step — reuse the same plan
    object, so the DSE grid search runs at most once per distinct GEMM shape
    in the network.  ``force_route`` overrides conv routing (e.g. "im2col"
    for A/B tests).  With ``mesh`` every layer is planned at its *local*
    per-shard shape (batch over the partition's M axes, output channels /
    FC widths over its N axes); the inter-layer geometry stays logical since
    activations are gathered between layers.

    ``spatial`` (a shard count or mesh axis name) plans the cross-chip
    H-slab partition instead (DESIGN.md §10): every conv and pool is planned
    at its halo-augmented local slab (the seams chain — each layer's slab
    layout is the previous layer's per-shard output rows), batch and Cout
    stay shard-local, and the FCs are planned at the logical shape (the
    flatten seam gathers the slabs, so ``mesh``/``partition`` do not apply
    to spatial plans).

    Recorded as the set-up span ``plan``, whose ``dse_searches`` is the
    PlanRegistry's miss delta (0 when every layer was a hit) and whose
    ``skinny_gemms`` counts the searches among them that took the skinny-M
    GEMM branch (an FC head at batch 1-8: 3 for VGG16).
    """
    with layer("plan") as attrs:
        with tpl.engine.plan_cache.scope() as delta:
            plan = _plan_cnn(tpl, spec, input_shape, force_route, mesh,
                             partition, spatial)
        attrs["dse_searches"] = delta["misses"]
        attrs["skinny_gemms"] = delta["skinny"]
    return plan


def _plan_cnn(tpl, spec, input_shape, force_route, mesh, partition, spatial):
    spatial_n, spatial_ax = 1, None
    if spatial is not None:
        from repro.parallel.sharding import spatial_shards

        spatial_n, spatial_ax = spatial_shards(spatial, mesh)
    mesh_key = None
    if mesh is not None:
        mesh_key = (
            tuple((a, mesh.shape[a]) for a in mesh.axis_names),
            partition,
        )
    key = (
        tpl.config, spec, tuple(input_shape), force_route, mesh_key,
        (spatial_n, spatial_ax),
    )
    plan = _NETWORK_PLANS.get(key)
    if plan is not None:
        return plan
    eng = tpl.engine
    n, hh, ww, ch = input_shape
    if spatial_n > 1:
        from repro.parallel.sharding import plan_spatial_halo

        lx = -(-hh // spatial_n)  # the _to_slabs layout of the input
        convs, pool_halos = [], []
        for cout, k, stride, pad, pool in spec.convs:
            hs = plan_spatial_halo(
                hh, k, stride, pad, spatial_n, axis=spatial_ax, lx=lx
            )
            cp = eng.plan_conv(
                (n, hh, ww, ch), (k, k, ch, cout), stride=stride,
                padding=pad, route=force_route, spatial=hs,
            )
            convs.append(cp)
            lx = hs.lo
            hh = (hh + 2 * pad - k) // stride + 1
            ww = (ww + 2 * pad - k) // stride + 1
            if pool:
                ph = plan_spatial_halo(
                    hh, pool, pool, 0, spatial_n, axis=spatial_ax, lx=lx
                )
                pool_halos.append(ph)
                lx = ph.lo
                hh //= pool
                ww //= pool
            else:
                pool_halos.append(None)
            ch = cout
        fan = hh * ww * ch
        fcs = []
        for wd in (*spec.fcs, spec.n_classes):
            fcs.append(eng.plan_gemm(n, wd, fan))
            fan = wd
        plan = NetworkPlan(
            convs=tuple(convs), fcs=tuple(fcs), spatial=spatial_n,
            spatial_axis=spatial_ax, pool_halos=tuple(pool_halos), feat_h=hh,
        )
        _NETWORK_PLANS[key] = plan
        return plan
    convs = []
    for cout, k, stride, pad, pool in spec.convs:
        cp = eng.plan_conv(
            (n, hh, ww, ch), (k, k, ch, cout), stride=stride, padding=pad,
            route=force_route, mesh=mesh, partition=partition,
        )
        convs.append(cp)
        hh = (hh + 2 * cp.pad - k) // stride + 1
        ww = (ww + 2 * cp.pad - k) // stride + 1
        if pool:
            hh //= pool
            ww //= pool
        ch = cout
    fan = hh * ww * ch
    fcs = []
    for wd in (*spec.fcs, spec.n_classes):
        fcs.append(eng.plan_gemm(n, wd, fan, mesh=mesh, partition=partition))
        fan = wd
    plan = NetworkPlan(convs=tuple(convs), fcs=tuple(fcs))
    _NETWORK_PLANS[key] = plan
    return plan


def cnn_layer_names(spec: CNNSpec) -> tuple:
    """The per-layer precision-DSE names, forward order: conv0.. then fc0..
    (the final entry is the classifier).  A layer's name keys its *input*
    activation grid in ``NumericsPolicy.layer_fmts`` and the plan store."""
    return tuple(f"conv{i}" for i in range(len(spec.convs))) + tuple(
        f"fc{i}" for i in range(len(spec.fcs) + 1)
    )


def quantize_cnn_params(tpl: Template, spec: CNNSpec, params,
                        policy: NumericsPolicy):
    """Quantize-once CNN parameter preparation (DESIGN.md §8, §11).

    Conv and FC weights become per-tensor max-abs calibrated QTensors;
    biases pin to the layer's activation grid.  Under a mixed policy each
    layer calibrates against its *own* input grid (``policy.fmt_for``): an
    int8-assigned layer gets int8 weights and the 24/23-bit accumulator
    headroom budget instead of 16/15.  Memoized by parameter-tree identity
    (and policy — ``layer_fmts`` is part of the key) in the engine's qparam
    cache — repeated inference calls never touch the float weights again.
    """
    policy = validate_policy(tpl.config, policy)
    if not policy.quantized:
        return params
    eng = tpl.engine
    names = cnn_layer_names(spec)

    def build():
        def qdense(leaf, name):
            # conv (kh, kw, cin, cout) reduces over kh*kw*cin; fc (k, n)
            # over k — the accumulator headroom rule bounds both
            axes = tuple(range(leaf["w"].ndim - 1))
            fmt = policy.fmt_for(name)
            return {
                "w": eng.quantize_weight(leaf["w"], policy,
                                         contraction_axes=axes,
                                         fused_bias=True,
                                         act_fmt=fmt,
                                         total_bits=fmt.total_bits),
                "b": eng.quantize_weight(leaf["b"], policy, fmt=fmt),
            }

        nc = len(params["convs"])
        return {
            "convs": [qdense(p, names[i]) for i, p in enumerate(params["convs"])],
            "fcs": [qdense(p, names[nc + i]) for i, p in enumerate(params["fcs"])],
        }

    with layer("quantize_params") as attrs:
        built = eng.counters["qparam_builds"]
        qp = eng.qparams_for(params, policy, build)
        attrs["built"] = eng.counters["qparam_builds"] - built
    return qp


def calibrate_cnn_policy(tpl: Template, spec: CNNSpec, params, x,
                         base: Optional[NumericsPolicy] = None) -> NumericsPolicy:
    """Max-abs activation calibration for the CNN zoo: one eager forward over
    a calibration batch picks the activation grid (see
    ``transformer.calibrate_policy`` for the transformer twin).  A QAT
    network whose activations fit [-2, 2) keeps the paper's Q2.14."""
    import dataclasses

    base = base or NumericsPolicy("q16")
    with layer("calibrate"):
        probe_qp = quantize_cnn_params(tpl, spec, params, base)
        fmt = tpl.engine.calibrate_activation_format(
            lambda: cnn_forward(tpl, spec, probe_qp, x, policy=base)
        )
    policy = dataclasses.replace(base, fmt=fmt)
    if policy != base:
        tpl.engine.drop_qparams(params, base)  # release the probe tree
    return policy


def calibrate_cnn_precision(
    tpl: Template,
    spec: CNNSpec,
    params,
    x,
    *,
    budget: float = 0.99,
    policy: Optional[NumericsPolicy] = None,
    drift: Optional[dict] = None,
    ref=None,
) -> NumericsPolicy:
    """The drift-aware per-layer precision DSE for a CNN (DESIGN.md §11).

    Warm path: when the PlanRegistry holds a pinned precision choice for
    *every* layer of ``spec`` (loaded from the v3 plan store), the mixed
    policy is rebuilt from the pins — zero forwards, zero searches, each
    layer a registry hit (the ``REPRO_PLAN_ASSERT_WARM`` contract).

    Cold path: measure each layer's *solo-flip* drift — run the network
    with only that layer's activations dropped to the int8 rung of the
    calibrated grid and record the argmax agreement vs the float reference
    (``drift`` short-circuits the sweep with pre-measured rows, e.g. from
    ``benchmarks/precision_drift.py``'s JSON) — then assign int8 wherever
    the agreement meets ``budget`` (:func:`repro.core.dse.choose_precision`)
    and pin every choice with ``source: measured`` provenance.

    ``ref`` overrides the reference class predictions (an (N,) argmax
    array).  The default is the pure-float forward; a QAT-trained network
    should pass the argmax of its *fake-quant* float forward — the clamp
    is part of the trained model, so the unclamped float path is not the
    semantics deployment must agree with (see examples/train_lenet_q214).
    """
    import dataclasses

    from repro.core import dse
    from repro.core.quantization import int8_rung

    policy = policy or calibrate_cnn_policy(tpl, spec, params, x)
    eng = tpl.engine
    reg = eng.plan_cache
    hw = tpl.config.hw
    names = cnn_layer_names(spec)
    low = int8_rung(policy.fmt)
    if low is None:
        return policy  # the calibrated range has no int8 rung
    pins = {name: reg.precision_for(spec.name, name, hw) for name in names}
    if all(p is not None for p in pins.values()):
        fmts = tuple(sorted(((n, p.fmt) for n, p in pins.items()),
                            key=lambda kv: kv[0]))
        return dataclasses.replace(policy, name="mixed", layer_fmts=fmts)
    if ref is None:
        ref = jnp.argmax(cnn_forward(tpl, spec, params, x), axis=-1)

    def probe_agreement(fmts):
        probe = dataclasses.replace(policy, name="mixed", layer_fmts=fmts)
        qp = quantize_cnn_params(tpl, spec, params, probe)
        got = jnp.argmax(cnn_forward(tpl, spec, qp, x, policy=probe), axis=-1)
        eng.drop_qparams(params, probe)  # release the probe tree
        return float(jnp.mean(got == ref))

    if drift is None:
        drift = {name: probe_agreement(((name, low),)) for name in names}
    chosen = dse.choose_precision(drift, budget, policy.fmt, low)

    def full_plan():
        return tuple(sorted(((n, chosen.get(n, policy.fmt)) for n in names),
                            key=lambda kv: kv[0]))

    # solo-flip drifts compose: the joint plan can land below the *network*
    # budget even when every member met it alone.  Greedily revert the int8
    # layer with the lowest measured agreement until the composed network
    # meets the budget — the accuracy constraint is on the network, not the
    # per-layer probes.
    while probe_agreement(full_plan()) < budget:
        int8s = [n for n in names if chosen[n].total_bits == 8]
        if not int8s:
            break
        chosen[min(int8s, key=lambda n: (drift[n], n))] = policy.fmt
    for name in names:
        reg.pin_precision(
            spec.name, name, chosen.get(name, policy.fmt),
            drift=drift.get(name), spec=hw, source="measured",
        )
    fmts = tuple(sorted(
        ((n, chosen.get(n, policy.fmt)) for n in names), key=lambda kv: kv[0]
    ))
    return dataclasses.replace(policy, name="mixed", layer_fmts=fmts)


def cnn_forward(
    tpl: Template,
    spec: CNNSpec,
    params,
    x: jax.Array,
    *,
    quantized: bool = False,
    fmt: QFormat = Q2_14,
    plan: Optional[NetworkPlan] = None,
    policy: Optional[NumericsPolicy] = None,
) -> jax.Array:
    """x: (N, H, W, C) -> logits (N, n_classes).

    ``quantized``: Q2.14 both weights and activations around every GEMM
    (the deployed fixed-point numerics); the GEMM itself runs on whatever
    backend ``tpl`` selects (XLA / Pallas float / Pallas q16).  Bias + ReLU
    (and, when quantized, the post-activation Q2.14 snap) are fused into the
    compute unit's write-back.  ``plan`` defaults to the memoized
    :func:`plan_cnn` result for this (config, spec, input shape).

    ``policy``: a quantized :class:`NumericsPolicy` (with a
    :func:`quantize_cnn_params` tree) runs the *whole network* grid-resident:
    the input is quantized exactly once, every conv/FC (ReLU fused in-kernel)
    and every maxpool stays on the int16 grid, and the only dequantization is
    the exact int32 read-out of the final classifier — one quantize and one
    dequantize for the entire forward (DESIGN.md §8).
    """
    with layer("forward", traced=isinstance(x, jax.core.Tracer)):
        if policy is not None and policy.quantized and isinstance(
            params["convs"][0]["w"], QTensor
        ):
            return _forward_q(tpl, spec, params, x, plan, policy)
        return _forward_float(tpl, spec, params, x, quantized, fmt, plan)


# Every layer of the forward runs under a span named as
# bench/roofline.py:layer_counts names it (conv{i}, fc{i}), plus quantize,
# pool{i} (numbered by the conv it follows) and gather: its device
# operations carry the name in their op_name, its trace time a host span.


def _forward_q(tpl, spec, params, x, plan, policy):
    eng = tpl.engine
    plan = plan or plan_cnn(tpl, spec, x.shape)
    halos = plan.pool_halos or (None,) * len(plan.convs)
    names = cnn_layer_names(spec)
    # each layer writes its *successor's* input grid in-kernel — the
    # mixed-boundary epilogue (DESIGN.md §11): an int8 layer feeds an
    # int16 layer (and vice versa) with zero float round-trips.  Pooling
    # is grid-transparent, so conv output and pooled map share the grid.
    with layer("quantize"):
        h = eng.quant(x, policy.fmt_for(names[0]))
    if plan.spatial > 1:
        h = _to_slabs(h, plan.spatial)
    nc = len(plan.convs)
    for i, (p, (cout, k, stride, pad, pool), cp, ph) in enumerate(zip(
        params["convs"], spec.convs, plan.convs, halos
    )):
        with layer(f"conv{i}"):
            h = tpl.conv2d(h, p["w"], stride=stride, padding=pad,
                           bias=p["b"], relu=True,
                           qout=policy.fmt_for(names[i + 1]), plan=cp)
        if pool:
            with layer(f"pool{i}"):
                h = _maxpool_spatial(h, pool, ph) if ph is not None else _maxpool(h, pool)
    h = _flatten(h, plan)

    def head(h, fcs):
        last = len(fcs) - 1
        for i, (p, gp) in enumerate(zip(fcs, plan.fcs)):
            with layer(f"fc{i}"):
                if i < last:
                    h = tpl.linear(h, p["w"], p["b"], relu=True,
                                   qout=policy.fmt_for(names[nc + i + 1]),
                                   plan=gp)
                else:
                    # final classifier: exact accumulator read-out (the
                    # single counted dequantize of the whole network)
                    h = tpl.linear(h, p["w"], p["b"], wide=True, plan=gp)
        return h

    return _fc_head(plan, head, h, params["fcs"])


def _forward_float(tpl, spec, params, x, quantized, fmt, plan):
    plan = plan or plan_cnn(tpl, spec, x.shape)
    halos = plan.pool_halos or (None,) * len(plan.convs)
    fq = (lambda a: fake_quant_fmt(a, fmt)) if quantized else (lambda a: a)
    qo = fmt if quantized else None
    h = fq(x)
    if plan.spatial > 1:
        h = _to_slabs(h, plan.spatial)
    for i, (p, (cout, k, stride, pad, pool), cp, ph) in enumerate(zip(
        params["convs"], spec.convs, plan.convs, halos
    )):
        with layer(f"conv{i}"):
            h = tpl.conv2d(
                h, fq(p["w"]), stride=stride, padding=pad,
                bias=fq(p["b"]), relu=True, qout=qo, plan=cp,
            )
        if pool:
            with layer(f"pool{i}"):
                h = _maxpool_spatial(h, pool, ph) if ph is not None else _maxpool(h, pool)
    h = _flatten(h, plan)

    def head(h, fcs):
        last = len(fcs) - 1
        for i, (p, gp) in enumerate(zip(fcs, plan.fcs)):
            with layer(f"fc{i}"):
                h = tpl.linear(
                    h, fq(p["w"]), fq(p["b"]),
                    relu=i < last, qout=qo if i < last else None, plan=gp,
                )
        return h

    return _fc_head(plan, head, h, params["fcs"])


def _flatten(h, plan: NetworkPlan):
    """The conv→FC seam: gather the H slabs of a spatial plan, flatten."""
    with layer("gather"):
        if plan.spatial > 1:
            h = _gather_slabs(h, plan.feat_h)
        return h.reshape(h.shape[0], -1)


def _fc_head(plan: NetworkPlan, head, h, fcs):
    """Run the FC layers after the flatten seam.  Under a spatial plan the
    gathered features are replicated and so is the head: every device runs
    it whole (:func:`~repro.parallel.sharding.on_every_device` — a Pallas
    kernel in a multi-device program has to be placed explicitly)."""
    if plan.spatial > 1:
        from repro.parallel.sharding import on_every_device

        return on_every_device(head, h, fcs)
    return head(h, fcs)


def cnn_forward_ref(spec: CNNSpec, params, x: jax.Array, *,
                    policy: Optional[NumericsPolicy] = None) -> jax.Array:
    """Plain-``jax.numpy`` reference of :func:`cnn_forward` — no template,
    no plan, no Pallas — for checking the engine's forward on a chip.

    Float (``policy`` None or float): f32 convs and dense layers at full f32
    precision (``Precision.HIGHEST``), bias, ReLU, max pool.  Quantized
    (a quantized ``policy`` with a :func:`quantize_cnn_params` tree): the
    grid-resident semantics of :func:`cnn_forward` op for op on the int
    raws — the input quantized once, every conv/FC the mixed-format oracle
    (:func:`repro.kernels.ref.conv2d_qtensor_ref`,
    :func:`repro.core.quantization.qtensor_matmul_ref`) writing its
    successor's grid, pools on the raws, and the classifier's int32
    accumulator read out exactly — so it is bit-identical to the engine.
    """
    from repro.core.quantization import qtensor_matmul_ref, quantize
    from repro.kernels.ref import conv2d_qtensor_ref

    hi = jax.lax.Precision.HIGHEST
    if policy is None or not policy.quantized:
        h = x.astype(jnp.float32)
        for p, (cout, k, stride, pad, pool) in zip(params["convs"], spec.convs):
            h = jax.lax.conv_general_dilated(
                h, p["w"].astype(jnp.float32), (stride, stride),
                [(pad, pad), (pad, pad)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi,
            )
            h = jnp.maximum(h + p["b"], 0.0)
            if pool:
                h = _maxpool(h, pool)
        h = h.reshape(h.shape[0], -1)
        last = len(params["fcs"]) - 1
        for i, p in enumerate(params["fcs"]):
            h = jnp.dot(h, p["w"].astype(jnp.float32), precision=hi) + p["b"]
            if i < last:
                h = jnp.maximum(h, 0.0)
        return h
    names = cnn_layer_names(spec)
    fmt0 = policy.fmt_for(names[0])
    h = QTensor(quantize(x, fmt0), fmt0)
    nc = len(spec.convs)
    for i, (p, (cout, k, stride, pad, pool)) in enumerate(
        zip(params["convs"], spec.convs)
    ):
        h = conv2d_qtensor_ref(h, p["w"], policy.fmt_for(names[i + 1]),
                               p["b"], stride=stride, padding=pad, relu=True)
        if pool:
            h = _maxpool(h, pool)
    h = h.reshape(h.shape[0], -1)
    last = len(params["fcs"]) - 1
    for i, p in enumerate(params["fcs"]):
        if i < last:
            h = qtensor_matmul_ref(h, p["w"], policy.fmt_for(names[nc + i + 1]),
                                   bias=p["b"], relu=True)
            continue
        acc_frac = h.fmt.frac_bits + p["w"].fmt.frac_bits
        acc = jnp.dot(h.raw.astype(jnp.int32), p["w"].raw.astype(jnp.int32),
                      preferred_element_type=jnp.int32)
        acc = acc + (p["b"].raw.astype(jnp.int32)
                     << (acc_frac - p["b"].fmt.frac_bits))
        return acc.astype(jnp.float32) * 2.0 ** -acc_frac
