"""The bottleneck residual form (``"form": "resnet"``): ResNet v1.5 as the
configuration's ``stem``, ``stem_pool`` and ``stages`` give it.

* the stem ``[out_ch, k, stride, pad]``: conv, ReLU;
* the max pool ``[window, stride, pad]`` (overlapping where the stride is
  below the window; padding never wins a window);
* per stage ``[blocks, mid, out, stride]``, ``blocks`` bottlenecks, each a
  1×1 conv to ``mid`` with ReLU, a 3×3 conv (pad 1) with ReLU that carries
  the stage's stride on the stage's first block (v1.5), a 1×1 conv to
  ``out`` without ReLU; the shortcut is the block's input, or a 1×1
  projection with the block's stride and no ReLU where the width or the
  stride changes; the two are added, then ReLU;
* a global average pool, FC layers of ``fcs`` widths with ReLU and the
  ``n_classes`` classifier.

Convs are numbered in that order, each block's projection after its third
conv; ``conv{i}``, ``pool0``, ``add{j}``, ``avgpool`` and ``fc{i}`` are the
program's layer scopes.  Batch norm is folded into the weights
(``init_params``).  The references here and their integer semantics are
written from this description, independent of the system under test; they
share only the layer table's grid rules (``bench/reference.py``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.forms import Program

#: ``models/cnn.py:cnn_forward``'s layer scopes for this form
layer_pattern = r"conv\d+|pool\d+|add\d+|avgpool|fc\d+"
#: pools and adds are XLA operations, not kernels
xla_layer_pattern = r"pool\d+|add\d+|avgpool"


def _blocks(cfg: dict):
    """Per bottleneck: (stride, mid, out, whether it projects)."""
    cin = cfg["stem"][0]
    for blocks, mid, out, stride in cfg["stages"]:
        for b in range(blocks):
            s = stride if b == 0 else 1
            yield s, mid, out, s != 1 or cin != out
            cin = out


def convs(cfg: dict) -> list[dict]:
    """Per conv in forward order: its input ``hw``, ``cin``, ``cout``,
    ``k``, ``stride``, ``pad``, ``relu`` and whether it ends a block's
    branch (``last``, the conv whose folded batch norm has the γ)."""
    hw, ch = cfg["input_hw"], cfg["input_ch"]
    out = []

    def conv(cout, k, stride, pad, relu=True, last=False, at=None):
        h, c = at or (hw, ch)
        out.append(dict(hw=h, cin=c, cout=cout, k=k, stride=stride, pad=pad,
                        relu=relu, last=last))
        return (h + 2 * pad - k) // stride + 1, cout

    hw, ch = conv(*cfg["stem"])
    window, stride, pad = cfg["stem_pool"]
    hw = (hw + 2 * pad - window) // stride + 1
    for s, mid, wide, proj in _blocks(cfg):
        block_in = (hw, ch)
        hw, ch = conv(mid, 1, 1, 0)
        hw, ch = conv(mid, 3, s, 1)
        hw, ch = conv(wide, 1, 1, 0, relu=False, last=True)
        if proj:
            conv(wide, 1, s, 0, relu=False, at=block_in)
    return out


def init_params(cfg: dict, key: jax.Array) -> dict:
    """``{"convs": [{"w", "b"}], "fcs": [{"w", "b"}]}`` in float32, convs in
    forward order: He-init weights, N(0, 0.01) biases.  Batch norm is
    folded in: the identity, except after each block's last conv, whose
    weights its γ (``folded_bn_gamma``) scales."""
    layers = [((c["k"], c["k"], c["cin"], c["cout"]), cfg["folded_bn_gamma"] if c["last"] else 1.0)
              for c in convs(cfg)]
    fan = cfg["stages"][-1][2]
    for width in (*cfg["fcs"], cfg["n_classes"]):
        layers.append(((fan, width), 1.0))
        fan = width
    keys = jax.random.split(key, 2 * len(layers))
    nc = len(layers) - len(cfg["fcs"]) - 1
    tree = {"convs": [], "fcs": []}
    for i, (shape, gamma) in enumerate(layers):
        fan_in = math.prod(shape[:-1])
        w = jax.random.normal(keys[2 * i], shape, jnp.float32) * (gamma * (2.0 / fan_in) ** 0.5)
        b = jax.random.normal(keys[2 * i + 1], shape[-1:], jnp.float32) * reference.BIAS_STD
        tree["convs" if i < nc else "fcs"].append({"w": w, "b": b})
    return tree


def program(cfg: dict) -> Program:
    """The program's CNN entry points on the configuration's residual spec,
    each looked up when it is called."""
    from repro.models import cnn as C

    spec = C.CNNSpec(cfg["network"], cfg["input_hw"], cfg["input_ch"], cfg["n_classes"],
                     fcs=tuple(cfg["fcs"]), stem=tuple(cfg["stem"]),
                     stem_pool=tuple(cfg["stem_pool"]),
                     stages=tuple(tuple(s) for s in cfg["stages"]))
    return Program(
        calibrate=lambda tpl, weights, x, base: C.calibrate_cnn_policy(
            tpl, spec, weights, x, base=base),
        quantize=lambda tpl, weights, policy: C.quantize_cnn_params(tpl, spec, weights, policy),
        plan=lambda tpl, shape, **placement: C.plan_cnn(tpl, spec, shape, **placement),
        forward=lambda tpl, params, x, policy, plan: C.cnn_forward(
            tpl, spec, params, x, policy=policy, plan=plan),
        layer_names=lambda: C.cnn_layer_names(spec),
    )


def _walk(cfg: dict, params: dict, x, conv, pool, add, avgpool, dense):
    """The forward, its operations given: ``conv(i, h)`` (the i-th conv with
    its ReLU or not), ``pool(h, window, stride, pad)``, ``add(a, b)`` (with
    its ReLU), ``avgpool(h)`` and ``dense(i, h)`` (FC i, ReLU on all but the
    classifier)."""
    h = pool(conv(0, x), *cfg["stem_pool"])
    i = 1
    for _, _, _, proj in _blocks(cfg):
        t = conv(i + 2, conv(i + 1, conv(i, h)))
        h = add(t, conv(i + 3, h) if proj else h)
        i += 3 + proj
    h = avgpool(h)
    for j in range(len(params["fcs"])):
        h = dense(j, h)
    return h


def _maxpool(h, window: int, stride: int, pad: int, low):
    return jax.lax.reduce_window(h, low, jax.lax.max, (1, window, window, 1),
                                 (1, stride, stride, 1),
                                 ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def float_forward(cfg: dict, params: dict, x: jax.Array, precision: str = "highest"):
    """Float32 ``jax.numpy`` logits; ``precision`` as in
    :func:`bench.reference.float_forward`."""
    layers = convs(cfg)
    last = len(params["fcs"]) - 1

    def conv(i, h):
        c, p = layers[i], params["convs"][i]
        op = partial(jax.lax.conv_general_dilated, window_strides=(c["stride"],) * 2,
                     padding=[(c["pad"], c["pad"])] * 2,
                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = reference._contract(op, h, p["w"], precision) + p["b"]
        return jnp.maximum(h, 0.0) if c["relu"] else h

    def dense(j, h):
        p = params["fcs"][j]
        h = reference._contract(jnp.dot, h, p["w"], precision) + p["b"]
        return jnp.maximum(h, 0.0) if j < last else h

    return _walk(cfg, params, x.astype(jnp.float32), conv,
                 lambda h, *w: _maxpool(h, *w, -jnp.inf),
                 lambda a, b: jnp.maximum(a + b, 0.0),
                 lambda h: jnp.mean(h, axis=(1, 2)), dense)


def float_reference(cfg: dict, weights, precision: str):
    """Frames -> logits of :func:`float_forward`."""
    return partial(jax.jit(partial(float_forward, cfg, precision=precision)), weights)


def _fixed_add(a, b, act):
    """The residual add on the grid: the sum of the raws, saturated to the
    grid's range, then ReLU."""
    return jnp.maximum(jnp.clip(a + b, *reference._raw_bounds(*act)), 0)


def fixed_forward(cfg: dict, qlayers: list[dict], x: jax.Array,
                  act: tuple[int, int]) -> jax.Array:
    """Logits (float32) of the fixed-point network on ``act`` = (m, f), in
    int32 arithmetic: each conv and FC as the layer table's
    (:func:`bench.reference.fixed_forward`: int32 accumulation, the bias
    shifted onto it, ReLU where the layer has one, a round-half-up shift
    back onto the grid, saturation); the max pool on the raws, padded with
    the grid's least raw; the residual add :func:`_fixed_add`; the average
    pool of n = H·W raws summing to s is ``(s + n // 2) // n`` (the raws are
    post-ReLU, so this rounds half up); the classifier's accumulator read
    out exactly."""
    layers = convs(cfg)
    nc = len(layers)
    fcs = qlayers[nc:]

    def conv(i, h):
        c, q = layers[i], qlayers[i]
        acc = reference._conv_i32(h, q["w"], c["stride"], c["pad"]) + (q["b"] << q["frac"])
        return reference._shift_back(jnp.maximum(acc, 0) if c["relu"] else acc, q["frac"], act)

    def avgpool(h):
        n = h.shape[1] * h.shape[2]
        return (jnp.sum(h, axis=(1, 2)) + n // 2) // n

    def dense(j, h):
        q = fcs[j]
        acc = jnp.dot(h, q["w"], preferred_element_type=jnp.int32) + (q["b"] << q["frac"])
        if j < len(fcs) - 1:
            return reference._shift_back(jnp.maximum(acc, 0), q["frac"], act)
        return acc.astype(jnp.float32) * 2.0 ** -(act[1] + q["frac"])

    low = jnp.int32(reference._raw_bounds(*act)[0])
    return _walk(cfg, {"fcs": fcs}, reference._to_grid(x, *act, jnp.int32), conv,
                 lambda h, *w: _maxpool(h, *w, low),
                 lambda a, b: _fixed_add(a, b, act), avgpool, dense)


def fixed_reference(cfg: dict, weights, calib_frames, bits: int):
    """Frames -> logits of :func:`fixed_forward` on the grid that covers
    ``calib_frames``, with the weights on theirs."""
    act = reference.grid_frac(float(np.abs(calib_frames).max()), bits)
    q = reference.quantize_params(weights, act, bits)
    fracs = [layer.pop("frac") for layer in q]
    fn = jax.jit(lambda q, x: fixed_forward(
        cfg, [dict(layer, frac=f) for layer, f in zip(q, fracs)], x, act))
    return partial(fn, q)


def layer_counts(cfg: dict, batch: int) -> list[dict]:
    """One entry per conv and FC layer, as ``bench/roofline.py`` counts the
    layer table's, each conv with its kernel size ``k``: operations
    ``2·N·Ho·Wo·k²·Cin·Cout``; bytes of the input pixels its taps read (all
    of them, but for a strided 1×1, which reads one in stride²), the
    weights, the bias and the output, at ``storage_bytes``."""
    sb = cfg["storage_bytes"]
    out = []
    for i, c in enumerate(convs(cfg)):
        hw, k, ch, cout = c["hw"], c["k"], c["cin"], c["cout"]
        ho = (hw + 2 * c["pad"] - k) // c["stride"] + 1
        read = min(hw * hw, ho * ho * k * k)
        elems = batch * read * ch + k * k * ch * cout + cout + batch * ho * ho * cout
        out.append({"name": f"conv{i}", "kind": "conv", "k": k,
                    "ops": 2 * batch * ho * ho * k * k * ch * cout, "bytes": elems * sb})
    fan = cfg["stages"][-1][2]
    for j, width in enumerate((*cfg["fcs"], cfg["n_classes"])):
        elems = batch * fan + fan * width + width + batch * width
        out.append({"name": f"fc{j}", "kind": "fc", "ops": 2 * batch * width * fan,
                    "bytes": elems * sb})
        fan = width
    return out
