"""Plain references of a layer-table network (the form of
``bench/networks/layer_table.py``, which is what calls the functions that
read a configuration's rows), written from the rows in its file and
independent of the system under test.  :func:`seed_key`,
:func:`grid_frac` and the bfloat16 split serve any form.

* :func:`init_params`: the benchmark's weights, made from the seed in one
  jitted call (He-init convs and FCs, small random biases).
* :func:`float_forward`: float32 ``jax.numpy``: conv, bias, ReLU, max pool
  (window = stride), flatten in NHWC order, FC layers, ReLU on all but the
  classifier.  ``precision="highest"`` contracts at full float32;
  ``"high"`` is the next precision down, three bfloat16 passes
  (hi*hi + hi*lo + lo*hi of each operand's bfloat16 split, accumulated in
  float32), spelled out so that it means the same on every backend.
* :func:`fixed_forward`: the fixed-point semantics of Qm.n inference on a
  ``bits``-wide grid, in exact integer arithmetic:

  - the activation grid is the smallest Qm.n (m counts the sign) whose
    largest value covers max|x| of the calibration frames;
  - a weight tensor takes the smallest Qm.n covering its max|w|, its
    fraction capped so that ``2^(bits-1) * L1`` stays below ``2^30``
    (L1: the largest per-output sum of |w| over the contraction; one bit
    is kept for the bias add);
  - biases sit on the activation grid; values are rounded half to even
    and saturated when quantized;
  - each layer accumulates in int32, adds the bias shifted onto the
    accumulator's scale, applies ReLU, then rounds half up by an
    arithmetic shift back onto the activation grid and saturates;
  - pools take the max of the raw integers; the classifier's int32
    accumulator is read out exactly as float32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.01


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative whole number (wider than 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def shapes(cfg: dict) -> list[tuple]:
    """(kind, weight shape) per layer, forward order."""
    hw, ch = cfg["input_hw"], cfg["input_ch"]
    out = []
    for cout, k, stride, pad, pool in cfg["convs"]:
        out.append(("conv", (k, k, ch, cout)))
        hw = (hw + 2 * pad - k) // stride + 1
        hw, ch = (hw // pool if pool else hw), cout
    fan = hw * hw * ch
    for width in (*cfg["fcs"], cfg["n_classes"]):
        out.append(("fc", (fan, width)))
        fan = width
    return out


def init_params(cfg: dict, key: jax.Array) -> dict:
    """``{"convs": [{"w", "b"}], "fcs": [{"w", "b"}]}`` in float32."""
    layers = shapes(cfg)
    keys = jax.random.split(key, 2 * len(layers))
    tree = {"convs": [], "fcs": []}
    for i, (kind, shape) in enumerate(layers):
        fan_in = math.prod(shape[:-1])
        w = jax.random.normal(keys[2 * i], shape, jnp.float32) * (2.0 / fan_in) ** 0.5
        b = jax.random.normal(keys[2 * i + 1], shape[-1:], jnp.float32) * BIAS_STD
        tree[kind + "s"].append({"w": w, "b": b})
    return tree


def _pool(h, w: int, init):
    return jax.lax.reduce_window(h, init, jax.lax.max, (1, w, w, 1), (1, w, w, 1), "VALID")


def _split_bf16(a):
    """``a`` as hi + lo, each a bfloat16 rounded to nearest even.  hi is
    rounded on the float32's bit pattern, which no compiler folds away as
    it may fold a float32 -> bfloat16 -> float32 round trip."""
    u = jax.lax.bitcast_convert_type(a, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(u, jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _contract(op, a, b, precision: str):
    """``op(a, b)`` at ``precision`` ("highest" or "high")."""
    if precision == "highest":
        return op(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split_bf16(a), _split_bf16(b)
    f32 = partial(op, preferred_element_type=jnp.float32)
    return f32(ah, bh) + f32(ah, bl) + f32(al, bh)


def float_forward(cfg: dict, params: dict, x: jax.Array,
                  precision: str = "highest") -> jax.Array:
    h = x.astype(jnp.float32)
    for p, (cout, k, stride, pad, pool) in zip(params["convs"], cfg["convs"]):
        conv = partial(jax.lax.conv_general_dilated, window_strides=(stride, stride),
                       padding=[(pad, pad), (pad, pad)],
                       dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jnp.maximum(_contract(conv, h, p["w"], precision) + p["b"], 0.0)
        if pool:
            h = _pool(h, pool, -jnp.inf)
    h = h.reshape(h.shape[0], -1)
    last = len(params["fcs"]) - 1
    for i, p in enumerate(params["fcs"]):
        h = _contract(jnp.dot, h, p["w"], precision) + p["b"]
        if i < last:
            h = jnp.maximum(h, 0.0)
    return h


# -- fixed point ------------------------------------------------------------


def grid_frac(maxabs: float, bits: int, max_frac: int | None = None) -> tuple[int, int]:
    """(int_bits, frac_bits) of the smallest grid covering ``maxabs``."""
    for m in range(1, bits + 1):
        f = bits - m
        if max_frac is not None:
            f = max(0, min(f, max_frac))
        if maxabs <= 2.0 ** (m - 1) - 2.0 ** (-f):
            return m, f
    return bits, 0


def _raw_bounds(m: int, f: int) -> tuple[int, int]:
    return -(1 << (m - 1 + f)), (1 << (m - 1 + f)) - 1


def _to_grid(v, m: int, f: int, dtype):
    lo, hi = _raw_bounds(m, f)
    return jnp.clip(jnp.round(v.astype(jnp.float32) * float(1 << f)), lo, hi).astype(dtype)


def quantize_params(params: dict, act: tuple[int, int], bits: int) -> list[dict]:
    """Per layer: int32 raw weights and bias and the weight fraction."""
    dtype = jnp.int8 if bits == 8 else jnp.int16
    out = []
    for p in (*params["convs"], *params["fcs"]):
        w = p["w"]
        l1 = float(jnp.max(jnp.sum(jnp.abs(w), axis=tuple(range(w.ndim - 1)))))
        cap = math.floor(31 - (bits - 1) - 1 - math.log2(l1) - 1e-9) if l1 > 0 else None
        m, f = grid_frac(float(jnp.max(jnp.abs(w))), bits, cap)
        out.append({
            "w": _to_grid(w, m, f, dtype).astype(jnp.int32),
            "b": _to_grid(p["b"], *act, dtype).astype(jnp.int32),
            "frac": f,
        })
    return out


def _shift_back(acc, shift: int, act: tuple[int, int]):
    if shift > 0:
        acc = (acc + jnp.int32(1 << (shift - 1))) >> shift
    elif shift < 0:
        acc = acc << (-shift)
    return jnp.clip(acc, *_raw_bounds(*act))


def _conv_i32(x, w, stride: int, pad: int):
    """Exact int32 conv by the tap loop."""
    x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    k = w.shape[0]
    ho = (x.shape[1] - k) // stride + 1
    wo = (x.shape[2] - k) // stride + 1
    acc = jnp.zeros((x.shape[0], ho, wo, w.shape[-1]), jnp.int32)
    for i in range(k):
        for j in range(k):
            tap = x[:, i:i + stride * (ho - 1) + 1:stride, j:j + stride * (wo - 1) + 1:stride]
            acc = acc + jnp.einsum("nhwc,cd->nhwd", tap, w[i, j],
                                   preferred_element_type=jnp.int32)
    return acc


def fixed_forward(cfg: dict, qlayers: list[dict], x: jax.Array,
                  act: tuple[int, int]) -> jax.Array:
    """Logits (float32) of the fixed-point network on ``act`` = (m, f)."""
    fa = act[1]
    dtype = jnp.int32
    h = _to_grid(x, *act, dtype)
    nc = len(cfg["convs"])
    for q, (cout, k, stride, pad, pool) in zip(qlayers[:nc], cfg["convs"]):
        acc = _conv_i32(h, q["w"], stride, pad) + (q["b"] << q["frac"])
        h = _shift_back(jnp.maximum(acc, 0), q["frac"], act)
        if pool:
            h = _pool(h, pool, jnp.iinfo(jnp.int32).min)
    h = h.reshape(h.shape[0], -1)
    fcs = qlayers[nc:]
    for i, q in enumerate(fcs):
        acc = jnp.dot(h, q["w"], preferred_element_type=jnp.int32) + (q["b"] << q["frac"])
        if i < len(fcs) - 1:
            h = _shift_back(jnp.maximum(acc, 0), q["frac"], act)
        else:
            return acc.astype(jnp.float32) * 2.0 ** -(fa + q["frac"])
    raise ValueError("a network needs at least one FC layer")
