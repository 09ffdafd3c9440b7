"""The layer-table form (``"form": "layer_table"``): a chain of convs, one
row ``[out_ch, k, stride, pad, pool]`` each, ReLU after every conv and a
max pool whose window equals its stride where ``pool`` is set; a flatten
in NHWC order; FC layers of ``fcs`` widths and the ``n_classes``
classifier, ReLU on all but the classifier.  VGG16 takes this form.

It binds the configuration's rows to the program's CNN entry points
(``models/cnn.py``), to the plain references of ``bench/reference.py`` and
to the counts of ``bench/roofline.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from bench import reference, roofline
from bench.forms import Program

#: ``models/cnn.py:cnn_forward``'s layer scopes
layer_pattern = r"conv\d+|pool\d+|fc\d+"
#: a pool is XLA's reduce-window, not a kernel
xla_layer_pattern = r"pool\d+"

init_params = reference.init_params
layer_counts = roofline.layer_counts


def program(cfg: dict) -> Program:
    """The program's CNN entry points on the configuration's spec, each
    looked up when it is called."""
    from repro.models import cnn as C

    spec = C.CNNSpec(cfg["network"], cfg["input_hw"], cfg["input_ch"], cfg["n_classes"],
                     convs=tuple(tuple(c) for c in cfg["convs"]), fcs=tuple(cfg["fcs"]))
    return Program(
        calibrate=lambda tpl, weights, x, base: C.calibrate_cnn_policy(
            tpl, spec, weights, x, base=base),
        quantize=lambda tpl, weights, policy: C.quantize_cnn_params(tpl, spec, weights, policy),
        plan=lambda tpl, shape, **placement: C.plan_cnn(tpl, spec, shape, **placement),
        forward=lambda tpl, params, x, policy, plan: C.cnn_forward(
            tpl, spec, params, x, policy=policy, plan=plan),
        layer_names=lambda: C.cnn_layer_names(spec),
    )


def float_reference(cfg: dict, weights, precision: str):
    """Frames -> logits of :func:`bench.reference.float_forward`."""
    return partial(jax.jit(partial(reference.float_forward, cfg, precision=precision)), weights)


def fixed_reference(cfg: dict, weights, calib_frames, bits: int):
    """Frames -> logits of :func:`bench.reference.fixed_forward` on the
    grid that covers ``calib_frames``, with the weights on theirs."""
    act = reference.grid_frac(float(np.abs(calib_frames).max()), bits)
    q = reference.quantize_params(weights, act, bits)
    fracs = [layer.pop("frac") for layer in q]
    fn = jax.jit(lambda q, x: reference.fixed_forward(
        cfg, [dict(layer, frac=f) for layer, f in zip(q, fracs)], x, act))
    return partial(fn, q)
