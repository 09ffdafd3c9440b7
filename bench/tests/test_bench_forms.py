"""A configuration's network form (``bench/networks/<form>.py``) is found by
name: a form added as files alone runs a cell end to end, a missing one
is an error that names it, the form's reference is what ``correct`` is
judged by, and the layer-table form counts as ``bench/roofline.py`` does."""
import json
import re

import jax
import pytest
from bench_tiny import REPO, config, make_root, traffic

from bench import forms, roofline
from bench import run as R

SEED = 2**32 + 41

#: appended to a copy of the layer-table form: each name a form exports
#: records that it was called
RECORDER = '''

CALLED = []


def _recorded(name, fn):
    def call(*args, **kwargs):
        CALLED.append(name)
        return fn(*args, **kwargs)
    return call


for _name in ("init_params", "program", "float_reference", "fixed_reference", "layer_counts"):
    globals()[_name] = _recorded(_name, globals()[_name])
'''

#: the layer-table form with a reference that leaves out the ReLU of the
#: last hidden layer
NO_LAST_RELU = '''"""The layer table, its reference without the last hidden ReLU."""
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import forms, reference

_table = forms.find_form({"form": "layer_table"}, Path(__file__).parent)
init_params, program, layer_counts = _table.init_params, _table.program, _table.layer_counts
layer_pattern, xla_layer_pattern = _table.layer_pattern, _table.xla_layer_pattern


def float_reference(cfg, weights, precision):
    def forward(w, x):
        # float_forward leaves out the ReLU of its last FC: stop it there
        h = reference.float_forward(cfg, dict(w, fcs=w["fcs"][:-1]), x, precision)
        last = w["fcs"][-1]
        return reference._contract(jnp.dot, h, last["w"], precision) + last["b"]
    return partial(jax.jit(forward), weights)


def fixed_reference(cfg, weights, calib_frames, bits):
    act = reference.grid_frac(float(np.abs(calib_frames).max()), bits)
    q = reference.quantize_params(weights, act, bits)
    fracs = [layer.pop("frac") for layer in q]
    nc = len(cfg["convs"])

    def forward(q, x):
        layers = [dict(layer, frac=f) for layer, f in zip(q, fracs)]
        h = reference._to_grid(x, *act, jnp.int32)
        for p, (cout, k, stride, pad, pool) in zip(layers[:nc], cfg["convs"]):
            acc = reference._conv_i32(h, p["w"], stride, pad) + (p["b"] << p["frac"])
            h = reference._shift_back(jnp.maximum(acc, 0), p["frac"], act)
            if pool:
                h = reference._pool(h, pool, jnp.iinfo(jnp.int32).min)
        h = h.reshape(h.shape[0], -1)
        *hidden, last = layers[nc:]
        for i, p in enumerate(hidden):
            acc = jnp.dot(h, p["w"], preferred_element_type=jnp.int32) + (p["b"] << p["frac"])
            h = reference._shift_back(acc if i == len(hidden) - 1 else jnp.maximum(acc, 0),
                                      p["frac"], act)
        acc = jnp.dot(h, last["w"], preferred_element_type=jnp.int32) + (last["b"] << last["frac"])
        return acc.astype(jnp.float32) * 2.0 ** -(act[1] + last["frac"])

    return partial(jax.jit(forward), q)
'''


def _root(tmp_path, numerics, form, text):
    cfg = dict(config(f"tiny-{form}-{numerics}", numerics), form=form)
    name = f"tiny-{form}-{numerics}.t1"
    root = make_root(tmp_path, {name: (cfg, "t1", traffic(1), 1)},
                     extra_files={f"bench/networks/{form}.py": text})
    return R.load_cell(name, root)


def _run(cell):
    return R.run_cell(cell, SEED, 0.3, False, jax.devices("cpu")[:1], log=lambda _: None)


@pytest.mark.parametrize("numerics,kind", [("q16", "fixed"), ("f32", "float")])
def test_a_form_added_as_files_runs_a_cell_end_to_end(tmp_path, numerics, kind):
    text = (REPO / "bench" / "networks" / "layer_table.py").read_text() + RECORDER
    cell = _root(tmp_path, numerics, "recorded_table", text)
    assert cell.form.__file__.startswith(str(tmp_path))
    res = _run(cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(cell.form.CALLED) == {"init_params", "program", f"{kind}_reference"}


@pytest.mark.parametrize("numerics", ["q16", "f32"])
def test_a_reference_without_the_last_relu_is_not_correct(tmp_path, numerics):
    cell = _root(tmp_path, numerics, "no_last_relu", NO_LAST_RELU)
    res = _run(cell)
    name = cell.cfg["check"]["name"]
    assert not res["correct"] and res["checks"][name]["value"] > res["checks"][name]["limit"]


@pytest.mark.parametrize("form,error,match", [
    (None, KeyError, '"form"'),
    ("no_such_form", FileNotFoundError, r"bench/networks/no_such_form\.py"),
])
def test_a_configuration_without_its_form_file_is_an_error(tmp_path, form, error, match):
    cfg = config("tiny-formless", "q16")
    cfg.pop("form")
    if form is not None:
        cfg["form"] = form
    root = make_root(tmp_path, {"tiny-formless.t1": (cfg, "t1", traffic(1), 1)})
    with pytest.raises(error, match=match):
        R.load_cell("tiny-formless.t1", root)


@pytest.mark.parametrize("numerics", ["q16", "f32"])
@pytest.mark.parametrize("batch", [1, 8])
def test_the_layer_table_counts_as_the_roofline_does(numerics, batch):
    cfg = json.loads((REPO / "bench" / "configs" / f"vgg16-224-{numerics}.json").read_text())
    counts = forms.find_form(cfg).layer_counts(cfg, batch)
    assert counts == roofline.layer_counts(cfg, batch)
    assert [c["name"] for c in counts] == [f"conv{i}" for i in range(13)] + ["fc0", "fc1", "fc2"]


@pytest.mark.parametrize("path", ["run.py", "layers.py", "control.py"])
def test_the_harness_names_no_network_shape(path):
    """Rows, layer kinds and the program's CNN entry points are the forms'."""
    text = (REPO / "bench" / path).read_text()
    code = re.sub(r'"""(.|\n)*?"""', "", text)  # docstrings may give examples
    for word in ('"convs"', '"fcs"', "models import cnn", "cnn_", "float_forward",
                 "fixed_forward", "quantize_params", "roofline.layer_counts", "init_params(",
                 '"conv"', '"fc"'):
        assert word not in code, word
