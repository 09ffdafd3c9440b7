"""The ``resnet`` form (``bench/networks/resnet.py``): a tiny residual cell runs
end to end and is ``correct`` against the form's fixed-point reference; a
reference that leaves out one residual add is not, nor is the int8-rung
control; and the form counts ResNet-50 as its Table 1 does."""
import json

import jax
import pytest
from bench_tiny import REPO, make_root, traffic
from tiny_resnet import resnet_config

from bench import control, forms
from bench import run as R

SEED = 2**32 + 173

#: the resnet form with a reference whose first identity or projection add
#: is left out: the block's branch goes on alone
NO_FIRST_ADD = '''"""The resnet form, its fixed reference without the first residual add."""
from pathlib import Path

from bench import forms

_resnet = forms.find_form({"form": "resnet"}, Path(__file__).parent)
init_params, program, layer_counts = _resnet.init_params, _resnet.program, _resnet.layer_counts
layer_pattern, xla_layer_pattern = _resnet.layer_pattern, _resnet.xla_layer_pattern
float_reference = _resnet.float_reference
_add = _resnet._fixed_add
_calls = []


def _skip_first(a, b, act):
    _calls.append(1)
    return _add(a, 0 * b, act) if len(_calls) == 1 else _add(a, b, act)


_resnet._fixed_add = _skip_first


def fixed_reference(cfg, weights, calib_frames, bits):
    _calls.clear()
    return _resnet.fixed_reference(cfg, weights, calib_frames, bits)
'''


def _cell(tmp_path, form, extra=()):
    cfg = dict(resnet_config(f"tinyres-{form}"), form=form)
    name = f"tinyres-{form}.t2"
    root = make_root(tmp_path, {name: (cfg, "t2", traffic(2), 1)}, extra_files=dict(extra))
    return R.load_cell(name, root)


def _run(cell):
    return R.run_cell(cell, SEED, 0.3, False, jax.devices("cpu")[:1], log=lambda _: None)


@pytest.mark.parametrize("form,text,correct", [
    ("resnet", None, True),
    ("resnet_no_first_add", NO_FIRST_ADD, False),
])
def test_a_tiny_resnet_cell_is_judged_by_the_forms_reference(tmp_path, form, text, correct):
    extra = {f"bench/networks/{form}.py": text} if text else {}
    cell = _cell(tmp_path, form, extra)
    res = _run(cell)
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["correct"] is correct
    value = res["checks"]["logits_differing"]["value"]
    assert value == 0 if correct else value > 0


def test_the_int8_rung_control_of_a_tiny_resnet_cell_is_not_correct(tmp_path):
    cell = _cell(tmp_path, "resnet")
    res = control.control_run(cell, SEED, 0.3, jax.devices("cpu")[:1], log=lambda _: None)
    assert not res["correct"] and res["checks"]["logits_differing"]["value"] > 0


def test_the_resnet_form_counts_resnet50_as_table_1_does():
    cfg = json.loads((REPO / "bench" / "configs" / "resnet50-224-q16.json").read_text())
    form = forms.find_form(cfg)
    counts = form.layer_counts(cfg, 1)
    convs = [c for c in counts if c["kind"] == "conv"]
    assert len(convs) == 53 and sum(c["k"] == 1 for c in convs) == 36
    assert [c["name"] for c in counts] == [f"conv{i}" for i in range(53)] + ["fc0"]
    assert sum(c["ops"] for c in counts) == 2 * 4_089_184_256  # 8.178 GOP an image
    assert form.layer_counts(cfg, 32)[5]["ops"] == 32 * convs[5]["ops"]
    shapes = jax.eval_shape(lambda k: form.init_params(cfg, k), jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 25_530_472
