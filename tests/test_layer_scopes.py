"""Every layer of ``cnn_forward`` is named in the compiled program.

The forward runs each layer under ``repro.runtime.spans.layer``, whose
``jax.named_scope`` puts the layer's name (``conv{i}``, ``pool{i}``,
``fc{i}``, ``quantize``, ``gather``) in the ``op_name`` of every HLO
instruction traced inside; ``bench/layers.py`` splits a device trace by
it.  On the CPU each Pallas kernel is interpreted, so its instructions are
those whose ``op_name`` passes through the kernel's name (``conv_untiled``,
``matmul_fp``, ...); each must lie in a layer scope, and the scopes found
are those of the network.  (The compile for a described TPU, whose kernels
are ``tpu_custom_call``s, is checked in ``test_tpu_compile.py``.)
"""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.quantization import NumericsPolicy
from repro.core.template import default_template
from repro.models import cnn as C

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench" / "tests"))
from bench_tiny import TINY  # noqa: E402

LAYER = re.compile(r"^(quantize|conv\d+|pool\d+|gather|fc\d+)$")
KERNEL = re.compile(r"^(conv|matmul)_[a-z0-9_]+$")
COLLECTIVE = re.compile(r"\s(collective-permute|all-gather|all-reduce)[a-z-]*\(")


def tiny_spec():
    return C.CNNSpec("tiny", TINY["input_hw"], TINY["input_ch"], TINY["n_classes"],
                     convs=tuple(tuple(c) for c in TINY["convs"]), fcs=tuple(TINY["fcs"]))


def scoped_instructions(hlo: str):
    """(kernel layers, kernels without a layer, collective layers,
    collectives without a layer) of a compiled module's text, whose
    function is a lambda.  Only full op_name paths count
    (``jit(<lambda>)/...``): a reducer's region body keeps a path relative
    to its caller."""
    kernels, bare_k, colls, bare_c = set(), 0, set(), 0
    for line in hlo.splitlines():
        m = re.search(r'op_name="(jit\(<lambda>\)/[^"]*)"', line)
        comps = m.group(1).split("/") if m else []
        layer = [c for c in comps if LAYER.match(c)]
        if any(KERNEL.match(c) for c in comps):
            kernels.update(layer[-1:])
            bare_k += not layer
        if COLLECTIVE.search(line):
            colls.update(layer[-1:])
            bare_c += not layer
    return kernels, bare_k, colls, bare_c


def _params(backend, spec, x):
    tpl = default_template(backend)
    params = C.init_cnn(jax.random.PRNGKey(0), spec)
    if backend != "q16":
        return tpl, params, None
    policy = C.calibrate_cnn_policy(tpl, spec, params, x, base=NumericsPolicy("q16"))
    return tpl, C.quantize_cnn_params(tpl, spec, params, policy), policy


@pytest.mark.parametrize("backend", ["pallas", "q16"])
def test_every_kernel_instruction_lies_in_a_layer_scope(backend):
    spec = tiny_spec()
    x = np.random.default_rng(0).standard_normal((2, 32, 32, 3), dtype=np.float32)
    tpl, params, policy = _params(backend, spec, x)
    hlo = jax.jit(lambda p, x: C.cnn_forward(tpl, spec, p, x, policy=policy)).lower(
        params, x).compile().as_text()
    kernels, bare, _, _ = scoped_instructions(hlo)
    assert bare == 0
    assert kernels == {"conv0", "conv1", "fc0", "fc1"}
    found = {c for op in re.findall(r'op_name="([^"]*)"', hlo)
             for c in op.split("/") if LAYER.match(c)}
    assert {"pool0", "pool1"} <= found
    if backend == "q16":
        assert "quantize" in found


_SLABS = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    sys.path.insert(0, "tests")
    from test_layer_scopes import _params, tiny_spec
    from repro.launch.mesh import make_mesh
    from repro.models import cnn as C
    from repro.parallel import sharding as sh

    out_dir = os.environ["LAYER_SCOPES_OUT"]
    spec = tiny_spec()
    x = np.random.default_rng(0).standard_normal((1, 32, 32, 3), dtype=np.float32)
    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    for backend in ("pallas", "q16"):
        tpl, params, policy = _params(backend, spec, x)
        rep = NamedSharding(mesh, P())
        params = jax.device_put(params, rep)
        with sh.use_mesh(mesh, sh.SERVE_RULES):
            plan = C.plan_cnn(tpl, spec, x.shape, mesh=mesh, spatial="data")
            assert plan.spatial == 2
            fwd = jax.jit(lambda p, a: C.cnn_forward(tpl, spec, p, a, policy=policy,
                                                     plan=plan))
            hlo = fwd.lower(params, jax.device_put(x, rep)).compile().as_text()
        with open(os.path.join(out_dir, backend + ".hlo"), "w") as f:
            f.write(hlo)
    """
)


def test_two_h_slabs_keep_every_kernel_and_collective_in_a_layer(tmp_path):
    """S=2 H slabs on two virtual host devices: the halo exchanges and the
    slab gather are collectives, each inside its conv, pool or gather
    scope; the replicated FC head keeps its fc scopes under shard_map."""
    env = dict(os.environ, PYTHONPATH="src", LAYER_SCOPES_OUT=str(tmp_path))
    env.pop("REPRO_PLAN_STORE", None)
    out = subprocess.run([sys.executable, "-c", _SLABS], capture_output=True, text=True,
                         env=env, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    for backend in ("pallas", "q16"):
        kernels, bare_k, colls, bare_c = scoped_instructions(
            (tmp_path / f"{backend}.hlo").read_text())
        assert bare_k == 0 and bare_c == 0, backend
        assert kernels == {"conv0", "conv1", "fc0", "fc1"}, backend
        assert {"conv0", "conv1"} <= colls, (backend, colls)
