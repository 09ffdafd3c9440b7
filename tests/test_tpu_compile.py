"""Compile rehearsal for the TPU v5e: the main path's kernels at real widths
(VGG16's and ResNet-50's), compiled for a described (not attached)
``v5e:2x2`` chip.

Interpret-mode tests cannot see what the chip's compiler refuses — a block
not aligned to the (8, 128) tiling, a view of a ref Mosaic cannot slice, a
working set over the VMEM limit, a matmul dtype the MXU lacks.  Each case
lowers the kernel for the TPU (``kernels/common.py`` picks the compiled
branch because the lowering platform is the TPU) with the plan and VMEM
limit the DSE chose, and asserts the compiled program holds the Mosaic
kernel.  Nothing runs: a compile is not a chip run.

The topology is described in a module-scoped fixture — never at import —
so every pytest worker collects the same tests and only the one that runs
this file loads the TPU compiler; it skips where no topology can be
described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.template import default_template
from repro.kernels import ops
from repro.models import cnn as C


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    return compiled


def _vgg16_layer(backend, hw, i):
    """(template, conv plan, input shape, weight shape) of VGG16 conv ``i``
    at ``hw``×``hw``, batch 1, as plan_cnn plans it."""
    tpl = default_template(backend)
    plan = C.plan_cnn(tpl, C.VGG16, (1, hw, hw, 3))
    h, ch = hw, 3
    for cout, k, stride, pad, pool in C.VGG16.convs[:i]:
        h = (h + 2 * pad - k) // stride + 1
        h = h // pool if pool else h
        ch = cout
    cout, k = C.VGG16.convs[i][:2]
    return tpl, plan.convs[i], (1, h, h, ch), (k, k, ch, cout)


def _resnet50_layer(backend, i):
    """The same for ResNet-50 conv ``i`` at batch 32."""
    tpl = default_template(backend)
    plan = C.plan_cnn(tpl, C.RESNET50, (32, 224, 224, 3))
    h, w, cin, cout, k = C.conv_shapes(C.RESNET50)[0][i][:5]
    return tpl, plan.convs[i], (32, h, w, cin), (k, k, cin, cout)


def _conv_fn(tpl, cp):
    fn = ops.conv2d if tpl.config.backend == "pallas" else ops.conv2d_q16
    return lambda x, w, b: fn(
        x, w, bias=b, stride=cp.stride, padding=cp.pad, tau=cp.tau, relu=True,
        route=cp.route, block=cp.block, tile_rows=cp.tile_rows,
        tile_cols=cp.tile_cols, halo_mode=cp.halo_mode,
        vmem_limit_bytes=tpl.config.hw.vmem_bytes,
    )


@pytest.mark.parametrize("backend,layer", [
    ("pallas", 0), ("pallas", 1), ("q16", 0), ("q16", 1), ("q16", 8),
])
def test_vgg16_224_conv_compiles_as_planned(one_chip, backend, layer):
    """VGG16@224 conv0 (Cin=3: one channel in 128 lanes), conv1 (the
    largest activation) and a Cin=512 fixed-point layer, as planned."""
    tpl, cp, xs, ws = _vgg16_layer(backend, 224, layer)
    assert cp.route == "direct" and cp.vmem_bytes <= tpl.config.hw.vmem_bytes
    dt = jnp.float32 if backend == "pallas" else jnp.int16
    _compile(_conv_fn(tpl, cp), one_chip, (xs, dt), (ws, dt), ((ws[-1],), dt))


@pytest.mark.parametrize("backend", ["pallas", "q16"])
@pytest.mark.parametrize("layer,strided", [(0, True), (12, True), (14, True), (3, False)])
def test_resnet50_conv_compiles_as_planned(one_chip, backend, layer, strided):
    """ResNet-50 at batch 32: the 7x7/s2 stem (Cin=3), stage 2's 3x3/s2 and
    its 1x1/s2 projection, and a 1x1 at 56x56 to Cout=256.  Mosaic loads no
    strided window of 16-bit data (nor of 32-bit data wider than 128
    lanes), so the strided ones run over their space-to-depth input."""
    tpl, cp, xs, ws = _resnet50_layer(backend, layer)
    assert (cp.stride > 1) == strided == cp.space_to_depth
    assert cp.route == "direct" and cp.vmem_bytes <= tpl.config.hw.vmem_bytes
    dt = jnp.float32 if backend == "pallas" else jnp.int16
    _compile(_conv_fn(tpl, cp), one_chip, (xs, dt), (ws, dt), ((ws[-1],), dt))


@pytest.mark.parametrize("backend", ["pallas", "q16"])
def test_vgg16_512_dma_halo_conv_compiles(one_chip, backend):
    """The manual-DMA halo regime: VGG16@512² conv1 plans (𝒯, ℭ) windows."""
    tpl, cp, xs, ws = _vgg16_layer(backend, 512, 1)
    assert cp.halo_mode == "dma" and cp.spatial_tiles * cp.col_tiles > 1
    dt = jnp.float32 if backend == "pallas" else jnp.int16
    _compile(_conv_fn(tpl, cp), one_chip, (xs, dt), (ws, dt), ((ws[-1],), dt))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8, jnp.int16])
def test_vgg16_fc_gemm_compiles(one_chip, dtype):
    """VGG16's FC GEMMs (fc0: k=25088 -> 4096) at batch 8 on their skinny-M
    blocks: matmul_fp in f32, matmul_q16 with int8 operands (one MXU pass
    per tile) and int16 (four int8 digits)."""
    tpl = default_template("pallas" if dtype == jnp.float32 else "q16")
    fcs = C.plan_cnn(tpl, C.VGG16, (8, 224, 224, 3)).fcs
    assert (fcs[0].m, fcs[0].n, fcs[0].k) == (8, 4096, 25088)
    vmem = tpl.config.hw.vmem_bytes
    for gp in fcs:
        assert gp.block.bm == 8 and gp.k % gp.block.bk == 0
        if dtype == jnp.float32:
            fn = lambda x, w, b, gp=gp: ops.matmul_fp(  # noqa: E731
                x, w, bias=b, relu=True, block=gp.block, vmem_limit_bytes=vmem)
        else:
            fn = lambda x, w, b, gp=gp: ops.matmul_q16(  # noqa: E731
                x, w, bias=b, relu=True, block=gp.block, vmem_limit_bytes=vmem)
        _compile(fn, one_chip, ((gp.m, gp.k), dtype), ((gp.k, gp.n), dtype),
                 ((gp.n,), dtype))


@pytest.mark.parametrize("backend", ["pallas", "q16"])
def test_every_kernel_of_the_forward_names_its_layer(one_chip, backend):
    """The whole forward of the tiny VGG (bench/tests/bench_tiny.py): each
    ``tpu_custom_call`` carries its layer scope (``conv{i}``, ``fc{i}``) in
    its op_name, which is how a device trace splits by layer."""
    import re

    import numpy as np

    from test_layer_scopes import LAYER, _params, tiny_spec

    spec = tiny_spec()
    x = np.random.default_rng(0).standard_normal((2, 32, 32, 3), dtype=np.float32)
    tpl, params, policy = _params(backend, spec, x)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), (params, x))
    hlo = jax.jit(lambda p, x: C.cnn_forward(tpl, spec, p, x, policy=policy)).lower(
        *shapes).compile().as_text()
    layers = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            layers.append([c for c in op_name.split("/") if LAYER.match(c)][-1])
    assert sorted(layers) == ["conv0", "conv1", "fc0", "fc1"]
