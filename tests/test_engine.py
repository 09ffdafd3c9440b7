"""Execution-plan engine: direct-conv kernel (all strides), fused epilogues,
routing decisions, and plan-cache memoization (DESIGN.md §1-§4)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dse
from repro.core.engine import Engine, PlanCache, reset_plan_caches
from repro.core.quantization import Q2_14, quantize
from repro.core.template import TemplateConfig, default_template
from repro.core.tiling import TPU_V5E
from repro.models.cnn import CNN_ZOO, LENET, cnn_forward, init_cnn, plan_cnn

KEY = jax.random.PRNGKey(11)


def _rand(shape, scale=0.3, salt=0):
    return jax.random.normal(jax.random.fold_in(KEY, salt), shape) * scale


# ---------------------------------------------------------------------------
# direct conv kernel: stride x padding x backend sweeps vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("padding", [0, "SAME"])
def test_direct_conv_float_vs_ref(stride, padding):
    from repro.kernels import ref

    eng = Engine(TemplateConfig(backend="pallas"))
    x = _rand((2, 13, 13, 5), salt=1)
    w = _rand((3, 3, 5, 8), salt=2)
    b = _rand((8,), scale=0.1, salt=3)
    plan = eng.plan_conv(x.shape, w.shape, stride=stride, padding=padding)
    assert plan.route == "direct"
    out = eng.conv2d(x, w, stride=stride, padding=padding, bias=b, relu=True, plan=plan)
    pad = 1 if padding == "SAME" else 0
    want = ref.conv2d_fused_ref(x, w, b, stride=stride, padding=pad, relu=True)
    assert out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("padding", [0, "SAME"])
def test_direct_conv_q16_vs_ref(stride, padding):
    from repro.kernels import ops, ref

    x = _rand((1, 12, 12, 4), salt=4)
    w = _rand((3, 3, 4, 8), salt=5)
    b = _rand((8,), scale=0.1, salt=6)
    xq, wq, bq = quantize(x), quantize(w), quantize(b)
    pad = 1 if padding == "SAME" else 0
    out = ops.conv2d_q16(
        xq, wq, bias=bq, stride=stride, padding=pad, relu=True
    )
    want = ref.conv2d_q16_ref(xq, wq, bq, stride=stride, padding=pad, relu=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("route", ["direct", "im2col"])
def test_conv_odd_cout_tau_padding(route):
    """cout=10 with tau=8 forces the tau-padded output-channel path."""
    from repro.kernels import ops, ref

    x = _rand((1, 9, 9, 4), salt=7)
    w = _rand((3, 3, 4, 10), salt=8)
    out = ops.conv2d(x, w, stride=2, padding=1, tau=8, route=route)
    want = ref.conv2d_ref(x, w, stride=2, padding=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_conv_q16_odd_cout_tau_padding():
    from repro.kernels import ops, ref

    x = _rand((1, 9, 9, 4), salt=9)
    w = _rand((3, 3, 4, 10), salt=10)
    xq, wq = quantize(x), quantize(w)
    out = ops.conv2d_q16(xq, wq, stride=1, padding=1, tau=8)
    want = ref.conv2d_q16_ref(xq, wq, stride=1, padding=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------------------------------------
# fused GEMM epilogues
# ---------------------------------------------------------------------------


def test_matmul_fp_fused_epilogue():
    from repro.kernels import ops, ref

    x = _rand((33, 47), salt=11)
    w = _rand((47, 19), salt=12)
    b = _rand((19,), scale=0.1, salt=13)
    out = ops.matmul_fp(x, w, bias=b, relu=True, qout=Q2_14)
    want = ref.matmul_fused_ref(x, w, b, relu=True, qout=Q2_14)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6, rtol=1e-6)


def test_matmul_q16_fused_epilogue():
    from repro.kernels import ops, ref

    x = _rand((24, 40), salt=14)
    w = _rand((40, 16), salt=15)
    b = _rand((16,), scale=0.1, salt=16)
    xq, wq, bq = quantize(x), quantize(w), quantize(b)
    out = ops.matmul_q16(xq, wq, bias=bq, relu=True)
    want = ref.matmul_q16_fused_ref(xq, wq, bq, relu=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------------------------------------
# plan cache: one DSE search per shape
# ---------------------------------------------------------------------------


def _count_searches(monkeypatch):
    calls = []
    real = dse.default_block_for

    def counting(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(dse, "default_block_for", counting)
    return calls


def test_plan_cache_memoizes_and_counts(monkeypatch):
    calls = _count_searches(monkeypatch)
    cache = PlanCache()
    b1 = cache.block_for(256, 256, 256)
    b2 = cache.block_for(256, 256, 256)
    assert b1 == b2
    assert len(calls) == 1, "second lookup must not re-run the DSE grid search"
    assert cache.hits == 1 and cache.misses == 1 and len(cache) == 1
    cache.block_for(512, 256, 256)
    assert len(calls) == 2 and cache.misses == 2


def test_plan_conv_direct_selection_is_plan_cached(monkeypatch):
    """The (tau, tile_rows) conv DSE runs once per layer geometry."""
    calls = []
    real = dse.default_conv_tile_for

    def counting(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(dse, "default_conv_tile_for", counting)
    cache = PlanCache()
    eng = Engine(TemplateConfig(backend="pallas"), plan_cache=cache)
    p1 = eng.plan_conv((1, 32, 32, 8), (3, 3, 8, 16))
    p2 = eng.plan_conv((1, 32, 32, 8), (3, 3, 8, 16))
    assert p1 == p2 and p1.route == "direct"
    assert len(calls) == 1, "second plan_conv must not re-run the conv-tile DSE"
    assert cache.hits == 1 and cache.misses == 1 and len(cache) == 1


def test_plan_cache_lifecycle_counters_and_replan():
    """reset_plan_caches() leaves counters consistent, and a re-planned
    network produces identical NetworkPlan blocks (guards persisted-autotune)."""
    reset_plan_caches()
    tpl = default_template("pallas")
    pc = tpl.engine.plan_cache
    p1 = plan_cnn(tpl, LENET, (1, 32, 32, 1))
    entries, misses = len(pc), pc.misses
    assert entries > 0
    assert misses == entries, "every cached entry costs exactly one DSE search"
    assert pc.hits == 0, "LeNet has no repeated layer shapes"
    # memoized NetworkPlan: no new searches, no new hits (plan table, not cache)
    assert plan_cnn(tpl, LENET, (1, 32, 32, 1)) is p1
    assert (pc.misses, pc.hits, len(pc)) == (misses, 0, entries)
    reset_plan_caches()
    assert len(pc) == 0 and pc.hits == 0 and pc.misses == 0
    p2 = plan_cnn(tpl, LENET, (1, 32, 32, 1))
    assert p2 is not p1, "reset must drop the NetworkPlan memo"
    assert p2 == p1, "re-planning after reset must reproduce identical blocks"
    assert pc.misses == misses and len(pc) == entries
    reset_plan_caches()


def test_register_plan_store_is_emptied_on_reset():
    from repro.core.engine import register_plan_store

    store = {("some", "plan", "key"): object()}
    register_plan_store(store)
    reset_plan_caches()
    assert store == {}


def test_template_matmul_single_dse_search(monkeypatch):
    reset_plan_caches()
    calls = _count_searches(monkeypatch)
    tpl = default_template("pallas")
    x = _rand((32, 48), salt=17)
    w = _rand((48, 16), salt=18)
    o1 = tpl.matmul(x, w)
    o2 = tpl.matmul(x, w)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))
    assert len(calls) == 1
    assert tpl.engine.plan_cache.hits >= 1
    # a *different* template instance with the same config shares the plan
    tpl2 = default_template("pallas")
    tpl2.matmul(x, w)
    assert len(calls) == 1
    reset_plan_caches()


# ---------------------------------------------------------------------------
# routing: CNN zoo convs all take the direct kernel; VMEM overflow falls back
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "q16"])
@pytest.mark.parametrize("net", ["lenet", "alexnet", "vgg16"])
def test_cnn_zoo_routes_direct(backend, net):
    """Stride-1 *and* strided (AlexNet conv1, stride 4) convs route direct."""
    spec = CNN_ZOO[net]
    tpl = default_template(backend)
    plan = plan_cnn(tpl, spec, (1, spec.input_hw, spec.input_hw, spec.input_ch))
    assert [cp.route for cp in plan.convs] == ["direct"] * len(spec.convs)
    assert all(cp.vmem_bytes <= tpl.config.hw.vmem_bytes for cp in plan.convs)


def test_conv_vmem_overflow_falls_back_to_im2col():
    # 16 KiB: below even the manual-DMA regime's minimal working set for
    # this layer (ISSUE 8 halved the direct route's residency, so the old
    # 64 KiB budget now legitimately fits a direct config)
    hw = dataclasses.replace(TPU_V5E, vmem_bytes=16 * 1024)
    eng = Engine(TemplateConfig(backend="pallas", hw=hw))
    plan = eng.plan_conv((1, 64, 64, 32), (3, 3, 32, 64))
    assert plan.route == "im2col"
    assert plan.block is not None
    with pytest.raises(ValueError):
        eng.plan_conv((1, 64, 64, 32), (3, 3, 32, 64), route="direct")


# ---------------------------------------------------------------------------
# end-to-end: direct path produces the same logits as the im2col path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "q16"])
def test_cnn_direct_matches_im2col(backend):
    params = init_cnn(jax.random.PRNGKey(0), LENET, scale=0.3)
    x = _rand((2, 32, 32, 1), scale=0.5, salt=19)
    tpl = default_template(backend)
    p_direct = plan_cnn(tpl, LENET, x.shape)
    p_gemm = plan_cnn(tpl, LENET, x.shape, force_route="im2col")
    assert all(cp.route == "direct" for cp in p_direct.convs)
    assert all(cp.route == "im2col" for cp in p_gemm.convs)
    f1 = cnn_forward(tpl, LENET, params, x, plan=p_direct)
    f2 = cnn_forward(tpl, LENET, params, x, plan=p_gemm)
    # float: 1e-4; q16: both paths are bit-exact int32 accumulations, allow
    # one Q2.14 LSB of slack for the dequantized logits.
    tol = 1e-4 if backend == "pallas" else Q2_14.resolution * 1.001
    assert float(jnp.abs(f1 - f2).max()) <= tol
    # routing assertion on the executed forward, not just the plan
    assert tpl.engine.counters["conv_direct"] >= len(LENET.convs)


def test_cnn_pallas_matches_xla_logits():
    params = init_cnn(jax.random.PRNGKey(0), LENET, scale=0.3)
    x = _rand((2, 32, 32, 1), scale=0.5, salt=20)
    f_xla = cnn_forward(default_template("xla"), LENET, params, x)
    f_pal = cnn_forward(default_template("pallas"), LENET, params, x)
    np.testing.assert_allclose(
        np.asarray(f_pal), np.asarray(f_xla), atol=1e-4, rtol=1e-4
    )


def test_plan_cnn_is_memoized():
    tpl = default_template("pallas")
    p1 = plan_cnn(tpl, LENET, (2, 32, 32, 1))
    p2 = plan_cnn(tpl, LENET, (2, 32, 32, 1))
    assert p1 is p2
    reset_plan_caches()
    p3 = plan_cnn(tpl, LENET, (2, 32, 32, 1))
    assert p3 is not p1, "reset_plan_caches must also drop NetworkPlan memos"


def test_plan_cnn_non_square_input():
    """Plans must track H and W independently (and forward must still run)."""
    spec = dataclasses.replace(LENET, convs=((6, 5, 1, 0, 2),), fcs=(16,))
    tpl = default_template("pallas")
    plan = plan_cnn(tpl, spec, (1, 32, 40, 1))
    # conv: (32-5+1, 40-5+1) = (28, 36); pool 2 -> (14, 18)
    assert plan.convs[0].gemm[0] == 28 * 36
    assert plan.fcs[0].k == 14 * 18 * 6
    # init_cnn assumes square inputs, so build params by hand from the plan
    x = _rand((1, 32, 40, 1), scale=0.5, salt=21)
    params = {
        "convs": [{"w": _rand((5, 5, 1, 6), salt=24), "b": jnp.zeros((6,))}],
        "fcs": [
            {"w": _rand((plan.fcs[0].k, 16), salt=22), "b": jnp.zeros((16,))},
            {"w": _rand((16, spec.n_classes), salt=23), "b": jnp.zeros((spec.n_classes,))},
        ],
    }
    out = cnn_forward(tpl, spec, params, x, plan=plan)
    assert out.shape == (1, spec.n_classes)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# ad-hoc dispatch under an active mesh plans LOCAL shapes (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


class _StubMesh:
    """Duck-typed 2x2 mesh: planners read ``.shape``/``.axis_names`` only,
    and ``use_mesh`` enters it as a context manager — lets a single-device
    host exercise multi-way local-shape math."""

    shape = {"data": 2, "model": 2}
    axis_names = ("data", "model")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_adhoc_matmul_plans_local_shape_under_mesh():
    """Plan-less Engine.matmul inside use_mesh must plan the per-shard
    (m/data, n/model, k) shape — the one plan_gemm(mesh=...) warms and the
    sharded program executes — not the global one."""
    from repro.parallel.sharding import TRAIN_RULES, use_mesh

    eng = Engine(TemplateConfig(backend="pallas"),
                 plan_cache=PlanCache())
    x = jax.random.normal(KEY, (8, 16))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 32))
    with use_mesh(_StubMesh(), TRAIN_RULES):
        eng.matmul(x, w)
    planned = {k[:3] for k in eng.plan_cache._blocks}
    assert (4, 16, 16) in planned, planned  # local shard shape
    assert (8, 32, 16) not in planned, planned  # global shape never planned
    # outside a mesh context the global shape is planned as before
    eng.matmul(x, w)
    assert (8, 32, 16) in {k[:3] for k in eng.plan_cache._blocks}


def test_adhoc_conv2d_plans_local_shape_under_mesh():
    from repro.parallel.sharding import TRAIN_RULES, use_mesh

    eng = Engine(TemplateConfig(backend="pallas"),
                 plan_cache=PlanCache())
    x = jax.random.normal(KEY, (4, 8, 8, 4)) * 0.3
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (3, 3, 4, 8)) * 0.3
    with use_mesh(_StubMesh(), TRAIN_RULES):
        eng.conv2d(x, w, padding=1)
    # conv DSE keys: (hp, wp, cin, kh, kw, ho, wo, cout, stride, in_bytes,
    # spec) — the planned Cout is the model-sharded local 4, never 8
    couts = {k[7] for k in eng.plan_cache._conv_tiles}
    assert couts == {4}, couts
