"""Mixed int8/int16 precision acceptance suite (DESIGN.md §11).

The load-bearing assertions of the precision ladder:

* **Mixed-boundary epilogue** — the grid-resident GEMM with q8/q16 operands
  in any combination (and either output rung) is bit-identical to
  ``qtensor_matmul_ref``: an int8 layer feeds an int16 layer (and vice
  versa) through the shift-based write-back with zero float round-trips.
* **Mixed LeNet forward** — a whole forced-mixed LeNet forward (int8 and
  int16 layers interleaved) matches an independent im2col +
  ``qtensor_matmul_ref`` oracle bit-for-bit, through the exact wide
  read-out of the classifier.
* **int8 KV cache** — a group the DSE drops to the int8 rung stores int8
  raws in both ``init_cache`` and the prefill-built cache; other groups
  stay int16.
* **Half-bytes law** — the byte accounting helpers report exactly half the
  q16 activation/KV bytes for int8-assigned layers.
* **Warm pins** — a populated registry rebuilds the identical mixed policy
  with hits only: zero misses, zero forwards (REPRO_PLAN_ASSERT_WARM).
* **Composed budget** — the greedy revert loop enforces the accuracy budget
  on the *network*: when the composed plan misses it, int8 layers revert
  (lowest solo-flip agreement first) until it holds or none remain.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.configs import get_config, reduced
from repro.core import dse
from repro.core.engine import (
    PLAN_STORE_ENV,
    Engine,
    PlanRegistry,
    plan_cache_for,
    reset_plan_caches,
)
from repro.core.quantization import (
    NumericsPolicy,
    Q2_6,
    Q2_14,
    QFormat,
    QTensor,
    int8_rung,
    qtensor_matmul_ref,
    quantize,
)
from repro.core.template import TemplateConfig, default_template
from repro.core.tiling import TPU_V5E
from repro.kernels.ops import conv_gemm_weights, im2col
from repro.models import transformer as T
from repro.models.cnn import (
    LENET,
    _maxpool,
    calibrate_cnn_policy,
    calibrate_cnn_precision,
    cnn_forward,
    cnn_layer_names,
    init_cnn,
    quantize_cnn_params,
)

Q3_13 = QFormat(3, 13)
Q3_5 = QFormat(3, 5, 8)


# ---------------------------------------------------------------------------
# mixed-boundary epilogue: q8<->q16 GEMM bit-exact vs the oracle
# ---------------------------------------------------------------------------


@given(st.integers(min_value=0, max_value=2 ** 31 - 1),
       st.sampled_from(["q8xq16", "q16xq8", "q8xq8", "q16xq16"]),
       st.sampled_from([Q2_14, Q2_6]))
@settings(max_examples=40, deadline=None)
def test_engine_mixed_width_matmul_bitexact_vs_oracle(seed, widths, out_fmt):
    """Engine grid-resident GEMM with any q8/q16 operand combination and
    either output rung == qtensor_matmul_ref bit-for-bit, bias + relu
    fused — the mixed-boundary epilogue is the same shift write-back."""
    eng = Engine(TemplateConfig(backend="q16"))
    xf = Q2_6 if widths.startswith("q8") else Q2_14
    wf = Q3_5 if widths.endswith("q8") else Q3_13
    rng = np.random.default_rng(seed)
    xq = QTensor(jnp.asarray(
        rng.integers(xf.raw_min, xf.raw_max + 1, (4, 8)), xf.storage_dtype), xf)
    wq = QTensor(jnp.asarray(
        rng.integers(wf.raw_min, wf.raw_max + 1, (8, 3)), wf.storage_dtype), wf)
    bq = QTensor(jnp.asarray(
        rng.integers(xf.raw_min, xf.raw_max + 1, (3,)), xf.storage_dtype), xf)
    got = eng.matmul(xq, wq, bias=bq, relu=True, qout=out_fmt)
    want = qtensor_matmul_ref(xq, wq, out_fmt, bias=bq, relu=True)
    assert got.fmt == out_fmt and got.raw.dtype == out_fmt.storage_dtype
    np.testing.assert_array_equal(np.asarray(got.raw), np.asarray(want.raw))


# ---------------------------------------------------------------------------
# forced-mixed LeNet forward: bit-exact vs an independent oracle
# ---------------------------------------------------------------------------


def _oracle_lenet_forward(qp, policy, x):
    """Independent mixed LeNet oracle: im2col + qtensor_matmul_ref per
    layer, maxpool on raws, exact int32 read-out for the classifier."""
    names = cnn_layer_names(LENET)
    nc = len(LENET.convs)
    f0 = policy.fmt_for(names[0])
    h = QTensor(quantize(x, f0), f0)
    for i, ((cout, k, stride, pad, pool), p) in enumerate(
            zip(LENET.convs, qp["convs"])):
        xr = h.raw
        if pad:
            xr = jnp.pad(xr, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        cols, ho, wo = im2col(xr, k, k, stride)
        out = qtensor_matmul_ref(
            QTensor(cols, h.fmt),
            QTensor(conv_gemm_weights(p["w"].raw), p["w"].fmt),
            policy.fmt_for(names[i + 1]), bias=p["b"], relu=True,
        )
        h = QTensor(out.raw.reshape(x.shape[0], ho, wo, cout), out.fmt)
        if pool:
            h = _maxpool(h, pool)
    h = h.reshape(h.shape[0], -1)
    last = len(qp["fcs"]) - 1
    for i, p in enumerate(qp["fcs"]):
        if i < last:
            h = qtensor_matmul_ref(h, p["w"], policy.fmt_for(names[nc + i + 1]),
                                   bias=p["b"], relu=True)
        else:
            # wide read-out: int32 accumulator + shifted bias, exact descale
            acc = (np.asarray(h.raw, np.int64)
                   @ np.asarray(p["w"].raw, np.int64))
            acc_frac = h.fmt.frac_bits + p["w"].fmt.frac_bits
            bshift = acc_frac - p["b"].fmt.frac_bits
            acc = acc + (np.asarray(p["b"].raw, np.int64) << bshift)
            return (acc.astype(np.int32).astype(np.float32)
                    * np.float32(2.0 ** -acc_frac))


def test_mixed_lenet_forward_bitexact_vs_oracle():
    """A forced-mixed plan (int8 and int16 layers interleaved, so both
    int8->int16 and int16->int8 boundaries occur) runs the grid path
    bit-identically to the independent oracle, logits included."""
    tpl = default_template("q16")
    params = init_cnn(jax.random.PRNGKey(0), LENET, scale=0.4)
    mixed = NumericsPolicy("mixed", fmt=Q2_14, layer_fmts=(
        ("conv0", Q2_6), ("fc0", Q2_6), ("fc2", Q2_6),
    ))
    qp = quantize_cnn_params(tpl, LENET, params, mixed)
    assert qp["convs"][0]["w"].raw.dtype == jnp.int8  # int8 weight grid
    assert qp["convs"][1]["w"].raw.dtype == jnp.int16
    img = jax.random.uniform(jax.random.PRNGKey(3), (4, 32, 32, 1)) * 2 - 1
    got = cnn_forward(tpl, LENET, qp, img, policy=mixed)
    want = _oracle_lenet_forward(qp, mixed, img)
    np.testing.assert_array_equal(np.asarray(got), want)
    tpl.engine.drop_qparams(params, mixed)


# ---------------------------------------------------------------------------
# int8 KV cache + mixed transformer forward
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mixed_tf_setup():
    cfg = reduced(get_config("qwen2-0.5b"))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tpl = default_template("q16")
    cal = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, cfg.vocab)
    policy = T.calibrate_policy(tpl, cfg, params, cal)
    low = int8_rung(policy.fmt)
    assert low is not None
    mixed = dataclasses.replace(policy, name="mixed",
                                layer_fmts=(("g0", low),))
    qp = T.quantize_params(tpl, cfg, params, mixed)
    return cfg, params, tpl, mixed, qp


def test_init_cache_kv_dtype_follows_group_grid(mixed_tf_setup):
    cfg, params, tpl, mixed, qp = mixed_tf_setup
    cache = T.init_cache(cfg, 2, 16, policy=mixed)
    c0 = cache["blocks"][0]["attn"]
    assert c0["k"].dtype == jnp.int8 and c0["v"].dtype == jnp.int8
    for blk in cache["blocks"][1:]:
        assert blk["attn"]["k"].dtype == jnp.int16
    for tail in cache["tail"]:
        assert tail["attn"]["k"].dtype == jnp.int16
    # an explicit dtype still overrides uniformly
    cache_f = T.init_cache(cfg, 2, 16, dtype=jnp.float32, policy=mixed)
    assert cache_f["blocks"][0]["attn"]["k"].dtype == jnp.float32


def test_prefill_cache_carries_int8_group(mixed_tf_setup):
    cfg, params, tpl, mixed, qp = mixed_tf_setup
    _, cache = T.prefill(tpl, cfg, qp, jnp.zeros((1, 8), jnp.int32),
                         cache_len=16, policy=mixed)
    c0 = cache["blocks"][0]["attn"]
    assert c0["k"].dtype == jnp.int8 and c0["v"].dtype == jnp.int8
    for blk in cache["blocks"][1:]:
        assert blk["attn"]["k"].dtype == jnp.int16
    # ...and decode runs off the int8 cache, emitting finite float logits
    logits, _ = T.decode_step(tpl, cfg, qp, jnp.zeros((1, 1), jnp.int32),
                              jnp.int32(8), cache, policy=mixed)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_mixed_transformer_tracks_float(mixed_tf_setup):
    """The forced-int8 group costs bounded drift on the fixed seed set.
    A random-init net has near-tie logits, so this is a loose sanity bound;
    the CI-gated >=99% agreement runs on the trained network in
    benchmarks/precision_drift.py, where the DSE chooses the plan."""
    cfg, params, tpl, mixed, qp = mixed_tf_setup
    tpl_f = default_template()
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, cfg.vocab)
    lf, _ = T.forward(tpl_f, cfg, params, toks, mode="fwd")
    lq, _ = T.forward(tpl, cfg, qp, toks, mode="fwd", policy=mixed)
    assert float(jnp.abs(lf - lq).mean()) < 0.3  # int8 (2^-6) noise scale
    assert float((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean()) >= 0.5


# ---------------------------------------------------------------------------
# half-bytes law (the byte accounting the CI gate enforces)
# ---------------------------------------------------------------------------


def test_lenet_int8_layers_cost_exactly_half_bytes():
    from benchmarks.precision_drift import (
        lenet_activation_bytes,
        lenet_activation_bytes_mixed,
        lenet_activation_elements,
    )

    base = NumericsPolicy("q16", fmt=Q2_14)
    names = cnn_layer_names(LENET)
    all8 = dataclasses.replace(
        base, name="mixed", layer_fmts=tuple((n, Q2_6) for n in names))
    q16 = lenet_activation_bytes(LENET, act_bytes=2)
    assert lenet_activation_bytes_mixed(LENET, base) == q16
    assert lenet_activation_bytes_mixed(LENET, all8) * 2 == q16
    # per-layer: dropping one layer saves exactly its element count
    el = lenet_activation_elements(LENET)
    for n in names:
        one = dataclasses.replace(base, name="mixed", layer_fmts=((n, Q2_6),))
        assert q16 - lenet_activation_bytes_mixed(LENET, one) == el[n]


def test_transformer_int8_groups_cost_exactly_half_bytes():
    from benchmarks.precision_drift import (
        transformer_decode_bytes,
        transformer_decode_bytes_mixed,
    )

    cfg = reduced(get_config("qwen2-0.5b"))
    base = NumericsPolicy("q16", fmt=Q2_14)
    names = T.precision_group_names(cfg)
    all8 = dataclasses.replace(
        base, name="mixed", layer_fmts=tuple((n, Q2_6) for n in names))
    q16 = transformer_decode_bytes(cfg, 128, act_bytes=2, kv_bytes=2)
    q8 = transformer_decode_bytes(cfg, 128, act_bytes=1, kv_bytes=1)
    assert transformer_decode_bytes_mixed(cfg, 128, base) == q16
    assert transformer_decode_bytes_mixed(cfg, 128, all8) == q8
    assert q8 * 2 == q16
    one = dataclasses.replace(base, name="mixed", layer_fmts=(("g0", Q2_6),))
    assert q16 > transformer_decode_bytes_mixed(cfg, 128, one) > q8


# ---------------------------------------------------------------------------
# DSE: choose_precision, composed revert, warm pins
# ---------------------------------------------------------------------------


def test_choose_precision_assigns_cheapest_grid_meeting_budget():
    drift = {"a": 1.0, "b": 0.991, "c": 0.42}
    plan = dse.choose_precision(drift, 0.99, Q2_14, Q2_6)
    assert plan == {"a": Q2_6, "b": Q2_6, "c": Q2_14}
    for bad in (-0.1, 1.5):
        with pytest.raises(ValueError, match="budget"):
            dse.choose_precision(drift, bad, Q2_14, Q2_6)


@pytest.fixture
def lenet_dse_setup():
    reset_plan_caches()
    tpl = default_template("q16")
    params = init_cnn(jax.random.PRNGKey(0), LENET, scale=0.4)
    img = jax.random.uniform(jax.random.PRNGKey(2), (4, 32, 32, 1)) * 2 - 1
    policy = calibrate_cnn_policy(tpl, LENET, params, img)
    yield tpl, params, img, policy
    reset_plan_caches()


def test_composed_budget_reverts_int8_layers(lenet_dse_setup):
    """Solo-flip drifts compose: hand the DSE per-layer drift that claims
    every layer passes, against a reference the composed network can never
    match — every int8 choice must be reverted to the base grid (and the
    pins record the reverted plan)."""
    tpl, params, img, policy = lenet_dse_setup
    names = cnn_layer_names(LENET)
    fake_drift = {n: 1.0 for n in names}
    wrong_ref = (jnp.argmax(cnn_forward(tpl, LENET, params, img), -1) + 1) % 10
    mixed = calibrate_cnn_precision(
        tpl, LENET, params, img, budget=0.99, policy=policy,
        drift=fake_drift, ref=wrong_ref,
    )
    assert all(f == policy.fmt for _, f in mixed.layer_fmts), \
        "an unreachable network budget must revert every int8 layer"
    reg = tpl.engine.plan_cache
    assert reg.precision_plan(LENET.name, tpl.config.hw) == {
        n: policy.fmt for n in names
    }


def test_warm_pins_rebuild_identical_policy_zero_forwards(
        lenet_dse_setup, monkeypatch):
    """Cold sweep pins every layer (one miss each); a second calibration
    replays from the pins — identical policy, hits only, and zero forwards
    (cnn_forward is boobytrapped)."""
    tpl, params, img, policy = lenet_dse_setup
    reg = tpl.engine.plan_cache
    names = cnn_layer_names(LENET)
    cold = calibrate_cnn_precision(
        tpl, LENET, params, img, budget=0.0, policy=policy,
        drift={n: 1.0 for n in names},
    )
    low = int8_rung(policy.fmt)
    assert all(f == low for _, f in cold.layer_fmts)  # budget 0: all int8
    assert reg.misses >= len(names)

    def boom(*a, **kw):  # pragma: no cover - only fires on regression
        raise AssertionError("warm precision replay ran a forward")

    monkeypatch.setattr("repro.models.cnn.cnn_forward", boom)
    misses0, hits0 = reg.misses, reg.hits
    warm = calibrate_cnn_precision(tpl, LENET, params, img,
                                   budget=0.0, policy=policy)
    assert warm == cold
    assert reg.misses == misses0, "warm replay must not search"
    assert reg.hits == hits0 + len(names)


def test_transformer_warm_pins_zero_forwards(monkeypatch):
    reset_plan_caches()
    cfg = reduced(get_config("qwen2-0.5b"))
    tpl = default_template("q16")
    base = NumericsPolicy("q16", fmt=Q2_14)
    reg = plan_cache_for(TPU_V5E)
    names = T.precision_group_names(cfg)
    for n in names:
        reg.pin_precision(cfg.name, n, Q2_6 if n == "g0" else Q2_14,
                          drift=1.0, searched=False)

    def boom(*a, **kw):  # pragma: no cover - only fires on regression
        raise AssertionError("warm precision replay ran a forward")

    monkeypatch.setattr(T, "forward", boom)
    warm = T.calibrate_precision(tpl, cfg, params=None, tokens=None,
                                 policy=base)
    assert warm.name == "mixed"
    assert dict(warm.layer_fmts)["g0"] == Q2_6
    assert all(dict(warm.layer_fmts)[n] == Q2_14 for n in names if n != "g0")
    reset_plan_caches()


# ---------------------------------------------------------------------------
# serve --backend q8: cold DSE + warm restart with zero searches
# ---------------------------------------------------------------------------


def test_serve_q8_warm_restart_zero_searches(tmp_path, monkeypatch):
    from repro.launch import serve

    monkeypatch.delenv(PLAN_STORE_ENV, raising=False)
    reset_plan_caches()
    store = str(tmp_path / "q8_store.json")
    args = ["--backend", "q8", "--prompts", "1", "--prompt-len", "8",
            "--gen", "2", "--precision-budget", "0.5", "--plan-store", store]
    serve.main(args)  # cold: calibrates, sweeps, pins, saves
    with open(store) as f:
        doc = json.load(f)
    assert doc["version"] == 4 and doc["precision"], \
        "cold q8 serve must persist measured precision pins"
    assert all(e["source"] == "measured" for e in doc["precision"])

    reset_plan_caches()  # fresh process: warm-start from the store
    serve.main(args)
    pc = plan_cache_for(TPU_V5E)
    assert pc.misses == 0, \
        "warm q8 serve must re-serve pinned precision with zero DSE searches"
    assert pc.hits > 0
    reset_plan_caches()
