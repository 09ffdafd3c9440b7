"""Fixed-point residency acceptance suite (DESIGN.md §8).

The load-bearing assertions of the QTensor layer:

* **The island law** — a traced q16 transformer step performs *zero* float
  round-trips between consecutive linear ops: the engine's quantize /
  dequantize counters equal exactly the designated-island counts
  (`transformer.q16_island_counts`: softmax/RoPE/activation islands + the
  head boundary), for both prefill and decode.
* **Quantize-once weights** — the qparam cache builds one tree per
  (params, policy) per engine; every later generate()/scheduler call is a
  cache hit (`qparam_builds == 1`).
* **Grid-resident CNN** — the whole LeNet forward costs one quantize (the
  input) and one dequantize (the classifier read-out); maxpool runs on the
  int16 raws.
* **int16 KV cache** — prefill/decode caches store int16 raws under the
  quantized policy, and the grid path stays bit-consistent with the
  mixed-format oracle.
* **Unsupported combos fail loudly** — q16 policy on a float backend, or on
  families whose mixers cannot run on the grid, raise ValueError.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.engine import Engine, validate_policy
from repro.core.quantization import (
    NumericsPolicy,
    Q2_14,
    QFormat,
    QTensor,
    qtensor_matmul_ref,
    quantize_qtensor,
)
from repro.core.template import TemplateConfig, default_template
from repro.models import transformer as T
from repro.models.cnn import (
    LENET,
    calibrate_cnn_policy,
    cnn_forward,
    init_cnn,
    quantize_cnn_params,
)


@pytest.fixture(scope="module")
def q16_setup():
    cfg = reduced(get_config("qwen2-0.5b"))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tpl = default_template("q16")
    cal = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, cfg.vocab)
    policy = T.calibrate_policy(tpl, cfg, params, cal)
    qp = T.quantize_params(tpl, cfg, params, policy)
    return cfg, params, tpl, policy, qp


def _reset_island_counters(eng):
    eng.counters["quantize_calls"] = 0
    eng.counters["dequantize_calls"] = 0


# ---------------------------------------------------------------------------
# the island law (acceptance criterion)
# ---------------------------------------------------------------------------


def test_decode_step_obeys_island_law(q16_setup):
    """One q16 decode step: counter ticks == designated float islands, no
    more — any extra tick is an un-designated float round-trip between
    consecutive linear ops."""
    cfg, params, tpl, policy, qp = q16_setup
    _, cache = T.prefill(tpl, cfg, qp, jnp.zeros((2, 8), jnp.int32),
                         cache_len=16, policy=policy)
    eng = tpl.engine
    _reset_island_counters(eng)
    tok = jnp.zeros((2, 1), jnp.int32)
    logits, _ = T.decode_step(tpl, cfg, qp, tok, jnp.int32(8), cache,
                              policy=policy)
    law = T.q16_island_counts(cfg, mode="decode")
    assert eng.counters["quantize_calls"] == law["quantize"]
    assert eng.counters["dequantize_calls"] == law["dequantize"]
    assert logits.dtype == jnp.float32  # the head read-out is the exit


def test_prefill_obeys_island_law(q16_setup):
    cfg, params, tpl, policy, qp = q16_setup
    eng = tpl.engine
    _reset_island_counters(eng)
    T.prefill(tpl, cfg, qp, jnp.zeros((1, 8), jnp.int32), cache_len=16,
              policy=policy)
    law = T.q16_island_counts(cfg, mode="prefill")
    assert eng.counters["quantize_calls"] == law["quantize"]
    assert eng.counters["dequantize_calls"] == law["dequantize"]


def test_island_law_scales_with_designated_islands():
    """The law itself is sane: swiglu adds one dequant over gelu; RoPE adds
    one quantize+dequant pair to decode."""
    import dataclasses

    cfg = reduced(get_config("qwen2-0.5b"))
    sw = T.q16_island_counts(cfg, mode="decode")
    ge = T.q16_island_counts(dataclasses.replace(cfg, act="gelu"), mode="decode")
    assert sw["dequantize"] == ge["dequantize"] + 1
    nr = T.q16_island_counts(dataclasses.replace(cfg, use_rope=False),
                             mode="decode")
    assert sw["quantize"] == nr["quantize"] + 1
    assert sw["dequantize"] == nr["dequantize"] + 1


# ---------------------------------------------------------------------------
# quantize-once weights
# ---------------------------------------------------------------------------


def test_weights_quantized_exactly_once(q16_setup):
    cfg, params, tpl, policy, qp = q16_setup
    eng = tpl.engine
    builds0 = eng.counters["qparam_builds"]
    hits0 = eng.counters["qparam_cache_hits"]
    qp2 = T.quantize_params(tpl, cfg, params, policy)
    qp3 = T.quantize_params(tpl, cfg, params, policy)
    assert qp2 is qp and qp3 is qp
    assert eng.counters["qparam_builds"] == builds0  # no rebuild
    assert eng.counters["qparam_cache_hits"] == hits0 + 2


def test_generate_reuses_qparams(q16_setup):
    from repro.launch.serve import generate

    cfg, params, tpl, policy, qp = q16_setup
    eng = tpl.engine
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0, cfg.vocab)
    generate(cfg, params, toks, gen=3, tpl=tpl, policy=policy)
    builds = eng.counters["qparam_builds"]
    weights = eng.counters["weights_quantized"]
    out1 = generate(cfg, params, toks, gen=3, tpl=tpl, policy=policy)
    out2 = generate(cfg, params, toks, gen=3, tpl=tpl, policy=policy)
    assert eng.counters["qparam_builds"] == builds, "generate() re-quantized"
    assert eng.counters["weights_quantized"] == weights
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_qparam_tree_shapes(q16_setup):
    cfg, params, tpl, policy, qp = q16_setup
    blk = qp["blocks"][0]
    assert isinstance(blk["attn"]["wq"]["w"], QTensor)
    assert isinstance(blk["ffn"]["down"]["w"], QTensor)
    assert blk["attn"]["wq"]["w"].dtype == jnp.int16
    # norms and the embedding lookup table stay float
    assert blk["norm"]["scale"].dtype == jnp.float32
    assert qp["embed"].dtype == jnp.float32
    # tied embeddings still get an int16 head copy
    assert isinstance(qp["lm_head"]["w"], QTensor)
    assert qp["lm_head"]["w"].shape == (cfg.d_model, cfg.vocab)


# ---------------------------------------------------------------------------
# int16 cache + numerics
# ---------------------------------------------------------------------------


def test_prefill_cache_is_int16(q16_setup):
    cfg, params, tpl, policy, qp = q16_setup
    _, cache = T.prefill(tpl, cfg, qp, jnp.zeros((1, 8), jnp.int32),
                         cache_len=16, policy=policy)
    c = cache["blocks"][0]["attn"]
    assert c["k"].dtype == jnp.int16 and c["v"].dtype == jnp.int16
    assert c["pos"].dtype == jnp.int32


def test_q16_decode_tracks_float_path(q16_setup):
    """Drift vs the float backend stays at quantization-noise scale and the
    greedy argmax matches on the fixed seed set."""
    cfg, params, tpl, policy, qp = q16_setup
    tpl_f = default_template()
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, cfg.vocab)
    lf, _ = T.forward(tpl_f, cfg, params, toks, mode="fwd")
    lq, _ = T.forward(tpl, cfg, qp, toks, mode="fwd", policy=policy)
    assert float(jnp.abs(lf - lq).mean()) < 5e-3
    assert float((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean()) >= 0.99


def test_grid_matmul_matches_mixed_format_oracle():
    """Engine grid-resident GEMM == qtensor_matmul_ref bit-for-bit, formats
    mixed (calibrated weight grid != activation grid), bias + relu fused."""
    eng = Engine(TemplateConfig(backend="q16"))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (6, 16)) * 0.4
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 8)) * 0.05
    b = jax.random.normal(jax.random.fold_in(key, 2), (8,)) * 0.1
    xq = quantize_qtensor(x, QFormat(4, 12))
    wq = quantize_qtensor(w)  # per-tensor calibrated (finer than Q4.12)
    bq = quantize_qtensor(b, QFormat(4, 12))
    assert wq.fmt.frac_bits > 12
    got = eng.matmul(xq, wq, bias=bq, relu=True)
    want = qtensor_matmul_ref(xq, wq, xq.fmt, bias=bq, relu=True)
    assert got.fmt == xq.fmt  # output follows the input's grid
    np.testing.assert_array_equal(np.asarray(got.raw), np.asarray(want.raw))


def test_wide_head_readout_is_exact():
    """wide=True returns the int32 accumulator exactly descaled — no
    saturation even when the true product leaves the int16 grid's range."""
    eng = Engine(TemplateConfig(backend="q16"))
    # true value 4 * 0.81 = 3.24 > 2 (outside Q2.14's range) while the int32
    # accumulator stays inside 2^31 (the documented wraparound bound)
    xq = quantize_qtensor(jnp.full((1, 4), 0.9), Q2_14)
    wq = quantize_qtensor(jnp.full((4, 2), 0.9), Q2_14)
    out = eng.matmul(xq, wq, wide=True)
    acc = np.asarray(xq.raw, np.int64) @ np.asarray(wq.raw, np.int64)
    want = (acc.astype(np.float32) * np.float32(2.0 ** -28)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(out), want)
    assert float(out[0, 0]) == pytest.approx(3.24, rel=1e-3)


# ---------------------------------------------------------------------------
# grid-resident CNN
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lenet_setup():
    params = init_cnn(jax.random.PRNGKey(0), LENET, scale=0.4)
    tpl = default_template("q16")
    img = jax.random.uniform(jax.random.PRNGKey(2), (4, 32, 32, 1)) * 2 - 1
    policy = calibrate_cnn_policy(tpl, LENET, params, img)
    qp = quantize_cnn_params(tpl, LENET, params, policy)
    return params, tpl, policy, qp


def test_lenet_forward_one_quant_one_dequant(lenet_setup):
    params, tpl, policy, qp = lenet_setup
    eng = tpl.engine
    img = jax.random.uniform(jax.random.PRNGKey(5), (3, 32, 32, 1)) * 2 - 1
    _reset_island_counters(eng)
    logits = cnn_forward(tpl, LENET, qp, img, policy=policy)
    assert eng.counters["quantize_calls"] == 1, "only the input quantizes"
    assert eng.counters["dequantize_calls"] == 1, "only the classifier dequantizes"
    assert logits.dtype == jnp.float32 and logits.shape == (3, 10)


def test_lenet_grid_path_tracks_float(lenet_setup):
    params, tpl, policy, qp = lenet_setup
    tpl_f = default_template()
    img = jax.random.uniform(jax.random.PRNGKey(6), (8, 32, 32, 1)) * 2 - 1
    lf = cnn_forward(tpl_f, LENET, params, img)
    lq = cnn_forward(tpl, LENET, qp, img, policy=policy)
    assert float(jnp.abs(lf - lq).max()) < 1e-2
    assert float((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean()) >= 0.99


def test_maxpool_on_raw_matches_pool_of_dequant():
    from repro.models.cnn import _maxpool

    q = quantize_qtensor(
        jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8, 4)), Q2_14
    )
    pooled = _maxpool(q, 2)
    assert isinstance(pooled, QTensor) and pooled.dtype == jnp.int16
    np.testing.assert_array_equal(
        np.asarray(pooled.dequantize()),
        np.asarray(_maxpool(q.dequantize(), 2)),
    )


# ---------------------------------------------------------------------------
# unsupported combos fail loudly
# ---------------------------------------------------------------------------


def test_q16_policy_requires_q16_backend():
    cfg = reduced(get_config("qwen2-0.5b"))
    with pytest.raises(ValueError, match="requires the 'q16' backend"):
        validate_policy(TemplateConfig(backend="xla"), NumericsPolicy("q16"))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="requires the 'q16' backend"):
        T.quantize_params(default_template("pallas"), cfg, params,
                          NumericsPolicy("q16"))


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "recurrentgemma-9b",
                                  "whisper-medium", "granite-moe-3b-a800m"])
def test_q16_policy_rejects_non_grid_families(arch):
    cfg = reduced(get_config(arch))
    tpl = default_template("q16")
    with pytest.raises(ValueError):
        T.quantize_params(tpl, cfg, {"blocks": (), "tail": ()},
                          NumericsPolicy("q16"))


def test_float_policy_is_passthrough():
    cfg = reduced(get_config("qwen2-0.5b"))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    out = T.quantize_params(default_template(), cfg, params,
                            NumericsPolicy("float"))
    assert out is params
