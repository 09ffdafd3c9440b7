"""Per-architecture smoke tests: reduced config, one forward + one train step
on CPU, asserting output shapes and no NaNs — plus prefill/decode parity
(the serving path must agree with the training path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, reduced
from repro.core.template import default_template
from repro.launch.steps import make_train_step
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.launch.mesh import make_mesh

ARCHS = sorted(all_configs())
TPL = default_template()


def _ctx_for(cfg, b, key):
    if cfg.family == "encdec":
        return jax.random.normal(key, (b, cfg.n_frames, cfg.d_model)) * 0.1
    if cfg.family == "vlm":
        return jax.random.normal(key, (b, cfg.n_image_tokens, cfg.d_model)) * 0.1
    return None


def _setup(name, no_drop_moe=False):
    cfg = reduced(all_configs()[name])
    if no_drop_moe and cfg.family == "moe":
        cfg = dataclasses.replace(cfg, capacity_factor=100.0)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    ctx = _ctx_for(cfg, b, jax.random.PRNGKey(2))
    return cfg, params, tokens, ctx


@pytest.mark.parametrize("name", ARCHS)
def test_forward_shapes_and_finite(name):
    cfg, params, tokens, ctx = _setup(name)
    logits, aux = T.forward(TPL, cfg, params, tokens, ctx=ctx)
    assert logits.shape == (*tokens.shape, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), f"{name}: non-finite logits"
    assert bool(jnp.isfinite(aux)), f"{name}: non-finite aux"


@pytest.mark.parametrize("name", ARCHS)
def test_one_train_step(name):
    cfg, params, tokens, ctx = _setup(name)
    opt_state = adamw_init(params)
    step = make_train_step(cfg)
    batch = {"tokens": tokens}
    if ctx is not None:
        batch["ctx"] = ctx
    new_params, new_opt, metrics = jax.jit(step)(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(new_opt.step) == 1
    # params must actually change
    moved = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(
            lambda p, q: float(jnp.abs(p - q).sum()), params, new_params
        ),
    )
    assert moved > 0, f"{name}: update was a no-op"


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_decode_parity(name):
    """decode_step(t=S-1) after prefill(S-1) == forward(S) at the last pos."""
    cfg, params, tokens, ctx = _setup(name, no_drop_moe=True)
    s = tokens.shape[1]
    logits_full, _ = T.forward(TPL, cfg, params, tokens, ctx=ctx)
    lg_pre, cache = T.prefill(TPL, cfg, params, tokens[:, : s - 1], ctx=ctx,
                              cache_len=s + 4)
    np.testing.assert_allclose(
        np.asarray(lg_pre), np.asarray(logits_full[:, -2]), atol=3e-4, rtol=3e-4,
        err_msg=f"{name}: prefill last-logit mismatch",
    )
    lg_dec, _ = T.decode_step(TPL, cfg, params, tokens[:, s - 1 : s], s - 1, cache)
    np.testing.assert_allclose(
        np.asarray(lg_dec), np.asarray(logits_full[:, -1]), atol=3e-4, rtol=3e-4,
        err_msg=f"{name}: decode parity mismatch",
    )


@pytest.mark.parametrize("name", ["recurrentgemma-9b", "mamba2-1.3b"])
def test_multi_step_decode_matches_forward(name):
    """Roll 4 decode steps; each must match the teacher-forced forward."""
    cfg, params, tokens, ctx = _setup(name)
    s = tokens.shape[1]
    logits_full, _ = T.forward(TPL, cfg, params, tokens, ctx=ctx)
    k = 4
    _, cache = T.prefill(TPL, cfg, params, tokens[:, : s - k], ctx=ctx, cache_len=s)
    for i in range(k):
        t = s - k + i
        lg, cache = T.decode_step(TPL, cfg, params, tokens[:, t : t + 1], t, cache)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(logits_full[:, t]), atol=5e-4, rtol=5e-4,
            err_msg=f"{name}: decode step {i} diverged",
        )


def test_sliding_window_ring_buffer_wraps():
    """Hybrid arch with tiny window: decode past the window must still match
    the windowed teacher-forced forward (ring-buffer slot reuse)."""
    cfg = reduced(all_configs()["recurrentgemma-9b"])
    cfg = dataclasses.replace(cfg, window=8)  # smaller than the sequence
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    b, s = 1, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    logits_full, _ = T.forward(TPL, cfg, params, tokens)
    _, cache = T.prefill(TPL, cfg, params, tokens[:, : s - 1], cache_len=s)
    # the local-attn layer cache must be window-sized, not seq-sized
    for pos_cache in jax.tree.leaves(cache):
        pass
    lg, _ = T.decode_step(TPL, cfg, params, tokens[:, s - 1 : s], s - 1, cache)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(logits_full[:, -1]), atol=5e-4, rtol=5e-4,
    )


def test_param_axes_structure_matches_params():
    """param_axes is a valid prefix pytree of params: every axes leaf either
    replicates a whole subtree (None) or names >= the leaf's rank axes."""
    from repro.models.transformer import _is_axes_leaf
    from repro.parallel.sharding import TRAIN_RULES, tree_shardings

    mesh = make_mesh((1,), ("data",))
    for name in ARCHS:
        cfg = reduced(all_configs()[name])
        params = jax.eval_shape(lambda c=cfg: T.init_params(jax.random.PRNGKey(0), c))
        axes = T.param_axes(cfg)
        # tree_shardings must accept the pair without structural errors
        sh = tree_shardings(mesh, TRAIN_RULES, params, axes)
        # and every tuple-axes leaf must match its param's rank exactly
        def walk(ax, p):
            if _is_axes_leaf(ax):
                if isinstance(ax, tuple) and hasattr(p, "shape"):
                    assert len(ax) == len(p.shape), (name, ax, p.shape)
            elif isinstance(ax, dict):
                for k in ax:
                    walk(ax[k], p[k])
            elif isinstance(ax, (list, tuple)):
                for a, q in zip(ax, p):
                    walk(a, q)

        walk(axes, params)


def test_cache_axes_structure():
    for name in ["qwen2-0.5b", "recurrentgemma-9b", "mamba2-1.3b", "whisper-medium"]:
        cfg = reduced(all_configs()[name])
        shapes = jax.eval_shape(lambda c=cfg: T.init_cache(c, 2, 32))
        axes = T.cache_axes(cfg, shapes)
        # must be structurally zippable
        jax.tree.map(
            lambda a, s: True,
            axes, shapes,
            is_leaf=lambda x: x is None or (
                isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x) and len(x) > 0
            ),
        )
