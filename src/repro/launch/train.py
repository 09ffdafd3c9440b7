"""End-to-end fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 50 \
        --reduced --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On this CPU container the driver runs *reduced* configs on the host device;
on a real cluster the same code runs the full config under
``make_production_mesh()`` (pass ``--mesh single|multi``).  Features:

  * deterministic restart-safe data pipeline (pure function of step)
  * atomic checkpoints + auto-resume (elastic across mesh changes)
  * crash-loop restarts with injected failures (``--fail-at``)
  * optional int8 gradient compression with error feedback (``--compress``)
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config, reduced
from repro.core.engine import (
    PLAN_STORE_ENV,
    plan_store_stats,
    save_plan_store,
    warm_start_plan_store,
)
from repro.data import make_pipeline
from repro.launch.steps import (
    default_optimizer,
    make_train_step,
    state_shardings,
)
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.parallel.sharding import TRAIN_RULES, use_mesh
from repro.runtime import FailureInjector, run_with_restarts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--mesh", choices=["none", "single", "multi"], default="none")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--fail-at", type=int, action="append", default=[],
                    help="inject a failure at this step (repeatable)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--plan-store", default=None,
                    help=f"persisted plan-store path (default: ${PLAN_STORE_ENV})")
    args = ap.parse_args(argv)

    store_path, n = warm_start_plan_store(args.plan_store)
    if n:
        print(f"[train] plan store: warm-started {n} entries from {store_path}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.rule_overrides:
        rules = TRAIN_RULES.with_overrides(**dict(cfg.rule_overrides))
    else:
        rules = TRAIN_RULES

    mesh = None
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    from repro.optim import AdamW, cosine_warmup

    opt = AdamW(lr=cosine_warmup(args.lr, max(args.steps // 10, 1), args.steps))
    train_step = make_train_step(cfg, opt=opt, accum=args.accum)
    pipe = make_pipeline(
        cfg, SHAPES["train_4k"], seed=args.seed,
        mesh=mesh, rules=rules if mesh else None,
        global_batch=args.batch, seq_len=args.seq,
    )

    def build_state():
        params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
        return params, adamw_init(params)

    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    injector = FailureInjector(fail_at_steps=args.fail_at)

    state = {}

    def restore_fn() -> int:
        params, opt_state = build_state()
        step = ckpt.latest()
        if step is None:
            state["params"], state["opt"] = params, opt_state
            return 0
        shardings = None
        if mesh is not None:
            p_sh, o_sh = state_shardings(cfg, mesh, rules)
            shardings = {"params": p_sh, "opt": o_sh}
        tree = {"params": params, "opt": opt_state}
        from repro.checkpoint import restore

        loaded = restore(args.ckpt_dir, step, tree, shardings)
        state["params"], state["opt"] = loaded["params"], loaded["opt"]
        print(f"[train] resumed from checkpoint step {step}")
        return step

    jit_step = jax.jit(train_step, donate_argnums=(0, 1))
    history = []

    def step_fn(step: int):
        injector.check(step)
        batch = pipe.batch(step)
        t0 = time.time()
        state["params"], state["opt"], metrics = jit_step(
            state["params"], state["opt"], batch
        )
        loss = float(metrics["loss"])
        history.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(
                f"[train] step {step:4d} loss {loss:8.4f} "
                f"gnorm {float(metrics['grad_norm']):8.3f} "
                f"lr {float(metrics['lr']):.2e} "
                f"({time.time() - t0:.2f}s)",
                flush=True,
            )

    def save_fn(step: int):
        ckpt.save(step, {"params": state["params"], "opt": state["opt"]},
                  extra={"arch": cfg.name})

    ctx = use_mesh(mesh, rules) if mesh is not None else _null_ctx()
    with ctx:
        stats = run_with_restarts(
            num_steps=args.steps,
            step_fn=step_fn,
            save_fn=save_fn,
            restore_fn=restore_fn,
            checkpoint_every=args.ckpt_every,
            max_failures=max(len(args.fail_at), 1),
        )
    first, last = history[0], sum(history[-5:]) / max(len(history[-5:]), 1)
    print(
        f"[train] done: {stats['steps']} steps, {stats['failures']} failures, "
        f"restarts at {stats['restarts']}, loss {first:.4f} -> {last:.4f}"
    )
    pst = plan_store_stats()
    print(f"[train] plan registry: {pst['gemm_blocks']} GEMM blocks + "
          f"{pst['conv_tiles']} conv tiles, {pst['misses']} DSE searches")
    if store_path:
        save_plan_store(store_path)
        print(f"[train] plan store: saved to {store_path}")
    return stats, history


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
