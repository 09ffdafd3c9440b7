"""Batched serving driver: prefill a batch of prompts, then decode tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --prompts 4 \
        --prompt-len 32 --gen 16

Reduced configs run end-to-end on CPU; full configs are exercised by the
dry-run (prefill_32k / decode_32k / long_500k cells compile the exact same
step functions under the production mesh).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.core.engine import PLAN_STORE_ENV, save_plan_store, warm_start_plan_store
from repro.core.template import default_template
from repro.data.pipeline import synthetic_batch
from repro.launch.scheduler import (
    Request,
    SamplingParams,
    SchedulerConfig,
    ServeScheduler,
    SystemClock,
    compiled_steps,
    replay_trace,
    sampler_fn,
)
from repro.launch.mesh import make_mesh
from repro.launch.router import ReplicaRouter
from repro.models import transformer as T


def shards_mesh(shards: int):
    """An ("data", "model") mesh with a ``shards``-way model axis over the
    visible devices (1 = no mesh, single-device decode)."""
    if shards <= 1:
        return None
    n = jax.device_count()
    if n % shards:
        raise SystemExit(
            f"--shards {shards} does not divide the {n} visible devices")
    return make_mesh((n // shards, shards), ("data", "model"))


def run_router(cfg, params, tpl, *, replicas: int, mesh=None,
               requests: int, prompt_len: int, gen: int, seed: int,
               policy=None, sampling=None) -> ReplicaRouter:
    """Serve the synthetic request set across N scheduler replicas behind
    the front-tier :class:`ReplicaRouter` (DESIGN.md §9).  Each replica runs
    the same tensor-parallel mesh (or none); tokens drain into the router's
    exactly-once ledger."""
    ladder = tuple(sorted({max(4, prompt_len // 2), prompt_len, 2 * prompt_len}))

    def make_sched(rid, clock):
        return ServeScheduler(
            cfg, params, tpl=tpl, clock=clock, policy=policy,
            sampling=sampling, mesh=mesh,
            sched=SchedulerConfig(ladder=ladder, slots=4,
                                  max_new_limit=max(gen, 1),
                                  max_queue=max(256, requests)),
        )

    router = ReplicaRouter(make_sched, replicas, clock=SystemClock(),
                           tick_dt=0.0)
    rng = np.random.default_rng(seed)
    trace = []
    for _ in range(requests):
        length = int(rng.integers(max(2, prompt_len // 2), 2 * prompt_len + 1))
        prompt = synthetic_batch(seed, len(trace), 1, length, cfg.vocab)
        trace.append(Request(prompt=tuple(int(t) for t in np.asarray(prompt)[0]),
                             max_new=gen))
    router.run(trace)
    return router


def generate(cfg, params, tokens, ctx=None, *, gen: int = 16, cache_len=None,
             greedy=True, tpl=None, policy=None, sampling=None):
    """Prefill + autoregressive decode.  tokens: (B, S) prompts.

    The jitted prefill/decode closures are hoisted into the
    `scheduler.compiled_steps` memo (keyed by template, config, cache_len,
    numerics policy): repeated calls — and the continuous-batching
    scheduler, which shares the memo — reuse one triple of compiled
    callables instead of retracing per call.

    ``policy``: a quantized :class:`NumericsPolicy` runs the whole decode
    loop grid-resident (weights quantized once via the engine's qparam
    cache, int16 KV cache, float only at the designated islands).

    ``sampling``: a :class:`SamplingParams` with temperature > 0 draws each
    token from a per-row RNG lane (lane = batch row, position = the drawn
    token's absolute position); None / temperature <= 0 is exact greedy.
    """
    tpl = tpl or default_template()
    if policy is not None and policy.quantized:
        params = T.quantize_params(tpl, cfg, params, policy)
    b, s = tokens.shape
    cache_len = cache_len or (s + gen)
    fns = compiled_steps(tpl, cfg, cache_len, policy)
    prefill, decode = fns.prefill, fns.decode
    sampled = sampling is not None and not sampling.greedy
    smp = sampler_fn(sampling.temperature, sampling.top_k) if sampled else None
    lanes = jnp.arange(b, dtype=jnp.int32)

    def pick(logits, position):
        if not sampled:
            return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        toks = smp(logits, jnp.uint32(sampling.seed), lanes,
                   jnp.full((b,), position, jnp.int32))
        return toks[:, None].astype(jnp.int32)

    logits, cache = prefill(params, tokens, ctx, jnp.int32(s - 1))
    out = []
    tok = pick(logits, s)
    out.append(tok)
    for i in range(gen - 1):
        logits, cache = decode(params, tok, jnp.int32(s + i), cache)
        tok = pick(logits, s + i + 1)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def run_scheduler(cfg, params, tpl, *, requests: int, prompt_len: int,
                  gen: int, seed: int, clock=None, policy=None,
                  sampling=None, prefill_chunk: int = 0,
                  mesh=None) -> ServeScheduler:
    """Serve a mixed-length synthetic request set through the
    continuous-batching scheduler (the production path of DESIGN.md §7).

    ``policy`` threads the numerics policy into the scheduler's compiled
    steps — `--backend q16 --scheduler` serves a fully fixed-point decode
    loop instead of silently ignoring the backend.  ``sampling`` selects
    greedy vs per-slot-lane sampled decode; ``prefill_chunk`` > 0 streams
    long prompts in chunks interleaved with decode."""
    ladder = tuple(sorted({max(4, prompt_len // 2), prompt_len, 2 * prompt_len}))
    sched = ServeScheduler(
        cfg, params, tpl=tpl, clock=clock or SystemClock(), policy=policy,
        sampling=sampling, mesh=mesh,
        # this path serves exactly `requests` requests, all arriving at t=0 —
        # the queue must hold the whole burst, rejection is not policy here
        sched=SchedulerConfig(ladder=ladder, slots=4, max_new_limit=max(gen, 1),
                              max_queue=max(256, requests),
                              prefill_chunk=prefill_chunk),
    )
    sched.warmup()
    rng = np.random.default_rng(seed)
    trace = []
    for _ in range(requests):
        length = int(rng.integers(max(2, prompt_len // 2), 2 * prompt_len + 1))
        prompt = synthetic_batch(seed, len(trace), 1, length, cfg.vocab)
        trace.append(Request(prompt=tuple(int(t) for t in np.asarray(prompt)[0]),
                             max_new=gen))
    replay_trace(sched, trace, tick=0.0)
    return sched


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "q16", "q8"])
    ap.add_argument("--precision-budget", type=float, default=0.99,
                    help="with --backend q8: minimum per-layer solo-flip "
                         "argmax agreement for the precision DSE to drop a "
                         "layer group to the int8 rung (DESIGN.md §11)")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the synthetic prompts AND the sampled-decode "
                         "RNG lanes (reproducible per seed)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampled decode temperature; 0 = exact greedy "
                         "argmax (the byte-parity default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampled decode to the k highest logits "
                         "(0 = full softmax)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="with --scheduler: stream prompts longer than this "
                         "into their slot in fixed-width chunks interleaved "
                         "with decode (0 = whole-bucket prefill)")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the continuous-batching scheduler "
                         "(mixed-length requests, bucketed prefill, coalesced "
                         "decode; DESIGN.md §7)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --scheduler: route requests across N "
                         "data-parallel scheduler replicas behind the "
                         "front-tier ReplicaRouter (DESIGN.md §9)")
    ap.add_argument("--shards", type=int, default=1,
                    help="with --scheduler: run each replica's decode step "
                         "tensor-parallel over an N-way model axis "
                         "(bitwise-equal to single-device; DESIGN.md §9)")
    ap.add_argument("--plan-store", default=None,
                    help=f"persisted plan-store path (default: ${PLAN_STORE_ENV})")
    args = ap.parse_args(argv)

    # Warm-start the plan registry from the persisted store (if any): a
    # restart with a populated store performs zero DSE grid searches.
    store_path, n = warm_start_plan_store(args.plan_store)
    if n:
        print(f"[serve] plan store: warm-started {n} entries from {store_path}")

    cfg = reduced(get_config(args.arch))
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)

    # One template (and thus one execution engine + shared plan cache) for the
    # whole serve session: prefill and every decode step reuse the same plan,
    # so DSE block selection runs at most once per distinct GEMM shape.
    # --backend q8 is the mixed-precision tier of the same q16 template: the
    # kernels are dtype-polymorphic, so the template backend stays "q16" and
    # the precision DSE decides per layer group which grid it runs on.
    backend = "q16" if args.backend == "q8" else args.backend
    tpl = default_template(backend)
    # --backend q16 serves grid-resident fixed point (DESIGN.md §8): weights
    # quantized once, int16 KV cache, activation grid picked by a small
    # max-abs calibration pass over one synthetic batch.
    policy = None
    if backend == "q16":
        cal = synthetic_batch(args.seed + 1, 7, 2, max(args.prompt_len, 8),
                              cfg.vocab)
        try:
            policy = T.calibrate_policy(tpl, cfg, params, cal)
        except ValueError as err:
            if args.scheduler:  # the batched path must not silently degrade
                raise SystemExit(f"--backend {args.backend} --scheduler: "
                                 f"{err}") from err
            print(f"[serve] WARNING: {err}; falling back to per-op q16 "
                  f"(float round-trips between layers)")
        else:
            if args.backend == "q8":
                # the drift-aware precision DSE (DESIGN.md §11): measure each
                # group's solo-flip argmax drift, drop groups meeting the
                # budget to the int8 rung, pin every choice in the registry
                # (warm restarts replay the pins with zero searches)
                policy = T.calibrate_precision(
                    tpl, cfg, params, cal, budget=args.precision_budget,
                    policy=policy)
                n8 = sum(1 for _, f in policy.layer_fmts if f.total_bits == 8)
                print(f"[serve] numerics: mixed int8/int16 grid-resident, "
                      f"base {policy.fmt.name}, {n8}/"
                      f"{len(policy.layer_fmts)} groups on the int8 rung "
                      f"(budget {args.precision_budget})")
            else:
                print(f"[serve] numerics: q16 grid-resident, activations "
                      f"{policy.fmt.name} (calibrated), weights per-tensor")
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              seed=args.seed)
    if not sampling.greedy:
        print(f"[serve] sampling: temperature={sampling.temperature} "
              f"top_k={sampling.top_k} seed={sampling.seed} "
              f"(per-lane RNG, reproducible per seed)")
    t0 = time.time()
    if args.scheduler and args.replicas > 1:
        try:
            router = run_router(cfg, params, tpl, replicas=args.replicas,
                                mesh=shards_mesh(args.shards),
                                requests=args.prompts,
                                prompt_len=args.prompt_len, gen=args.gen,
                                seed=args.seed, policy=policy,
                                sampling=sampling)
        except ValueError as err:
            raise SystemExit(f"--replicas: {err}") from err
        dt = time.time() - t0
        ledger = router.ledger.as_dict()
        n_tok = sum(len(s) for s in ledger.values())
        print(f"[serve] arch={cfg.name} backend={args.backend} "
              f"router replicas={args.replicas} shards={args.shards} "
              f"requests={args.prompts} generated={n_tok} tokens "
              f"in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        print(f"[serve] {router.stats_line()}")
        gen = [ledger[r] for r in sorted(ledger)]
    elif args.scheduler:
        try:
            sched = run_scheduler(cfg, params, tpl, requests=args.prompts,
                                  prompt_len=args.prompt_len, gen=args.gen,
                                  seed=args.seed, policy=policy,
                                  sampling=sampling,
                                  prefill_chunk=args.prefill_chunk,
                                  mesh=shards_mesh(args.shards))
        except ValueError as err:  # admission policy lives in ServeScheduler
            raise SystemExit(f"--scheduler: {err}") from err
        dt = time.time() - t0
        n_tok = sched.counters["tokens"]
        print(f"[serve] arch={cfg.name} backend={args.backend} "
              f"scheduler requests={args.prompts} generated={n_tok} tokens "
              f"in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        print(f"[serve] {sched.stats_line()}")
        gen = [sched.results[r].generated for r in sorted(sched.results)]
    else:
        tokens = synthetic_batch(args.seed, 0, args.prompts, args.prompt_len,
                                 cfg.vocab)
        ctx = None
        if cfg.family == "encdec":
            ctx = jax.random.normal(
                jax.random.PRNGKey(1), (args.prompts, cfg.n_frames, cfg.d_model)
            ) * 0.1
        elif cfg.family == "vlm":
            ctx = jax.random.normal(
                jax.random.PRNGKey(1), (args.prompts, cfg.n_image_tokens, cfg.d_model)
            ) * 0.1
        gen = generate(cfg, params, tokens, ctx, gen=args.gen, tpl=tpl,
                       policy=policy, sampling=sampling)
        dt = time.time() - t0
        print(f"[serve] arch={cfg.name} backend={args.backend} batch={args.prompts} "
              f"prompt={args.prompt_len} generated={gen.shape[1]} tokens "
              f"in {dt:.2f}s ({args.prompts * args.gen / dt:.1f} tok/s)")
    st = tpl.engine.plan_cache.stats()
    print(f"[serve] plan registry: {st['gemm_blocks']} GEMM blocks + "
          f"{st['conv_tiles']} conv tiles planned "
          f"({st['measured']} measured), {st['misses']} DSE searches, "
          f"{st['hits']} cache hits")
    if store_path:
        save_plan_store(store_path)
        print(f"[serve] plan store: saved to {store_path}")
    print("[serve] sample generations:")
    for row in gen[: min(2, len(gen))]:
        print("   ", list(np.asarray(row).tolist()))
    return gen


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
