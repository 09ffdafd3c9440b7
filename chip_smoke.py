"""Smoke test of the template engine on a TPU: VGG16 at 224x224, its
published widths, through the normal calls — ``plan_cnn`` then
``cnn_forward`` on a :class:`~repro.core.template.Template` — with every
conv and FC layer a compiled Pallas kernel.

    python chip_smoke.py [--seed 0]        # one chip
    python chip_smoke.py --four-chips      # H-slab sharding over four chips

One chip, batch 1 and batch 8, weights drawn from ``--seed``:

* float: the ``pallas`` backend against a float32 XLA reference of the same
  network (``models.cnn.cnn_forward_ref``) run on the same chip at
  ``jax.default_matmul_precision("highest")``;
* q16: the grid-resident fixed-point backend (``calibrate_cnn_policy`` +
  ``quantize_cnn_params``) against the plain-jnp integer oracle run on the
  host CPU in this process — bit-identical — and its argmax against float.

``--four-chips`` runs only the spatial (H-slab) path: S=4 slabs over four
chips against the unsharded one-chip forward, q16 bit-identical and float
allclose, and prints where the slabs were placed.

Every compiled Pallas program must hold a ``tpu_custom_call`` (a kernel that
fell back to the interpreter or to XLA would not).  Timing is the
benchmark's (``bench/run.py``), not this script's.  Any failed check
raises; the last line of stdout is the JSON result.  Without a TPU the
script exits non-zero before printing a result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import CacheEvents, enable_compile_cache  # noqa: E402

#: |pallas − reference| / max|reference| bound for the float forward.  Both
#: sides contract f32 operands at full f32 precision on the MXU; what is
#: left is the order of accumulation over up to 25088-term sums through 16
#: layers, a few f32 ulps of the logit scale.  1e-4 is ~800 ulps (2^-23).
FLOAT_RTOL = 1e-4
#: Minimum share of images whose q16 argmax equals the float argmax.  q16
#: rounds every activation to its calibrated grid (2^-12 here); with random
#: weights some top-2 logits lie closer than that noise, so a flip is a
#: numerics fact, not a kernel fault — the q16 kernels are held to the
#: bit-identical oracle check instead.
ARGMAX_MIN = 0.75
BATCHES = (1, 8)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    """A smoke check: raises (exit non-zero) when it fails, also under -O."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def require_tpu(count: int):
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind!r} count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {d.platform!r}); nothing run")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} chips, JAX sees {len(devs)}")
    return devs


def compiled_phase(name: str, fn, *args, pallas: bool = True):
    """jit + lower + compile ``fn``, check for the Pallas custom call, run
    it once; returns its output."""
    compiled = jax.jit(fn).lower(*args).compile()
    if pallas:
        n_kernels = compiled.as_text().count("custom_call_target=\"tpu_custom_call\"")
        check(n_kernels > 0, f"{name}: no tpu_custom_call in the compiled HLO")
        log(f"  {name}: pallas_kernels={n_kernels}")
    return jax.block_until_ready(compiled(*args))


def check_float(name, out, ref):
    out, ref = np.asarray(out), np.asarray(ref)
    check(out.shape == ref.shape and bool(np.isfinite(out).all()),
          f"{name}: shape {out.shape} vs {ref.shape}, or non-finite values")
    scale = float(np.abs(ref).max())
    err = float(np.abs(out - ref).max())
    log(f"  check {name}: max|out-ref| = {err:.3e}, max|ref| = {scale:.3e}, "
        f"rel = {err / scale:.3e} (bound {FLOAT_RTOL:g})")
    check(err <= FLOAT_RTOL * scale, f"{name}: {err} > {FLOAT_RTOL} * {scale}")


def check_bitwise(name, out, ref):
    out, ref = np.asarray(out), np.asarray(ref)
    same = out.shape == ref.shape and np.array_equal(out, ref)
    log(f"  check {name}: bit-identical = {same}")
    check(same, f"{name}: not bit-identical")


def one_chip(seed: int, spec=None) -> None:
    """VGG16@224 (or ``spec``, for a rehearsal at a smaller size) at batch
    1 and 8, float and q16."""
    from repro.core.quantization import NumericsPolicy
    from repro.core.template import default_template
    from repro.models import cnn as C

    spec = spec or C.VGG16
    cpu = jax.devices("cpu")[0]
    key = jax.random.PRNGKey(seed)
    params = C.init_cnn(key, spec, scale=2**0.5)  # He init: O(1) activations
    hw = spec.input_hw
    x8 = jax.random.normal(jax.random.fold_in(key, 1), (8, hw, hw, spec.input_ch))

    tpl = default_template("pallas")
    tq = default_template("q16")
    log(f"plan target: {tpl.config.hw}")
    policy = C.calibrate_cnn_policy(tq, spec, params, x8, base=NumericsPolicy("q16"))
    qp = C.quantize_cnn_params(tq, spec, params, policy)
    log(f"q16 activation grid (calibrated): {policy.fmt}")
    qp_cpu = jax.device_put(qp, cpu)

    for n in BATCHES:
        x = x8[:n]
        for backend, t in (("pallas", tpl), ("q16", tq)):
            plan = C.plan_cnn(t, spec, x.shape)
            log(f"plan vgg16 batch={n} backend={backend}:")
            for line in plan.describe():
                log(f"    {line}")
        log(f"phase float batch={n}")
        pf = C.plan_cnn(tpl, spec, x.shape)
        out_f = compiled_phase(
            f"float_pallas_b{n}",
            lambda p, a: C.cnn_forward(tpl, spec, p, a, plan=pf), params, x)
        with jax.default_matmul_precision("highest"):
            ref_f = compiled_phase(
                f"float_xla_reference_b{n}",
                lambda p, a: C.cnn_forward_ref(spec, p, a), params, x,
                pallas=False)
        check_float(f"float pallas vs XLA highest, batch {n}", out_f, ref_f)

        log(f"phase q16 batch={n}")
        pq = C.plan_cnn(tq, spec, x.shape)
        out_q = compiled_phase(
            f"q16_pallas_b{n}",
            lambda p, a: C.cnn_forward(tq, spec, p, a, policy=policy, plan=pq),
            qp, x)
        with jax.default_device(cpu):
            ref_q = jax.jit(
                lambda p, a: C.cnn_forward_ref(spec, p, a, policy=policy)
            )(qp_cpu, jax.device_put(x, cpu))
            ref_q = jax.block_until_ready(ref_q)
        log(f"  q16 CPU oracle on {cpu}")
        check_bitwise(f"q16 pallas vs CPU integer oracle, batch {n}", out_q, ref_q)
        agree = float(np.mean(np.argmax(np.asarray(out_q), -1)
                              == np.argmax(np.asarray(out_f), -1)))
        log(f"  check q16 vs float argmax agreement, batch {n}: {agree:.3f} "
            f"(bound {ARGMAX_MIN})")
        check(agree >= ARGMAX_MIN, f"argmax agreement {agree} < {ARGMAX_MIN}")


def four_chips(seed: int, spec=None) -> None:
    """VGG16@224 (or ``spec``) at batch 1: S=4 H slabs over four chips
    against the unsharded forward on one of them, float and q16."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.quantization import NumericsPolicy
    from repro.core.template import default_template
    from repro.launch.mesh import make_mesh
    from repro.models import cnn as C
    from repro.parallel import sharding as sh

    spec = spec or C.VGG16
    devs = jax.devices()
    mesh = make_mesh((4,), ("data",), devices=devs[:4])
    key = jax.random.PRNGKey(seed)
    params = C.init_cnn(key, spec, scale=2**0.5)
    hw = spec.input_hw
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, hw, hw, spec.input_ch))
    tpl = default_template("pallas")
    tq = default_template("q16")
    policy = C.calibrate_cnn_policy(tq, spec, params, x, base=NumericsPolicy("q16"))
    qp = C.quantize_cnn_params(tq, spec, params, policy)
    replicated = NamedSharding(mesh, P())

    for backend, t, p, pol in (("pallas", tpl, params, None),
                               ("q16", tq, qp, policy)):
        log(f"phase spatial {backend}: S=4 H slabs over {mesh.devices.tolist()}")
        p0 = C.plan_cnn(t, spec, x.shape)
        one = compiled_phase(
            f"{backend}_one_chip",
            lambda q, a: C.cnn_forward(t, spec, q, a, policy=pol, plan=p0),
            jax.device_put(p, devs[0]), jax.device_put(x, devs[0]))
        with sh.use_mesh(mesh, sh.SERVE_RULES):
            ps = C.plan_cnn(t, spec, x.shape, mesh=mesh, spatial="data")
            check(ps.spatial == 4 and all(cp.halo is not None for cp in ps.convs),
                  "the spatial plan does not put every conv on 4 slabs")
            for line in ps.describe():
                log(f"    {line}")
            four = compiled_phase(
                f"{backend}_four_chip_slabs",
                lambda q, a: C.cnn_forward(t, spec, q, a, policy=pol, plan=ps),
                jax.device_put(p, replicated), jax.device_put(x, replicated))
            slabs = jax.jit(
                lambda a: sh.constrain_slabs(C._to_slabs(a, 4), "data")
            )(jax.device_put(x, replicated))
        placed = sorted((s.index[0].start or 0, s.device.id)
                        for s in slabs.addressable_shards)
        log(f"  slab placement (slab -> device id): {placed}")
        check(len({d for _, d in placed}) == 4, f"slabs not on 4 chips: {placed}")
        if backend == "q16":
            check_bitwise("q16 4-chip slabs vs one chip", four, one)
        else:
            check_float("float 4-chip slabs vs one chip", four, one)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the H-slab path over four chips")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    events = CacheEvents()
    log(f"compile cache: {cache}")
    count = 4 if args.four_chips else 1
    devs = require_tpu(count)
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"compile cache: {events}")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
