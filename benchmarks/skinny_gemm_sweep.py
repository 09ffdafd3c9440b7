"""Chip sweep behind the skinny-M GEMM model (``core/dse.py``).

Times ``matmul_q16`` (int16 operands) and ``matmul_fp`` (float32, HIGHEST)
at VGG16's FC shapes with M = 1 and 8, over weight tiles (bk, bn) with
bm = 8.  Each point runs the kernel ``reps`` times inside one jitted loop
(each iteration depends on the last, so nothing is hoisted) and reports the
host-clock time per call, the grid steps and the time per step.  The DSE's
per-step overhead and per-weight cost are fitted from these rows.

Run on a TPU (compiles take about a minute, timings a few more)::

    PYTHONPATH=src python benchmarks/skinny_gemm_sweep.py --out skinny_sweep.json

Without a TPU it exits non-zero: an interpreted kernel's time says nothing
about the chip.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.core.tiling import TPU_V5E, MatmulBlock, ceil_div
from repro.kernels.matmul_fp import matmul_fp_pallas
from repro.kernels.matmul_q16 import matmul_q16_pallas

#: (name, K, N) of VGG16's first two FC layers.
SHAPES = (("fc0", 25088, 4096), ("fc1", 4096, 4096))
#: Weight tiles swept at every shape (those that divide it), besides the
#: (128, 128) block the planner fell back to before the skinny branch.
BKS = (256, 512, 896, 1024, 1792, 2048, 3584, 4096)
BNS = (512, 1024, 2048, 4096)
#: Largest tile swept, in weight elements (a little over what the VMEM model
#: admits, so the sweep shows where the chip's compiler refuses).
MAX_TILE = 6 * 2**20


def points():
    """(kind, layer, m, K, N, bk, bn) of every point swept: all tiles at
    M = 8, and at M = 1 the fallback block and two large tiles (M = 1 pads
    to the same 8-row kernel)."""
    out = []
    for kind in ("q16", "f32"):
        for layer, k, n in SHAPES:
            tiles = [(128, 128)] + [
                (bk, bn) for bk in BKS for bn in BNS
                if k % bk == 0 and n % bn == 0 and bk * bn <= MAX_TILE
            ]
            for m in (8, 1):
                some = tiles if m == 8 else [tiles[0], (512, 4096), (1024, 2048)]
                out += [(kind, layer, m, k, n, bk, bn) for bk, bn in some
                        if k % bk == 0]
    return out


def _looped(kind, block, reps):
    """``reps`` kernel calls in one program, each input tied to the last
    output (a ReLU output is never negative, which the compiler cannot
    know) so the calls run one after another and none is hoisted."""
    vm = TPU_V5E.vmem_bytes

    def call(x, w, b):
        if kind == "q16":
            return matmul_q16_pallas(x, w, b, block=block, relu=True,
                                     vmem_limit_bytes=vm)
        return matmul_fp_pallas(x, w, b, block=block, relu=True,
                                vmem_limit_bytes=vm)

    def run(x, w, b):
        def body(_, x):
            y = call(x, w, b)
            return jnp.where(y[0, 0] < 0, x + 1, x)

        return jax.lax.fori_loop(0, reps, body, x)

    return jax.jit(run)


def operands(kind, m, k, n, key):
    kx, kw, kb = jax.random.split(key, 3)
    if kind == "q16":
        r = lambda kk, s: jax.random.randint(kk, s, -2**12, 2**12, jnp.int16)
    else:
        r = lambda kk, s: jax.random.normal(kk, s, jnp.float32)
    return r(kx, (m, k)), r(kw, (k, n)), r(kb, (n,))


def sweep(pts, reps=20, repeats=3, workers=8):
    """One row per point: compile (in parallel), then time on the device."""
    args = {}
    for kind, layer, m, k, n, bk, bn in pts:
        if (kind, m, k, n) not in args:
            args[(kind, m, k, n)] = operands(kind, m, k, n, jax.random.PRNGKey(k + n + m))

    def compile_one(p):
        kind, layer, m, k, n, bk, bn = p
        f = _looped(kind, MatmulBlock(8, bn, bk), reps)
        try:
            return p, f.lower(*args[(kind, m, k, n)]).compile(), None
        except Exception as e:  # the chip's compiler refuses the tile
            return p, None, str(e).splitlines()[0][:200]

    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        compiled = list(ex.map(compile_one, pts))
    rows = []
    for p, fn, err in compiled:
        kind, layer, m, k, n, bk, bn = p
        steps = ceil_div(n, bn) * ceil_div(k, bk)
        row = dict(kind=kind, layer=layer, m=m, k=k, n=n, bk=bk, bn=bn,
                   steps=steps, tile_elems=bk * bn)
        if fn is None:
            row["error"] = err
        else:
            a = args[(kind, m, k, n)]
            jax.block_until_ready(fn(*a))
            best = float("inf")
            for _ in range(repeats):
                t = time.perf_counter()
                jax.block_until_ready(fn(*a))
                best = min(best, (time.perf_counter() - t) / reps)
            row.update(us_per_call=best * 1e6, us_per_step=best * 1e6 / steps)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="")
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU (found {dev.platform}): the sweep times compiled kernels",
              file=sys.stderr)
        return 1
    rows = sweep(points(), reps=a.reps)
    if a.out:
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
