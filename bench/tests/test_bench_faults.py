"""A run with the timed path broken underneath reads ``correct: false``.

Each test drives the whole of a run but the look for a chip, on the CPU
at a tiny size, once sound and once for each fault the cell can have: an
answer altered where it is produced; half of the batch left out (the
other half answered twice); the halo exchange between chips left out;
and the control (the next precision down in the program's place)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from bench_tiny import REPO, config, make_root, traffic

from bench import control
from bench import run as R

SEED = 2**31 + 99


def _cell(tmp_path, numerics, batch, in_flight=1, spatial=1, chips=1):
    name = f"tiny-{numerics}.t{batch}s{spatial}"
    root = make_root(tmp_path, {name: (config(f"tiny-{numerics}", numerics), f"t{batch}s{spatial}",
                                       traffic(batch, in_flight, spatial), chips)})
    return R.load_cell(name, root)


def _run(cell, fault=None, devices=None):
    return R.run_cell(cell, SEED, 0.3, False, devices or jax.devices("cpu")[:1],
                      fault=fault, log=lambda _: None)


def altered_answer(forward):
    return lambda p, x: forward(p, x).at[0, 0].add(1.0)


def half_batch(forward):
    def run(p, x):
        half = x[: x.shape[0] // 2]
        return forward(p, jnp.concatenate([half, half]))
    return run


@pytest.mark.parametrize("numerics,batch", [("q16", 1), ("f32", 4)])
def test_an_altered_answer_is_not_correct(tmp_path, numerics, batch):
    cell = _cell(tmp_path, numerics, batch, in_flight=min(batch, 2))
    assert _run(cell)["correct"]
    bad = _run(cell, altered_answer)
    name = cell.cfg["check"]["name"]
    assert not bad["correct"] and bad["checks"][name]["value"] > bad["checks"][name]["limit"]


@pytest.mark.parametrize("numerics", ["q16", "f32"])
def test_half_of_the_batch_left_out_is_not_correct(tmp_path, numerics):
    cell = _cell(tmp_path, numerics, 4, in_flight=2)
    assert not _run(cell, half_batch)["correct"]


@pytest.mark.parametrize("numerics", ["q16", "f32"])
def test_the_control_is_not_correct(tmp_path, numerics):
    cell = _cell(tmp_path, numerics, 2)
    res = control.control_run(cell, SEED, 0.3, jax.devices("cpu")[:1], log=lambda _: None)
    name = cell.cfg["check"]["name"]
    assert not res["correct"] and res["checks"][name]["value"] > res["checks"][name]["limit"]


FOUR_CHIPS = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [{bench_tests!r}]
    import jax
    import jax.numpy as jnp
    from bench_tiny import config, make_root, traffic
    from bench import run as R
    from repro.parallel import sharding as sh

    root = make_root(Path({tmp!r}), {{"tiny-q16.s4": (config("tiny-q16", "q16"), "s4",
                                                     traffic(1, 1, 4), 4)}})
    cell = R.load_cell("tiny-q16.s4", root)
    devices = jax.devices()[:4]
    sound = R.run_cell(cell, {seed}, 0.3, False, devices, log=lambda _: None)
    exchange = sh.halo_exchange

    def no_exchange(v, hs):
        # the windows as the exchange builds them, with every row that
        # would have come from a neighbouring slab left at zero
        out = exchange(v, hs)
        rows = jnp.arange(hs.win)[None, :] + jnp.asarray(hs.offsets)[:, None]
        own = (rows >= hs.up) & (rows < hs.up + hs.lx)
        return jnp.where(own[:, None, :, None, None], out, jnp.zeros_like(out))

    sh.halo_exchange = no_exchange
    broken = R.run_cell(cell, {seed}, 0.3, False, devices, log=lambda _: None)
    print(json.dumps([sound["correct"], broken["correct"], broken["checks"]]))
""")


def test_the_exchange_between_chips_left_out_is_not_correct(tmp_path):
    code = FOUR_CHIPS.format(bench_tests=str(REPO / "bench" / "tests"), tmp=str(tmp_path),
                             seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    sound, broken, checks = __import__("json").loads(out.stdout.strip().splitlines()[-1])
    assert sound is True
    assert broken is False and checks["logits_differing"]["value"] > 0
