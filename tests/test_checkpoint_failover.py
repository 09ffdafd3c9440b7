"""Fault-tolerance: atomic checkpoints, restart loops, stragglers, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.checkpoint.manager import _MANIFEST
from repro.runtime import (
    FailureInjector,
    HeartbeatMonitor,
    SimulatedFailure,
    detect_stragglers,
    run_with_restarts,
)
from repro.runtime.failover import plan_elastic_remesh
from repro.launch.mesh import make_mesh


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "a": jax.random.normal(k, (4, 8)),
        "nested": {"b": jnp.arange(6, dtype=jnp.int32), "c": jnp.float32(3.5)},
        "list": (jnp.ones((2, 2)), jnp.zeros((3,))),
    }


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 7, t, extra={"note": "x"})
    assert latest_step(str(tmp_path)) == 7
    back = restore(str(tmp_path), 7, jax.tree.map(jnp.zeros_like, t))
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomicity_no_partial_checkpoints(tmp_path):
    """A crashed writer must leave no visible checkpoint."""
    t = _tree()
    import repro.checkpoint.manager as M

    orig = M.json.dump
    try:
        def boom(*a, **k):
            raise RuntimeError("crash mid-write")

        M.json.dump = boom
        with pytest.raises(RuntimeError):
            save(str(tmp_path), 3, t)
    finally:
        M.json.dump = orig
    assert latest_step(str(tmp_path)) is None
    # tmp dirs cleaned on the next successful save
    save(str(tmp_path), 4, t)
    leftovers = [d for d in os.listdir(tmp_path) if ".tmp" in d]
    assert leftovers == []
    assert latest_step(str(tmp_path)) == 4


def test_restore_shape_mismatch_rejected(tmp_path):
    save(str(tmp_path), 1, {"w": jnp.ones((4,))})
    with pytest.raises(ValueError):
        restore(str(tmp_path), 1, {"w": jnp.ones((5,))})


def test_manager_rotation(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        m.save(s, {"x": jnp.full((2,), s)})
    steps = sorted(
        int(d.split("_")[1]) for d in os.listdir(tmp_path) if d.startswith("step_")
    )
    assert steps == [3, 4]
    assert m.latest() == 4


def test_elastic_restore_different_rules(tmp_path):
    """Save unsharded, restore with explicit (single-device) shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save(str(tmp_path), 2, t)
    mesh = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P())}
    back = restore(str(tmp_path), 2, t, sh)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(t["w"]))


# ---------------------------------------------------------------------------
# restart loop
# ---------------------------------------------------------------------------


def test_run_with_restarts_resumes_exactly(tmp_path):
    """Injected failures must replay from the checkpoint with identical data."""
    state = {"acc": 0.0, "step": 0}
    ckpt = {}
    seen = []

    def step_fn(step):
        inj.check(step)
        seen.append(step)
        state["acc"] += float(step)

    def save_fn(step):
        ckpt[step] = dict(state, step=step)

    def restore_fn():
        if not ckpt:
            state.update(acc=0.0, step=0)
            return 0
        s = max(ckpt)
        state.update({k: v for k, v in ckpt[s].items() if k != "step"})
        return s

    inj = FailureInjector(fail_at_steps=[7, 13])
    stats = run_with_restarts(
        num_steps=20, step_fn=step_fn, save_fn=save_fn, restore_fn=restore_fn,
        checkpoint_every=5, max_failures=3,
    )
    assert stats["failures"] == 2
    assert stats["restarts"] == [5, 10]
    # restore discards replayed partial work: the final state is EXACTLY the
    # no-failure result even though some steps executed twice
    assert state["acc"] == sum(range(20))
    assert sorted(set(seen)) == list(range(20))
    replayed = [s for s in set(seen) if seen.count(s) == 2]
    assert sorted(replayed) == [5, 6, 10, 11, 12]


def test_run_with_restarts_gives_up_after_max():
    inj = FailureInjector(fail_at_steps=[1])

    def step_fn(step):
        if step == 1:
            raise SimulatedFailure("always")

    with pytest.raises(SimulatedFailure):
        run_with_restarts(
            num_steps=5, step_fn=step_fn, save_fn=lambda s: None,
            restore_fn=lambda: 0, max_failures=2,
        )


# ---------------------------------------------------------------------------
# heartbeats / stragglers / elasticity
# ---------------------------------------------------------------------------


def test_heartbeat_detects_dead_host():
    mon = HeartbeatMonitor(["h0", "h1", "h2"], timeout_steps=2)
    for step in range(5):
        mon.report("h0", step, 1.0)
        mon.report("h1", step, 1.0)
        if step < 2:
            mon.report("h2", step, 1.0)
    assert mon.dead_hosts(current_step=4) == ["h2"]


def test_straggler_detection_median_policy():
    times = {
        "h0": [1.0] * 5,
        "h1": [1.0] * 5,
        "h2": [1.0] * 5,
        "slow": [1.0, 1.0, 3.1, 3.2, 3.3],
    }
    assert detect_stragglers(times, factor=2.0, patience=3) == ["slow"]
    # a single slow step is not a straggler
    times["blip"] = [1.0, 1.0, 1.0, 3.5, 1.0]
    assert "blip" not in detect_stragglers(times, factor=2.0, patience=3)


def test_elastic_remesh_plan():
    plan = plan_elastic_remesh({"data": 16, "model": 16}, lost_hosts=4,
                               hosts_per_replica=4)
    assert plan is not None
    assert plan.new_shape == (15, 16)
    assert plan.dropped_axis == "data"
    with pytest.raises(SimulatedFailure):
        plan_elastic_remesh({"data": 1, "model": 16}, lost_hosts=8,
                            hosts_per_replica=4)


def test_end_to_end_train_restart(tmp_path):
    """The real training driver: loss decreases and failures do not corrupt."""
    from repro.launch.train import main

    stats, history = main([
        "--arch", "qwen2-0.5b", "--steps", "14", "--batch", "4", "--seq", "64",
        "--ckpt-every", "4", "--ckpt-dir", str(tmp_path), "--fail-at", "9",
        "--log-every", "100",
    ])
    assert stats["failures"] == 1
    assert stats["steps"] == 14
    assert history[-1] < history[0]  # learned something through the restart
