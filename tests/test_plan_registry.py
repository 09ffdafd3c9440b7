"""PlanRegistry: persisted plan store, measured-time autotune, and
sharding-aware local-shape planning (DESIGN.md §6).

Covers the acceptance criteria: a save → clear → load cycle reproduces
bit-identical plans (blocks, conv tiles, no-fit sentinels) with zero DSE
searches afterwards; corrupted / version-mismatched stores are rejected
cleanly; a warm serve session performs zero grid searches; and the same
logical GEMM planned under a mesh vs a single device yields local-shape
plans whose executed outputs match the unsharded reference.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import dse
from repro.core.engine import (
    PLAN_STORE_ENV,
    Engine,
    PlanCache,
    PlanRegistry,
    PlanStoreError,
    PrecisionChoice,
    load_plan_store,
    plan_cache_for,
    plan_store_stats,
    register_plan_store,
    reset_plan_caches,
    save_plan_store,
    warm_start_plan_store,
)
from repro.core.quantization import Q2_6, Q2_14
from repro.core.template import TemplateConfig, default_template
from repro.core.tiling import TPU_V5E

# Small enough that *no* direct config fits the (1, 64, 64, 32) x (3, 3, 32,
# 64) layer below: since the DMA-halo regime (ISSUE 8) can shrink the input
# window to a few rows x cols, the floor is the double-buffered tau=8 weight
# slab (9*32*8*4*2 = 18 KiB) plus the minimal window/accumulator — ~21 KiB.
TINY_HW = dataclasses.replace(TPU_V5E, vmem_bytes=16 * 1024)


def _populated_registry():
    """A registry holding a GEMM block, a direct conv tile, and — via a
    tiny-VMEM spec — a cached no-fit sentinel plus the fallback GEMM block."""
    reg = PlanRegistry()
    eng = Engine(TemplateConfig(backend="pallas"), plan_cache=reg)
    g = eng.plan_gemm(256, 512, 256)
    c = eng.plan_conv((1, 32, 32, 8), (3, 3, 8, 16), stride=1, padding=1)
    tiny = Engine(
        TemplateConfig(backend="pallas", hw=TINY_HW), plan_cache=reg
    )
    c_nofit = tiny.plan_conv((1, 64, 64, 32), (3, 3, 32, 64))
    assert c.route == "direct" and c_nofit.route == "im2col"
    return reg, (g, c, c_nofit)


def _forbid_searches(monkeypatch):
    def boom(*a, **kw):  # pragma: no cover - only fires on regression
        raise AssertionError("DSE grid search ran against a warm registry")

    monkeypatch.setattr(dse, "default_block_for", boom)
    monkeypatch.setattr(dse, "default_conv_tile_for", boom)


# ---------------------------------------------------------------------------
# serialization round-trip
# ---------------------------------------------------------------------------


def test_round_trip_bit_identical(tmp_path, monkeypatch):
    """save → clear → load reproduces every plan without a single search."""
    reg, (g, c, c_nofit) = _populated_registry()
    path = str(tmp_path / "store.json")
    reg.save(path)
    doc = reg.to_doc()

    loaded = PlanRegistry()
    n = loaded.load(path)
    assert n == len(reg) > 0
    assert loaded.to_doc() == doc, "round-trip must be bit-identical"
    assert loaded.misses == 0 and loaded.hits == 0, "loads are not lookups"

    _forbid_searches(monkeypatch)
    eng = Engine(TemplateConfig(backend="pallas"), plan_cache=loaded)
    assert eng.plan_gemm(256, 512, 256) == g
    assert eng.plan_conv((1, 32, 32, 8), (3, 3, 8, 16), stride=1, padding=1) == c
    tiny = Engine(
        TemplateConfig(backend="pallas", hw=TINY_HW), plan_cache=loaded
    )
    assert tiny.plan_conv((1, 64, 64, 32), (3, 3, 32, 64)) == c_nofit
    assert loaded.misses == 0


def test_no_fit_sentinel_round_trips(tmp_path):
    reg, _ = _populated_registry()
    assert None in reg._conv_tiles.values(), "test premise: a no-fit entry exists"
    path = str(tmp_path / "store.json")
    reg.save(path)
    loaded = PlanRegistry()
    loaded.load(path)
    assert None in loaded._conv_tiles.values()
    assert set(loaded._conv_tiles) == set(reg._conv_tiles)


def test_store_is_versioned_json(tmp_path):
    reg, _ = _populated_registry()
    path = str(tmp_path / "store.json")
    reg.save(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["format"] == "repro-plan-store"
    assert doc["version"] == 4
    assert doc["specs"] and doc["gemm"] and doc["conv"]
    assert "precision" in doc
    # every entry carries provenance
    assert all(e["source"] in ("analytic", "measured") for e in doc["gemm"])
    assert all(e["source"] in ("analytic", "measured") for e in doc["conv"])


# ---------------------------------------------------------------------------
# precision pins: v3 round-trip + lenient v2/v1 migration (DESIGN.md §11)
# ---------------------------------------------------------------------------


def test_precision_pin_round_trip(tmp_path):
    """Pinned per-layer grids (fmt + drift + provenance) survive
    save -> clear -> load bit-identically, and a warm replay serves them as
    hits with zero misses."""
    reg, _ = _populated_registry()
    reg.pin_precision("lenet", "conv0", Q2_6, drift=1.0)
    reg.pin_precision("lenet", "fc2", Q2_14, drift=0.97)
    path = str(tmp_path / "store.json")
    reg.save(path)

    loaded = PlanRegistry()
    n = loaded.load(path)
    assert n == len(reg) > 0
    assert loaded.misses == 0 and loaded.hits == 0, "loads are not lookups"
    assert loaded.to_doc() == reg.to_doc(), "round-trip must be bit-identical"
    assert loaded.precision_plan("lenet") == {"conv0": Q2_6, "fc2": Q2_14}
    assert loaded.precision_for("lenet", "conv0") == PrecisionChoice(Q2_6, 1.0)
    assert loaded.hits == 1 and loaded.misses == 0, \
        "warm precision replay is hits-only (REPRO_PLAN_ASSERT_WARM contract)"


def test_precision_miss_charged_by_pin_not_lookup():
    """An absent pin is not a miss (the sweep itself charges it via
    pin_precision(searched=True)); replayed pins charge nothing."""
    reg = PlanRegistry()
    assert reg.precision_for("net", "l0") is None
    assert reg.misses == 0 and reg.hits == 0
    reg.pin_precision("net", "l0", Q2_6, drift=0.995)
    assert reg.misses == 1
    reg.pin_precision("net", "l1", Q2_14, searched=False)
    assert reg.misses == 1


def test_v2_store_migrates_gemm_and_conv_without_precision(tmp_path):
    """A v2 (pre-precision) store loads leniently: gemm + conv entries merge
    unchanged, precision pins simply don't exist — even a stray precision
    section in a v2 doc is ignored rather than trusted."""
    reg, (g, c, c_nofit) = _populated_registry()
    reg.pin_precision("lenet", "conv0", Q2_6, drift=1.0)
    doc = reg.to_doc()
    doc["version"] = 2  # keep the (stray) precision section on purpose
    for e in doc["conv"]:
        e["key"].insert(8, 1)  # v2 keys carry the layer's stride
    path = tmp_path / "v2.json"
    path.write_text(json.dumps(doc))

    loaded = PlanRegistry()
    n = loaded.load(str(path))
    assert n == len(reg._blocks) + len(reg._conv_tiles)
    assert loaded._blocks == reg._blocks
    assert loaded._conv_tiles == reg._conv_tiles
    assert loaded.precision_plan("lenet") == {}
    # the migrated plans still serve without a search
    eng = Engine(TemplateConfig(backend="pallas"),
                 plan_cache=loaded)
    assert eng.plan_gemm(256, 512, 256) == g
    assert eng.plan_conv((1, 32, 32, 8), (3, 3, 8, 16), stride=1, padding=1) == c
    assert loaded.misses == 0


def test_v3_store_drops_its_strided_conv_entries(tmp_path):
    """A v3 store keys a conv by the layer's stride: its stride-1 entries
    load without the stride, and a strided entry (planned over strided
    windows) is dropped, so that layer re-plans over its space-to-depth
    input."""
    reg, (_, c, _) = _populated_registry()
    doc = reg.to_doc()
    doc["version"] = 3
    for e in doc["conv"]:
        e["key"].insert(8, 1)
    strided = json.loads(json.dumps(doc["conv"][0]))
    strided["key"][8] = 2
    doc["conv"].append(strided)
    path = tmp_path / "v3.json"
    path.write_text(json.dumps(doc))

    loaded = PlanRegistry()
    assert loaded.load(str(path)) == len(reg._blocks) + len(reg._conv_tiles)
    assert loaded._conv_tiles == reg._conv_tiles
    eng = Engine(TemplateConfig(backend="pallas"), plan_cache=loaded)
    assert eng.plan_conv((1, 32, 32, 8), (3, 3, 8, 16), stride=1, padding=1) == c
    assert loaded.misses == 0
    eng.plan_conv((1, 32, 32, 8), (3, 3, 8, 16), stride=2, padding=1)
    assert loaded.misses == 1


def test_v1_store_migrates_gemm_only(tmp_path):
    """v1 keeps gemm entries; its pre-column-tiling conv docs and (stray)
    precision pins are dropped so those layers re-plan/re-sweep."""
    reg, _ = _populated_registry()
    reg.pin_precision("lenet", "conv0", Q2_6, drift=1.0)
    doc = reg.to_doc()
    doc["version"] = 1
    path = tmp_path / "v1.json"
    path.write_text(json.dumps(doc))

    loaded = PlanRegistry()
    n = loaded.load(str(path))
    assert n == len(reg._blocks)
    assert loaded._blocks == reg._blocks
    assert not loaded._conv_tiles
    assert loaded.precision_plan("lenet") == {}


def test_bad_precision_entry_rejected(tmp_path):
    """A v3 store with a malformed precision entry is rejected loudly and
    leaves nothing half-merged."""
    reg, _ = _populated_registry()
    reg.pin_precision("lenet", "conv0", Q2_6, drift=1.0)
    doc = reg.to_doc()
    doc["precision"][0]["fmt"] = [2, 6]  # missing total_bits
    path = tmp_path / "badprec.json"
    path.write_text(json.dumps(doc))
    fresh = PlanRegistry()
    with pytest.raises(PlanStoreError, match="precision"):
        fresh.load(str(path))
    assert len(fresh) == 0


# ---------------------------------------------------------------------------
# rejection of bad stores
# ---------------------------------------------------------------------------


def test_corrupted_store_rejected(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("{this is not json")
    with pytest.raises(PlanStoreError):
        PlanRegistry().load(str(path))


def test_version_mismatch_rejected(tmp_path):
    path = tmp_path / "old.json"
    path.write_text(json.dumps({
        "format": "repro-plan-store", "version": 999,
        "specs": [], "gemm": [], "conv": [],
    }))
    with pytest.raises(PlanStoreError, match="version"):
        PlanRegistry().load(str(path))


def test_wrong_format_rejected(tmp_path):
    path = tmp_path / "other.json"
    path.write_text(json.dumps({"format": "something-else", "version": 1}))
    with pytest.raises(PlanStoreError, match="format"):
        PlanRegistry().load(str(path))


def test_rejected_store_leaves_registry_untouched(tmp_path):
    """A store whose tail is corrupt must not half-merge its valid head."""
    reg, _ = _populated_registry()
    path = tmp_path / "half.json"
    doc = reg.to_doc()
    doc["conv"].append({"spec": 99, "key": [1] * 10, "choice": None})  # bad spec
    path.write_text(json.dumps(doc))
    fresh = PlanRegistry()
    with pytest.raises(PlanStoreError):
        fresh.load(str(path))
    assert len(fresh) == 0, "valid gemm entries must not leak from a rejected store"


@pytest.mark.parametrize("entry,n_specs", [
    ({"spec": 0, "key": [1, 2, 3], "block": [8, 128, 128]}, 0),  # spec missing
    ({"spec": -1, "key": [1, 2, 3], "block": [8, 128, 128]}, 1),  # negative wrap
    ({"spec": 0, "key": [1, 2], "block": [8, 128, 128]}, 1),  # short key
    ({"spec": 0, "key": [1, 2, 3], "block": [512]}, 1),  # short block
])
def test_structurally_broken_store_rejected(tmp_path, entry, n_specs):
    path = tmp_path / "broken.json"
    path.write_text(json.dumps({
        "format": "repro-plan-store", "version": 1,
        "specs": [dataclasses.asdict(TPU_V5E)] * n_specs,
        "gemm": [entry], "conv": [],
    }))
    with pytest.raises(PlanStoreError):
        PlanRegistry().load(str(path))


def test_missing_file_rejected_unless_missing_ok(tmp_path):
    with pytest.raises(PlanStoreError):
        PlanRegistry().load(str(tmp_path / "nope.json"))
    assert load_plan_store(str(tmp_path / "nope.json"), missing_ok=True) == 0


# ---------------------------------------------------------------------------
# measured-time autotune overwrite
# ---------------------------------------------------------------------------


def _time_here(monkeypatch):
    """Let measure_and_pin time the interpreted kernels of this CPU process:
    the pin mechanism is under test here, not the chip's timings."""
    import repro.core.engine as engine_mod

    monkeypatch.setattr(engine_mod, "_timing_device", lambda: None)


def test_measure_and_pin_refuses_the_interpreter():
    with pytest.raises(RuntimeError, match="TPU"):
        PlanRegistry().measure_and_pin(128, 256, 128, reps=1)


def test_measure_and_pin_overwrites_with_provenance(tmp_path, monkeypatch):
    _time_here(monkeypatch)
    reg = PlanRegistry()
    analytic = reg.block_for(128, 256, 128)
    assert reg.source_for(128, 256, 128) == "analytic"
    pinned = reg.measure_and_pin(128, 256, 128, reps=1)
    assert reg.source_for(128, 256, 128) == "measured"
    assert reg.stats()["measured"] == 1
    # the pinned block is served on the next lookup with no new search
    misses = reg.misses
    assert reg.block_for(128, 256, 128) == pinned
    assert reg.misses == misses

    # provenance survives the store round-trip
    path = str(tmp_path / "store.json")
    reg.save(path)
    loaded = PlanRegistry()
    loaded.load(path)
    assert loaded.source_for(128, 256, 128) == "measured"
    assert loaded.block_for(128, 256, 128) == pinned
    del analytic


def test_measure_and_pin_picks_from_candidates(monkeypatch):
    from repro.core.tiling import MatmulBlock

    _time_here(monkeypatch)
    reg = PlanRegistry()
    cands = [MatmulBlock(128, 128, 128), MatmulBlock(256, 128, 128)]
    best = reg.measure_and_pin(256, 128, 128, candidates=cands, reps=1)
    assert best in cands


def test_merge_never_downgrades_measured_pins(tmp_path, monkeypatch):
    """A concurrent analytic writer must not clobber a measured pin — in
    merge_from, in load, and through the shared-store save cycle."""
    reset_plan_caches()
    _time_here(monkeypatch)
    path = str(tmp_path / "shared.json")
    # writer A: measured pin, saved to the shared store
    a = PlanRegistry()
    pinned = a.measure_and_pin(128, 256, 128, reps=1)
    a.save(path)
    # writer B: plans the same shape analytically and saves to the same store
    monkeypatch.setenv(PLAN_STORE_ENV, path)
    plan_cache_for(TPU_V5E).block_for(128, 256, 128, TPU_V5E)
    save_plan_store()
    # the measured pin survives on disk...
    check = PlanRegistry()
    check.load(path)
    assert check.source_for(128, 256, 128) == "measured"
    assert check.block_for(128, 256, 128) == pinned
    # ...and loading an analytic store over a live measured pin keeps the pin
    b = PlanRegistry()
    b.block_for(128, 256, 128)
    analytic_doc = b.to_doc()
    a.merge_doc(analytic_doc)
    assert a.source_for(128, 256, 128) == "measured"
    reset_plan_caches()


def test_cell_gemm_plans_pallas_template_warms_registry():
    """step_and_specs threads tpl → cell_gemm_plans: a Pallas template pins
    real blocks for the local shard shapes into the registry."""
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeSpec
    from repro.launch.steps import cell_gemm_plans
    from repro.parallel.sharding import TRAIN_RULES

    reset_plan_caches()
    cfg = reduced(get_config("qwen2-0.5b"))
    shape = ShapeSpec("t", 64, 8, "train")
    tpl = default_template("pallas")
    plans = cell_gemm_plans(cfg, shape, _StubMesh(), TRAIN_RULES, tpl)
    assert all(p.block is not None for p in plans.values())
    assert plan_cache_for(TPU_V5E).stats()["gemm_blocks"] > 0
    reset_plan_caches()


def test_engine_measure_and_pin_uses_engine_spec(monkeypatch):
    _time_here(monkeypatch)
    reg = PlanRegistry()
    eng = Engine(TemplateConfig(backend="pallas"), plan_cache=reg)
    blk = eng.measure_and_pin(128, 128, 128, reps=1)
    assert reg.source_for(128, 128, 128, TPU_V5E) == "measured"
    assert eng.plan_gemm(128, 128, 128).block == blk


# ---------------------------------------------------------------------------
# global store: env warm start, stats, registration dedupe
# ---------------------------------------------------------------------------


def test_global_store_env_round_trip(tmp_path, monkeypatch):
    reset_plan_caches()
    path = str(tmp_path / "global.json")
    monkeypatch.setenv(PLAN_STORE_ENV, path)
    plan_cache_for(TPU_V5E).block_for(64, 128, 64, TPU_V5E)
    plan_cache_for(TINY_HW).block_for(32, 128, 32, TINY_HW)  # 2nd spec, same file
    save_plan_store()
    reset_plan_caches()
    assert plan_store_stats()["gemm_blocks"] == 0
    ret_path, n = warm_start_plan_store()
    assert ret_path == path and n == 2
    st = plan_store_stats()
    assert st["gemm_blocks"] == 2 and st["misses"] == 0
    # both specs were re-distributed to their own registries
    assert len(plan_cache_for(TPU_V5E)) == 1
    assert len(plan_cache_for(TINY_HW)) == 1
    reset_plan_caches()


def test_warm_start_no_env_is_noop(monkeypatch):
    monkeypatch.delenv(PLAN_STORE_ENV, raising=False)
    assert warm_start_plan_store() == (None, 0)
    with pytest.raises(ValueError):
        save_plan_store()


def test_warm_start_tolerates_unusable_store(tmp_path):
    """A corrupt/version-mismatched store must not be a startup SPOF: the
    drivers cold-start with a warning instead of crashing."""
    path = tmp_path / "bad.json"
    path.write_text("{definitely not json")
    with pytest.warns(UserWarning, match="unusable plan store"):
        ret_path, n = warm_start_plan_store(str(path))
    assert ret_path == str(path) and n == 0
    # strict loading still rejects it
    with pytest.raises(PlanStoreError):
        load_plan_store(str(path))


def test_save_plan_store_merges_existing_file(tmp_path, monkeypatch):
    """Concurrent writers sharing one store append, not overwrite: saving
    merges the on-disk entries with this process's registries."""
    reset_plan_caches()
    path = str(tmp_path / "shared.json")
    # writer A persists one shape
    other = PlanRegistry()
    other.block_for(512, 512, 512, TPU_V5E)
    other.save(path)
    # writer B (this process) knows a different shape and saves to same file
    plan_cache_for(TPU_V5E).block_for(64, 128, 64, TPU_V5E)
    save_plan_store(path)
    reset_plan_caches()
    assert load_plan_store(path) == 2, "both writers' entries must survive"
    reg = plan_cache_for(TPU_V5E)
    assert (512, 512, 512, TPU_V5E) in reg._blocks
    assert (64, 128, 64, TPU_V5E) in reg._blocks
    reset_plan_caches()


def test_stats_reports_gemm_and_conv_separately():
    reg, _ = _populated_registry()
    st = reg.stats()
    assert st["gemm_blocks"] == 2  # direct gemm + im2col fallback block
    assert st["conv_tiles"] == 2  # direct tile + no-fit sentinel
    assert len(reg) == st["gemm_blocks"] + st["conv_tiles"]
    assert st["misses"] == 4 and st["hits"] == 0


def test_register_plan_store_dedupes_by_identity():
    from repro.core import engine as E

    store: dict = {}
    before = len(E._EXTRA_PLAN_STORES)
    register_plan_store(store)
    register_plan_store(store)  # re-registration (e.g. module re-import)
    register_plan_store(store)
    assert len(E._EXTRA_PLAN_STORES) == before + 1
    # remove by identity — list.remove would drop the first *equal* (empty) dict
    E._EXTRA_PLAN_STORES[:] = [s for s in E._EXTRA_PLAN_STORES if s is not store]


# ---------------------------------------------------------------------------
# sharding-aware planning (local per-shard shapes)
# ---------------------------------------------------------------------------


class _StubMesh:
    """Duck-typed mesh for pure local-shape math (no devices needed)."""

    axis_names = ("data", "model")
    shape = {"data": 4, "model": 2}


def test_local_gemm_shape_default_partition():
    from repro.parallel.sharding import local_gemm_shape

    assert local_gemm_shape(256, 512, 128, mesh=_StubMesh()) == (64, 256, 128)


def test_local_dim_rules():
    from repro.parallel.sharding import axis_size, local_dim

    mesh = _StubMesh()
    assert axis_size(mesh, ("pod", "data")) == 4  # missing "pod" dropped
    assert local_dim(256, mesh, "data") == 64
    # non-divisible stays replicated: the jit-boundary shardings DROP a
    # mapping they can't pad, so the planner must plan the full dim — one
    # rule on both sides (was ceil-div, which planned shapes that never ran)
    assert local_dim(257, mesh, "data") == 257
    assert local_dim(3, mesh, "data") == 3  # smaller than axis: replicated
    assert local_dim(256, mesh, None) == 256


def test_local_conv_shapes_batch_and_cout():
    from repro.parallel.sharding import local_conv_shapes

    x, w = local_conv_shapes((8, 32, 32, 3), (3, 3, 3, 64), mesh=_StubMesh())
    assert x == (2, 32, 32, 3)  # batch / data(4)
    assert w == (3, 3, 3, 32)  # cout / model(2)


def test_plan_gemm_mesh_vs_single_from_one_registry():
    reg = PlanRegistry()
    eng = Engine(TemplateConfig(backend="pallas"), plan_cache=reg)
    single = eng.plan_gemm(256, 512, 128)
    local = eng.plan_gemm(256, 512, 128, mesh=_StubMesh())
    assert single.logical == () and (single.m, single.n, single.k) == (256, 512, 128)
    assert local.logical == (256, 512, 128)
    assert (local.m, local.n, local.k) == (64, 256, 128)
    assert single != local, "mesh and single-chip plans must differ"
    assert reg.stats()["gemm_blocks"] == 2, "one registry holds both"


def test_plan_cnn_mesh_local_shapes():
    from jax.sharding import PartitionSpec as P

    from repro.models.cnn import LENET, plan_cnn

    reset_plan_caches()
    tpl = default_template("pallas")
    mesh = _StubMesh()
    p_single = plan_cnn(tpl, LENET, (8, 32, 32, 1))
    p_mesh = plan_cnn(tpl, LENET, (8, 32, 32, 1), mesh=mesh,
                      partition=P("data", "model"))
    # conv GEMM M scales with the local batch (8 -> 2)
    assert p_mesh.convs[0].gemm[0] == p_single.convs[0].gemm[0] // 4
    # FC N is model-sharded (120 -> 60), K stays the gathered full width
    assert p_mesh.fcs[0].n == p_single.fcs[0].n // 2
    assert p_mesh.fcs[0].k == p_single.fcs[0].k
    # memoized separately per topology
    assert plan_cnn(tpl, LENET, (8, 32, 32, 1), mesh=mesh,
                    partition=P("data", "model")) is p_mesh
    assert plan_cnn(tpl, LENET, (8, 32, 32, 1)) is p_single
    reset_plan_caches()


def test_cell_gemm_plans_thread_rules():
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeSpec
    from repro.launch.steps import cell_gemm_plans
    from repro.parallel.sharding import TRAIN_RULES

    cfg = reduced(get_config("qwen2-0.5b"))
    shape = ShapeSpec("t", 64, 8, "train")
    plans = cell_gemm_plans(cfg, shape, _StubMesh(), TRAIN_RULES)
    assert set(plans) == {"qkv", "attn_out", "mlp_up", "mlp_down", "lm_head"}
    m_tokens = shape.tokens
    # M sharded over ("pod","data") -> data(4); N of mlp_up over model(2)
    assert plans["mlp_up"].m == m_tokens // 4
    assert plans["mlp_up"].n == cfg.d_ff // 2
    assert plans["mlp_up"].logical == (m_tokens, cfg.d_ff, cfg.d_model)
    # the down-projection contracts over the model-sharded ff dim
    assert plans["mlp_down"].k == cfg.d_ff // 2
    assert plans["mlp_down"].n == cfg.d_model


# ---------------------------------------------------------------------------
# acceptance: mesh vs single device — local plans, executed outputs match
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.engine import Engine
    from repro.core.template import TemplateConfig
    from repro.launch.mesh import make_test_mesh, gemm_partition

    mesh = make_test_mesh()  # (2, 2) ("data", "model")
    eng = Engine(TemplateConfig(backend="pallas"))
    m, n, k = 256, 512, 128
    p_single = eng.plan_gemm(m, n, k)
    p_mesh = eng.plan_gemm(m, n, k, mesh=mesh)

    rng = np.random.default_rng(0)
    X = rng.standard_normal((m, k)).astype(np.float32) * 0.3
    W = rng.standard_normal((k, n)).astype(np.float32) * 0.3
    x = jax.device_put(jnp.asarray(X), NamedSharding(mesh, P("data", None)))
    w = jax.device_put(jnp.asarray(W), NamedSharding(mesh, P(None, "model")))
    out = np.asarray(jax.jit(jnp.dot)(x, w))
    ref = X @ W
    print(json.dumps({
        "single": [p_single.m, p_single.n, p_single.k],
        "local": [p_mesh.m, p_mesh.n, p_mesh.k],
        "logical": list(p_mesh.logical),
        "x_shard": list(x.addressable_shards[0].data.shape),
        "w_shard": list(w.addressable_shards[0].data.shape),
        "max_err": float(np.abs(out - ref).max()),
        "blocks_differ": p_single.block != p_mesh.block,
    }))
    """
)


def test_mesh_local_plans_match_executed_shards():
    """Under make_test_mesh() the plan's (m, n, k) must equal the shapes the
    shards actually execute, and the sharded product must match the
    unsharded reference (runs in a subprocess: needs 8 host devices)."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert out.returncode == 0, f"mesh-plan subprocess failed:\n{out.stderr[-3000:]}"
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["single"] == [256, 512, 128]
    assert rec["local"] == [128, 256, 128]
    assert rec["logical"] == [256, 512, 128]
    # the planned local shape IS the executed shard shape
    assert rec["x_shard"] == [rec["local"][0], rec["local"][2]]
    assert rec["w_shard"] == [rec["local"][2], rec["local"][1]]
    assert rec["max_err"] < 1e-3


# ---------------------------------------------------------------------------
# acceptance: warm serve session performs zero DSE searches
# ---------------------------------------------------------------------------


def test_serve_warm_start_zero_searches(tmp_path, monkeypatch):
    from repro.launch import serve

    monkeypatch.delenv(PLAN_STORE_ENV, raising=False)
    reset_plan_caches()
    store = str(tmp_path / "serve_store.json")
    args = ["--backend", "pallas", "--prompts", "1", "--prompt-len", "8",
            "--gen", "2", "--plan-store", store]
    serve.main(args)  # cold: populates + saves the store
    assert os.path.exists(store)
    cold_misses = plan_cache_for(TPU_V5E).misses
    assert cold_misses > 0, "cold serve must have planned something"

    reset_plan_caches()  # simulate a fresh serving process
    serve.main(args)  # warm: loads the store
    pc = plan_cache_for(TPU_V5E)
    assert pc.misses == 0, "warm serve must perform zero DSE grid searches"
    assert pc.hits > 0
    reset_plan_caches()
