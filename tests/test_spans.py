"""repro.runtime.spans: the host side of a layer span (nesting, self time,
the bounded store), its profiler annotation, and the set-up spans of the
CNN path with the counts read where the work happens."""
import glob

import jax
import jax.numpy as jnp
import pytest

from repro.core.template import default_template
from repro.models import cnn as C
from repro.runtime import spans as S


def _named(name):
    return [s for s in S.spans() if s.name == name]


def test_spans_nest_and_name_their_parent():
    with S.layer("nest-outer", k=1) as attrs:
        with S.layer("nest-a"):
            pass
        with S.layer("nest-b"):
            with S.layer("nest-c"):
                pass
        attrs["late"] = 2
    (outer,), (a,), (b,), (c,) = (_named(n) for n in ("nest-outer", "nest-a", "nest-b", "nest-c"))
    assert outer.parent is None and outer.attrs == {"k": 1, "late": 2}
    assert a.parent == b.parent == outer.index and c.parent == b.index
    assert outer.index < a.index < b.index < c.index
    assert outer.start_ns <= a.start_ns <= a.end_ns <= b.start_ns <= c.start_ns
    assert c.end_ns <= b.end_ns <= outer.end_ns


def test_a_span_closes_when_its_block_raises():
    with pytest.raises(ValueError):
        with S.layer("raises"):
            raise ValueError("boom")
    (sp,) = _named("raises")
    assert sp.end_ns >= sp.start_ns


def test_self_time_is_duration_less_the_children():
    outer = S.Span(1, "outer", 0, 100, None, {})
    among = [outer, S.Span(2, "a", 10, 30, 1, {}), S.Span(3, "b", 40, 90, 1, {}),
             S.Span(4, "c", 50, 60, 3, {})]
    assert S.self_ns(outer, among) == 100 - 20 - 50
    assert S.self_ns(among[2], among) == 50 - 10
    assert S.self_ns(among[3], among) == 10
    with S.layer("self-outer"):
        with S.layer("self-inner"):
            pass
    (o,), (i,) = _named("self-outer"), _named("self-inner")
    assert S.self_ns(o) == o.dur_ns - i.dur_ns >= 0


def test_the_store_keeps_the_newest_spans():
    for _ in range(S.MAX_SPANS + 5):
        with S.layer("bound"):
            pass
    kept = S.spans()
    assert len(kept) == S.MAX_SPANS
    assert [s.name for s in kept] == ["bound"] * S.MAX_SPANS
    assert kept[-1].index - kept[0].index == S.MAX_SPANS - 1


def test_the_span_names_the_traced_operations():
    def f(x):
        with S.layer("conv3"):
            y = jnp.sin(x) * 2
        with S.layer("fc0"):
            return jnp.tanh(y)

    hlo = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    assert '/conv3/sin"' in hlo and '/fc0/tanh"' in hlo


def test_the_span_is_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with S.layer("span-on-the-trace"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events}
    assert "span-on-the-trace" in names


def test_plan_counts_dse_searches_cold_and_none_warm():
    # a geometry no other test plans, so the first call searches
    spec = C.CNNSpec("spans-probe", 20, 5, 7, convs=((24, 3, 1, 1, 2),), fcs=(40,))
    tpl = default_template("pallas")
    first = C.plan_cnn(tpl, spec, (3, 20, 20, 5))
    cold = _named("plan")[-1]
    again = C.plan_cnn(tpl, spec, (3, 20, 20, 5))
    warm = _named("plan")[-1]
    assert again is first and warm.index > cold.index
    assert cold.attrs["dse_searches"] > 0 and warm.attrs["dse_searches"] == 0


def test_plan_counts_skinny_gemms_of_the_fc_head():
    # VGG16 at batch 1 on emptied caches: its three FC GEMMs (M = 1) take
    # the skinny-M branch; the memoized second plan searches nothing
    from repro.core.engine import reset_plan_caches

    reset_plan_caches()
    tpl = default_template("q16")
    first = C.plan_cnn(tpl, C.VGG16, (1, 224, 224, 3))
    cold = _named("plan")[-1]
    again = C.plan_cnn(tpl, C.VGG16, (1, 224, 224, 3))
    warm = _named("plan")[-1]
    assert again is first and warm.index > cold.index
    assert cold.attrs["skinny_gemms"] == 3 and warm.attrs["skinny_gemms"] == 0
    assert [gp.block.bm for gp in first.fcs] == [8, 8, 8]


def test_set_up_spans_of_calibration_and_quantization():
    spec = C.CNNSpec("spans-q16", 16, 3, 10, convs=((8, 3, 1, 1, 2),), fcs=(16,))
    tpl = default_template("q16")
    params = C.init_cnn(jax.random.PRNGKey(0), spec)
    x = 3 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))  # off the Q2.14 grid
    policy = C.calibrate_cnn_policy(tpl, spec, params, x)
    cal = _named("calibrate")[-1]
    kids = [s for s in S.spans() if s.parent == cal.index]
    assert [s.name for s in kids] == ["quantize_params", "forward"]
    assert kids[0].attrs == {"built": 1} and kids[1].attrs == {"traced": False}
    C.quantize_cnn_params(tpl, spec, params, policy)
    C.quantize_cnn_params(tpl, spec, params, policy)
    assert [s.attrs["built"] for s in _named("quantize_params")[-2:]] == [1, 0]
