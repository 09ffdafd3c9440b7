"""The reduction of bench/layers.py (a device trace split by the program's
layer scopes) and the readers built on it, on a synthetic ``.xplane.pb``
and synthetic spans, and the traced branch of a tiny run on the CPU."""
import json
from types import SimpleNamespace

import jax
import pytest
from bench_tiny import REPO, config, make_root, traffic

from bench import layers as L
from bench import run as R
from bench import trace as tr

S = tr.Span
MS = 1e6
TPU0 = "/device:TPU:0"
PALLAS = "jit(fwd)/forward/{}/jit(conv2d_q16_pallas)/cond/branch_0_fun/conv_untiled/pallas_call"

#: one forward on one chip: (event name, op_name or None, duration in ms)
FORWARD = [
    ("%copy.2 = f32[8]{0:T(128)} copy(f32[8]{0} %x.1)", None, 1),
    ("%convert_fusion = s16[8]{0} fusion(f32[8]{0} %copy.2), kind=kLoop",
     "jit(fwd)/forward/quantize/convert_element_type", 2),
    ("%pad.3 = s16[10]{0} pad(s16[8]{0} %convert_fusion)", "jit(fwd)/forward/conv0/jit(_pad)/pad", 3),
    ("%conv_untiled.14 = s16[8]{0} custom-call(s16[10]{0} %pad.3)", PALLAS.format("conv0"), 10),
    ("%collective-permute-start.2 = (s16[1]{0}, s16[1]{0}) collective-permute-start(...)",
     "jit(fwd)/forward/conv1/shard_map/ppermute", 1),
    ("%collective-permute-done.2 = s16[1]{0} collective-permute-done(...)",
     "jit(fwd)/forward/conv1/shard_map/ppermute", 2),
    ("%conv_untiled.15 = s16[8]{0} custom-call(s16[8]{0} %conv_untiled.14)",
     PALLAS.format("conv1/shard_map"), 20),
    ("%reduce-window.1 = s16[4]{0} reduce-window(s16[8]{0} %conv_untiled.15)",
     "jit(fwd)/forward/pool1/reduce_window", 4),
    ("%all-gather.1 = s16[8]{0} all-gather(s16[4]{0} %reduce-window.1)",
     "jit(fwd)/forward/gather/all_gather", 5),
    ("%bitcast_fusion = s16[8]{0} fusion(s16[8]{0} %all-gather.1)", "jit(fwd)/forward/gather/reshape", 6),
    ("%matmul_q16.3 = s32[8]{0} custom-call(s16[8]{0} %bitcast_fusion)",
     "jit(fwd)/forward/shard_map/fc0/jit(matmul_q16_pallas)/cond/branch_0_fun/matmul_q16/pallas_call", 30),
    ("%custom-call.9 = s16[8]{0} custom-call(s16[8]{0} %matmul_q16.3)",
     "jit(fwd)/forward/fc0/concatenate", 1),  # not a Pallas kernel
    ("%copy.5 = f32[8]{0} copy(s32[8]{0} %custom-call.9)", "jit(fwd)/forward/fc0/convert_element_type", 6),
    ("%fusion.77 = f32[8]{0} fusion(f32[8]{0} %copy.5)", "jit(fwd)/forward/reshape", 8),
]
#: per forward, by layer and kind (ms)
TABLE = {L.NO_LAYER: {"other": 1 + 8}, "quantize": {"other": 2},
         "conv0": {"kernel": 10, "other": 3}, "conv1": {"kernel": 20, "collective": 3},
         "pool1": {"other": 4}, "gather": {"collective": 5, "other": 6},
         "fc0": {"kernel": 30, "other": 1 + 6}}
FORWARD_MS = 99
GLUE_MS = 3 + 6 + 7 + 9  # conv0, gather, fc0 and no scope


# -- a tiny protobuf writer for the fields bench/layers.py reads ------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def write_xplane(path, planes, ref_ops=()):
    """An XSpace whose planes (name -> [(event name, op_name, start ns,
    duration ns)]) hold one "XLA Ops" line; each op_name is a ``tf_op``
    statistic of the event's metadata, by value or, for ``ref_ops``, by
    reference to a stat metadata entry (as the profiler interns strings)."""
    tf_op, category = 1, 2
    space = b""
    for pid, (pname, events) in enumerate(planes.items(), 1):
        ids, strings, metas, line = {}, {}, b"", _msg((1, 1), (2, tr.OPS_LINE), (3, 0))
        for text, op, start, dur in events:
            if text not in ids:
                ids[text] = mid = len(ids) + 1
                stats = [(5, _msg((1, category), (5, "custom-call")))]
                if op is not None and op in ref_ops:
                    sid = strings.setdefault(op + ":", 100 + len(strings))
                    stats.append((5, _msg((1, tf_op), (7, sid))))
                elif op is not None:
                    stats.append((5, _msg((1, tf_op), (5, op + ":"))))
                display = text.split(" = ", 1)[0].lstrip("%")  # conv_untiled.14
                meta = _msg((1, mid), (2, text), (4, display), *stats)
                metas += _msg((4, _msg((1, mid), (2, meta))))
            line += _msg((4, _msg((1, ids[text]), (2, int(start * 1000)), (3, int(dur * 1000)))))
        stat_meta = {tf_op: "tf_op", category: "hlo_category",
                     **{sid: s for s, sid in strings.items()}}
        smetas = b"".join(_msg((5, _msg((1, k), (2, _msg((1, k), (2, v))))))
                          for k, v in stat_meta.items())
        space += _msg((1, _msg((1, pid), (2, pname), (3, line)) + metas + smetas))
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space)
    return str(path)


def _events(forwards=1, scale=1.0):
    out, t = [], 0.0
    for _ in range(forwards):
        for text, op, ms in FORWARD:
            out.append((text, op, t, ms * MS * scale))
            t += ms * MS * scale
    return out


def _ms(table):
    return {k: {kind: v / MS for kind, v in row.items() if v} for k, row in table.items()}


# -- the reduction ----------------------------------------------------------

def test_layer_and_kind_come_from_the_op_name():
    assert L.layer_of(PALLAS.format("conv3")) == "conv3"
    assert L.layer_of("jit(fwd)/forward/shard_map/fc2/dot") == "fc2"
    assert L.layer_of("jit(fwd)/forward/reshape") == L.NO_LAYER == L.layer_of("")
    assert L.kind_of("conv_untiled", PALLAS.format("conv3")) == "kernel"
    assert L.kind_of("custom-call", "jit(fwd)/forward/fc0/concatenate") == "other"
    assert L.kind_of("collective-permute-done", "jit(fwd)/forward/conv1/ppermute") == "collective"
    assert L.kind_of("all-gather", "") == "collective"


def test_the_trace_names_each_operations_layer(tmp_path):
    ops = ["jit(fwd)/forward/pool1/reduce_window"]  # interned, by reference
    path = write_xplane(tmp_path / "t.xplane.pb",
                        {"/host:CPU": _events(1), TPU0: _events(2)}, ref_ops=ops)
    names = L.op_names(path, [TPU0])
    assert list(names) == [TPU0]
    assert names[TPU0][FORWARD[3][0]] == names[TPU0]["conv_untiled.14"] == PALLAS.format("conv0")
    assert names[TPU0][FORWARD[7][0]] == ops[0]
    assert FORWARD[0][0] not in names[TPU0]  # no op_name
    table = L.by_layer(L.read_ops(path, [TPU0]), 0, 1e12)[TPU0]
    assert _ms(table) == {k: {kind: 2 * v for kind, v in row.items()} for k, row in TABLE.items()}


def test_layer_sums_equal_the_kernel_name_sums(tmp_path):
    path = write_xplane(tmp_path / "t.xplane.pb", {TPU0: _events(3)})
    named = tr.read_xplane(path).devices[TPU0]  # as bench/trace.py reads it
    lo, hi = 5 * MS, 250 * MS
    table = L.by_layer(L.read_ops(path, [TPU0]), lo, hi)[TPU0]
    by_prefix = lambda *ps: sum(tr.time_by_prefix(named, p, lo, hi) for p in ps)  # noqa: E731
    assert sum(r["kernel"] for r in table.values()) == pytest.approx(by_prefix("conv_", "matmul_"))
    assert sum(r["collective"] for r in table.values()) == pytest.approx(
        by_prefix("collective-permute", "all-gather"))
    assert sum(sum(r.values()) for r in table.values()) == pytest.approx(tr.busy_ns(named, lo, hi))


def test_glue_and_the_scoped_share():
    ops = [L.Op(t, t + d, L.layer_of(op or ""), L.kind_of(tr.op_name(text), op or ""))
           for text, op, t, d in _events(2)]
    table = L.by_layer({TPU0: ops}, 0, 1e12)[TPU0]
    # glue: conv, FC and gather scopes and no scope; not quantize or pools
    assert L.glue_ns(table) / MS == 2 * GLUE_MS
    assert L.scoped_share(table) == pytest.approx(1 - 9 / FORWARD_MS)
    first = L.by_layer({TPU0: ops}, 0, FORWARD_MS * MS)[TPU0]  # clipped to the window
    assert sum(sum(r.values()) for r in first.values()) == FORWARD_MS * MS
    assert L.named({TPU0: table}) and not L.named({TPU0: {L.NO_LAYER: table["conv0"]}})


def test_the_busiest_chip_and_the_description(tmp_path):
    path = write_xplane(tmp_path / "t.xplane.pb", {"/device:TPU:1": _events(1), TPU0: _events(2)})
    tables = L.by_layer(L.read_ops(path, [TPU0, "/device:TPU:1"]), 0, 1e12)
    assert L.busiest(tables) is tables[TPU0]
    lines = L.describe({TPU0: tables[TPU0]}, 2, {"conv0": 5e-3, "fc0": 1e-3})
    assert lines[0].startswith(f"{TPU0}: 90.91% of operation time in a layer scope; glue 25.0000")
    assert any(line.split()[:3] == ["conv0", "kernel", "10.0000"] and line.endswith("roofline 50.00%")
               for line in lines)
    assert [line.split()[0] for line in lines[1:]] == [
        "quantize", "conv0", "conv1", "pool1", "gather", "fc0", L.NO_LAYER]


# -- the readers ------------------------------------------------------------

def _prog_span(index, name, dur_s, parent=None, **attrs):
    return SimpleNamespace(index=index, name=name, dur_ns=int(dur_s * 1e9), parent=parent,
                           attrs=attrs)


def test_the_readers_on_synthetic_traces_and_spans(tmp_path, monkeypatch):
    glue, cal, trace_s = (R.find_reader(n) for n in ("glue_ms.throughput", "calibrate_s", "trace_s"))
    monkeypatch.setattr(L, "TRACE_DIR", tmp_path / "trace")
    write_xplane(tmp_path / "trace" / "t.xplane.pb",
                 {TPU0: _events(2), "/device:TPU:1": _events(4, scale=0.4)})
    ctx = SimpleNamespace(trace=tr.Trace({TPU0: [], "/device:TPU:1": []}), lo=0, hi=1e12,
                          forwards=2)
    assert glue(ctx) == pytest.approx(GLUE_MS)  # on the busier chip
    monkeypatch.setattr(L, "program_spans", lambda: [
        _prog_span(0, "calibrate", 5.0), _prog_span(1, "forward", 3.0, 0, traced=False),
        _prog_span(2, "plan", 0.1, dse_searches=0),
        _prog_span(3, "forward", 2.5, traced=True), _prog_span(4, "conv0", 0.5, 3)])
    assert cal(ctx) == 5.0 and trace_s(ctx) == 2.5
    # a program without scopes or spans (the parent of these readers)
    # reads nothing, and nothing raises
    write_xplane(tmp_path / "trace" / "t.xplane.pb",
                 {TPU0: [(text, None, t, d) for text, _, t, d in _events(2)]})
    monkeypatch.setattr(L, "program_spans", lambda: [])
    assert glue(ctx) is None and cal(ctx) is None and trace_s(ctx) is None
    monkeypatch.setattr(L, "TRACE_DIR", tmp_path / "no-trace")
    assert glue(ctx) is None


def test_a_traced_run_reads_the_layer_metrics(tmp_path, monkeypatch):
    """The traced branch of a tiny q16 run on the CPU: the program's own
    set-up spans give calibrate_s and trace_s; glue_ms reads a recorded
    device plane (a CPU has none)."""
    names = ["glue_ms.throughput", "calibrate_s", "trace_s"]
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    per_layer = [{k: v for k, v in m.items() if k != "workloads"}
                 for m in bench["per_layer"] if m["name"] in names]
    root = make_root(tmp_path, {"tiny-q16.t1": (config("tiny-q16", "q16"), "t1", traffic(1), 1)},
                     per_layer=per_layer)
    write_xplane(tmp_path / "recorded" / "t.xplane.pb", {TPU0: _events(1)})
    recorded = tr.Trace(devices={TPU0: [S("conv_untiled", 0, 10 * MS)]},
                        host=[S("window", 0, FORWARD_MS * MS)])
    monkeypatch.setattr(R, "reduce_trace",
                        lambda log_dir, devices, record: (recorded, 0.0, FORWARD_MS * MS))
    monkeypatch.setattr(R.roofline, "load_peaks",
                        lambda kind, real=R.roofline.load_peaks: real("TPU v5 lite"))
    monkeypatch.setattr(R, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(L, "TRACE_DIR", tmp_path / "recorded")
    res = R.run_cell(R.load_cell("tiny-q16.t1", root), 11, 0.3, True, jax.devices("cpu")[:1],
                     log=lambda _: None)
    assert res["correct"] and set(res["metrics"]) == set(names)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["glue_ms.throughput"] == pytest.approx(GLUE_MS / res["attempted"])
    traced = [s for s in L.program_spans() if s.name == "forward" and s.attrs["traced"]]
    assert m["trace_s"] == traced[-1].dur_ns / 1e9 > 0 and m["calibrate_s"] > 0
