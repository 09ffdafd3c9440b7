"""Device time of a traced window by layer of the network, and the
program's own host spans.

The program names each layer with a ``jax.named_scope``: ``quantize``
and ``gather`` around a network's layers, and inside them the scopes of
its form's ``layer_pattern`` (``bench/networks/<form>.py``; the layer
table's ``conv{i}``, ``pool{i}`` and ``fc{i}`` are the names its
``layer_counts`` uses).  Every HLO instruction traced inside carries the
scope in its ``op_name``
(``jit(fwd)/forward/conv3/jit(conv2d_q16_pallas)/.../pallas_call``).  The
profiler keeps that ``op_name`` as the ``tf_op`` statistic of each
operation's event metadata on the device plane; ``jax.profiler.ProfileData``
does not expose event metadata, so :func:`op_names` reads it from the
``.xplane.pb`` itself (a few protobuf fields, decoded here).  Each
operation of the "XLA Ops" line then has a layer and a kind:

* ``kernel`` — a Pallas kernel (its ``op_name`` ends in ``pallas_call``);
* ``collective`` — a cross-chip transfer (collective-permute, all-gather,
  ...);
* ``other`` — everything else: pads, copies, converts, fusions, pools.

An operation outside every layer scope falls under :data:`NO_LAYER`.  A
program without layer scopes reads all its time there, and the readers
built on this return None; so do they where the program records no spans.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass
from pathlib import Path

from bench import forms
from bench import trace as tr

#: Where bench/run.py writes the profile of a traced window.
TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_trace"
#: the program's scopes around a network's layers
PROGRAM_SCOPES = ("quantize", "gather")
NO_LAYER = "-"
KINDS = ("kernel", "collective", "other")
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "collective-broadcast", "reduce-scatter", "send", "recv")
#: XSpace.planes; XPlane.name, .event_metadata, .stat_metadata; map entry
#: key, value; XEventMetadata.name, .display_name, .stats;
#: XStatMetadata.name; XStat.metadata_id, .str_value, .ref_value
_PLANES, _NAME, _EVENT_META, _STAT_META, _KEY, _VALUE = 1, 2, 4, 5, 1, 2
_DISPLAY_NAME, _STATS, _STAT_ID, _STR, _REF = 4, 5, 1, 5, 7


@dataclass(frozen=True)
class Op:
    start: float  # ns
    end: float
    layer: str
    kind: str


def _varint(b: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one protobuf message in b[i:end]: an int
    for a varint, (start, end) of the bytes for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def op_names(path: str, planes: list) -> dict:
    """Per device plane of ``planes``: event name -> the ``op_name`` of
    its HLO instruction (the ``tf_op`` statistic, less its ``:type``)."""
    b = Path(path).read_bytes()
    out = {}
    for field, plane in _fields(b, 0, len(b)):
        if field != _PLANES:
            continue
        name, metas, stat_names = None, [], {}
        for f, v in _fields(b, *plane):
            if f == _NAME:
                name = _text(b, v)
            elif f == _EVENT_META:
                metas.append(v)
            elif f == _STAT_META:
                entry = dict(_fields(b, *v))
                stat = dict(_fields(b, *entry[_VALUE]))
                stat_names[entry[_KEY]] = _text(b, stat[_NAME]) if _NAME in stat else ""
        if name not in planes:
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        names = out.setdefault(name, {})
        for entry in metas:
            value = dict(_fields(b, *entry)).get(_VALUE)
            keys, op = [], None
            for f, v in _fields(b, *value) if value else ():
                if f in (_NAME, _DISPLAY_NAME):
                    keys.append(_text(b, v))
                elif f == _STATS:
                    stat = dict(_fields(b, *v))
                    if stat.get(_STAT_ID) in tf_op:
                        op = (_text(b, stat[_STR]) if _STR in stat
                              else stat_names.get(stat.get(_REF), ""))
            if op is not None:
                names.update(dict.fromkeys(keys, op.rsplit(":", 1)[0]))
    return out


def _either(patterns) -> re.Pattern:
    return re.compile("^(" + "|".join(p for p in patterns if p) + ")$")


@functools.cache
def scopes() -> tuple:
    """(every layer scope, the scopes whose operations are the layer's
    own work and not glue): :data:`PROGRAM_SCOPES` with the
    ``layer_pattern`` of every form in ``bench/networks``, and ``quantize``
    with every form's ``xla_layer_pattern``."""
    found = forms.every_form()
    return (_either([*PROGRAM_SCOPES, *(f.layer_pattern for f in found)]),
            _either(["quantize", *(f.xla_layer_pattern for f in found)]))


def layer_of(op_name: str) -> str:
    """The innermost layer scope in an ``op_name`` path, or NO_LAYER."""
    layer = scopes()[0]
    found = [c for c in op_name.split("/") if layer.match(c)]
    return found[-1] if found else NO_LAYER


def kind_of(name: str, op_name: str) -> str:
    if op_name.rsplit("/", 1)[-1] == "pallas_call":
        return "kernel"
    return "collective" if name.startswith(COLLECTIVES) else "other"


def read_ops(path: str, planes: list) -> dict:
    """Per device plane of ``planes``: the "XLA Ops" events, each with its
    layer and kind."""
    from jax.profiler import ProfileData

    where = op_names(path, planes)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name not in planes:
            continue
        named, seen = where.get(plane.name, {}), {}
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for e in line.events:
                if e.name not in seen:
                    op_name = named.get(e.name, "")
                    seen[e.name] = (layer_of(op_name), kind_of(tr.op_name(e.name), op_name))
                out.setdefault(plane.name, []).append(
                    Op(e.start_ns, e.start_ns + e.duration_ns, *seen[e.name]))
    return out


def by_layer(ops: dict, lo: float, hi: float) -> dict:
    """Per chip: {layer: {kind: device ns inside [lo, hi]}}."""
    out = {}
    for chip, chip_ops in ops.items():
        table: dict = {}
        for op in chip_ops:
            d = min(op.end, hi) - max(op.start, lo)
            if d > 0:
                table.setdefault(op.layer, dict.fromkeys(KINDS, 0.0))[op.kind] += d
        out[chip] = table
    return out


def window_by_layer(ctx) -> dict:
    """:func:`by_layer` of a traced run's window ({} without a profile)."""
    try:
        path = tr.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return {}
    return by_layer(read_ops(path, list(ctx.trace.devices)), ctx.lo, ctx.hi)


def named(tables: dict) -> bool:
    """Whether any operation fell in a layer scope."""
    return any(set(t) - {NO_LAYER} for t in tables.values())


def scoped_share(table: dict) -> float:
    """Share of one chip's operation time that falls in a layer scope."""
    total = sum(sum(row.values()) for row in table.values())
    if total <= 0:
        return 0.0
    return 1.0 - sum(table.get(NO_LAYER, {}).values()) / total


def busiest(tables: dict) -> dict:
    """The table of the chip with the most operation time."""
    return max(tables.values(), key=lambda t: sum(sum(r.values()) for r in t.values()))


def glue_ns(table: dict) -> float:
    """Operation time that is neither a kernel nor a collective, in the
    scopes of kernel layers and ``gather`` and outside every scope: the
    pads, copies, converts and relayouts around the kernels."""
    own = scopes()[1]
    return sum(row["other"] for layer, row in table.items()
               if layer == NO_LAYER or not own.match(layer))


def program_spans() -> list:
    """The program's own host spans (``repro.runtime.spans``), or [] for a
    program that records none."""
    try:
        from repro.runtime import spans
    except ImportError:
        return []
    return spans.spans()


def describe(tables: dict, forwards: int, least_s: dict) -> list:
    """One line per chip and layer, in the order the layers first ran and
    the time outside every scope last: ms per request of kernel,
    collective and other operations, and for the layers of ``least_s``
    (layer -> seconds per forward) the kernel's share of that least
    time."""
    lines = []
    per = max(forwards, 1)
    for chip, table in tables.items():
        lines.append(f"{chip}: {100 * scoped_share(table):.2f}% of operation time in a "
                     f"layer scope; glue {glue_ns(table) / 1e6 / per:.4f} ms/request")
        for layer in sorted(table, key=lambda layer: layer == NO_LAYER):
            row = table[layer]
            ms = {k: v / 1e6 / per for k, v in row.items()}
            roof = ""
            if layer in least_s and row["kernel"] > 0:
                roof = f" roofline {100 * forwards * least_s[layer] / (row['kernel'] / 1e9):.2f}%"
            lines.append(f"  {layer:>8} kernel {ms['kernel']:.4f} collective "
                         f"{ms['collective']:.4f} other {ms['other']:.4f} ms/request{roof}")
    return lines
