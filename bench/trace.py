"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's ``.xplane.pb``, taken with the host tracer off.
:func:`read_xplane` turns it into plain spans: per device, the operations
on its "XLA Ops" line, each named by its HLO instruction without the
``%`` and the ``.N`` suffix (``%conv_untiled.14 = s16[...] custom-call``
-> ``conv_untiled``), and the program executions on its "XLA Modules"
line.  The host spans are the benchmark's own, on the host's clock;
:func:`align_offset` moves them onto the trace's clock by the program
executions they dispatched.  Everything else here works on those spans,
so it can be checked on a small recorded or synthetic list:

* :func:`busy_ns` — the union of a device's operation intervals inside
  a window (overlapping operations count once);
* :func:`time_by_prefix` — device time of the operations whose names
  start with a prefix (a kernel class: ``conv_``, ``matmul_``,
  ``collective-permute``);
* :func:`idle_by_label` — the device's idle gaps inside the window, each
  labelled by the innermost host span that covers its middle;
* :func:`top_ops` — the operations that took most time.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

#: The device lines with one operation, and one program execution, per event.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Z]+:\d+$")
UNLABELLED = "no_host_span"


@dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    devices: dict  # plane name -> list[Span] of operations
    host: list = field(default_factory=list)  # list[Span], on the trace's clock
    modules: dict = field(default_factory=dict)  # plane name -> list[Span]


def op_name(hlo: str) -> str:
    """``%name.N = type op(...)`` -> ``name``."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].strip().lstrip("%"))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_xplane(path: str) -> Trace:
    """Per device plane: operations and program executions."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = Trace({})
    lines = {OPS_LINE: out.devices, MODULES_LINE: out.modules}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            into = lines.get(line.name)
            if into is not None:
                into.setdefault(plane.name, []).extend(
                    Span(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
    out.devices = {k: v for k, v in out.devices.items() if v}
    return out


def align_offset(dispatches: list, executions: list) -> float:
    """The offset from the host's clock to the trace's: the i-th program
    execution follows the i-th dispatch, so the smallest gap between the
    two (a request that found the device idle) is the offset plus the
    launch latency, at most."""
    pairs = list(zip(sorted(dispatches), sorted(executions)))
    if not pairs:
        raise ValueError("no dispatch and execution to align the host spans by")
    return min(e - d for d, e in pairs)


def shifted(spans, offset: float) -> list:
    return [Span(s.name, s.start + offset, s.end + offset) for s in spans]


def merged(spans, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``spans`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: list[list[float]] = []
    for s, e in sorted((max(sp.start, lo), min(sp.end, hi)) for sp in spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(spans, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(spans, lo, hi))


def time_by_prefix(spans, prefix: str, lo: float, hi: float) -> float:
    """Device time of the operations named ``prefix...``, inside [lo, hi]."""
    return sum(max(0.0, min(sp.end, hi) - max(sp.start, lo))
               for sp in spans if sp.name.startswith(prefix))


def idle_gaps(spans, lo: float, hi: float) -> list[tuple[float, float]]:
    gaps, at = [], lo
    for s, e in merged(spans, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    return gaps


def labels_at(times: list, host: list) -> list[str]:
    """For each of the sorted ``times``, the name of the innermost host
    span that covers it (host spans come from one thread, so they nest)."""
    order = sorted(host, key=lambda h: (h.start, -h.end))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(order) and order[i].start <= t:
            while stack and stack[-1].end < order[i].start:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1].end < t:
            stack.pop()
        out.append(stack[-1].name if stack else UNLABELLED)
    return out


def idle_by_label(spans, host: list, lo: float, hi: float) -> dict:
    """Idle ns inside [lo, hi] per label of the host span covering each
    gap's middle."""
    gaps = idle_gaps(spans, lo, hi)
    out: dict = {}
    for (s, e), name in zip(gaps, labels_at([(s + e) / 2 for s, e in gaps], host)):
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def top_ops(spans, lo: float, hi: float, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` operation names with the most device ns inside [lo, hi]."""
    tot: dict = {}
    for sp in spans:
        d = min(sp.end, hi) - max(sp.start, lo)
        if d > 0:
            tot[sp.name] = tot.get(sp.name, 0.0) + d
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
