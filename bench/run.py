"""The benchmark: one cell of ``BENCHMARK.json`` per run, on the chip.

    python3 bench/run.py --workload vgg16-224-q16.b1 --seed 7 --seconds 15 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: the
network's sizes, the numerics, the check) and a traffic mix
(``bench/traffic/<traffic>.json``: batch, requests in flight, spatial
shards, frame pool, requests checked).  The configuration's ``"form"``
names the file ``bench/networks/<form>.py`` (``bench/forms.py``) that
holds all that depends on the network's shape: its weights, its binding
to the program, its plain reference and its roofline counts.  Per-layer
metrics are read by ``bench/metrics/<stem>.py`` (the metric's name up to
its first ``.``: ``conv_roofline.py`` reads ``conv_roofline.latency`` and
``conv_roofline.throughput``), each a ``read(ctx)`` that returns a number
or None.  Nothing here names a cell, a configuration, a metric or a
network's form: new ones are new files.

A run: set-up (weights made on the device from the seed, the program's
numerics preparation, the plan, compile through the persistent cache,
warm-up of the cell's own shape), then a closed loop of requests for
``--seconds``.  A request is a host float32 frame batch from the seeded
pool: ``device_put``, the program's jitted forward, the logits fetched
to the host; its latency runs from submission to logits on the host.
After the window a sample of the completed requests drawn from the seed
is compared with the form's plain reference, on the host CPU.  The last
line of stdout is the JSON result; the numbers compared, each with its
limit, are the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from functools import partial  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# run as a script, sys.path[0] is bench/, whose trace.py would shadow the
# standard library's: import this directory as the package ``bench``
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import forms, reference, roofline  # noqa: E402
from bench import trace as tr  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    readers: dict
    form: object  # the module of bench/networks/<cfg["form"]>.py


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_reader(name: str, metrics_dir: Path = BENCH / "metrics"):
    """``read`` of ``metrics/<stem>.py``, the stem being ``name`` up to its
    first ``.``."""
    stem = name.split(".")[0]
    path = metrics_dir / f"{stem}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(by_name)}")
    w = by_name[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(
        name=workload, chips=w["chips"], cfg=cfg, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer,
        readers={m["name"]: find_reader(m["name"], root / "bench" / "metrics")
                 for m in per_layer},
        form=forms.find_form(cfg, root / "bench" / "networks"),
    )


def chips_or_exit(n: int):
    """The first ``n`` accelerator devices; exits (no result) without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        sys.exit("bench: JAX finds no accelerator (platform cpu); no result")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX finds {len(devs)}; no result")
    return devs[:n]


@dataclass
class Setup:
    """What set-up hands the window: the compiled forward and its inputs."""

    forward: object  # compiled (params, x) -> logits
    params: object  # the program's parameters, placed
    weights: object  # the benchmark's float32 weights (device 0)
    batches: np.ndarray  # (pool // batch, batch, H, W, C) host frames
    x_sharding: object
    mesh_ctx: object  # () -> the context the program runs under (its mesh)
    compile_s: float
    spans: dict = field(default_factory=dict)


def build(cell: Cell, seed: int, devices, log=log) -> Setup:
    """Set-up, each step a host span: weights from the seed on the device,
    the program's numerics preparation, its plan, compile, in that order."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.core.quantization import NumericsPolicy
    from repro.core.template import default_template
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as sh

    cfg, traffic = cell.cfg, cell.traffic
    prog = cell.form.program(cfg)
    spans: dict = {}

    @contextlib.contextmanager
    def span(name):
        t = time.perf_counter()
        yield
        spans[name] = time.perf_counter() - t

    b, hw, ch = traffic["batch"], cfg["input_hw"], cfg["input_ch"]
    shards = traffic["spatial"]
    with span("frames"):
        rng = np.random.default_rng([seed, 1])
        pool = rng.standard_normal((traffic["pool"], hw, hw, ch), dtype=np.float32)
        batches = pool.reshape(traffic["pool"] // b, b, hw, hw, ch)
    dev0 = SingleDeviceSharding(devices[0])
    with span("weights"):
        weights = jax.jit(partial(cell.form.init_params, cfg), out_shardings=dev0)(
            reference.seed_key(seed))
        jax.block_until_ready(weights)
    tpl = default_template(cfg["template"])
    policy = None
    params = weights
    if cfg["policy"]:
        with span("calibrate"):
            x_cal = jax.device_put(batches[0], dev0)
            policy = prog.calibrate(tpl, weights, x_cal, NumericsPolicy(cfg["policy"]))
        with span("quantize"):
            params = jax.block_until_ready(prog.quantize(tpl, weights, policy))
        log(f"activation grid (calibrated): {policy.fmt}")
    mesh_ctx = contextlib.nullcontext
    x_sharding = dev0
    plan_kw = {}
    if shards > 1:
        mesh = make_mesh((shards,), ("data",), devices=list(devices[:shards]))
        x_sharding = NamedSharding(mesh, PartitionSpec())
        params = jax.device_put(params, x_sharding)
        mesh_ctx = partial(sh.use_mesh, mesh, sh.SERVE_RULES)
        plan_kw = {"mesh": mesh, "spatial": "data"}
    with mesh_ctx():
        with span("plan"):
            plan = prog.plan(tpl, (b, hw, hw, ch), **plan_kw)
        for line in plan.describe():
            log(f"  plan {line}")

        def fwd(p, x):
            return prog.forward(tpl, p, x, policy, plan)

        with span("compile"):
            x_spec = jax.ShapeDtypeStruct((b, hw, hw, ch), np.float32, sharding=x_sharding)
            forward = jax.jit(fwd).lower(params, x_spec).compile()
    return Setup(forward, params, weights, batches, x_sharding, mesh_ctx,
                 spans["compile"], spans)


@dataclass
class Window:
    latencies_s: list
    outputs: dict  # request id -> host logits
    attempted: int
    failed: int
    images_in_window: int
    seconds: float


def closed_loop(st: Setup, traffic: dict, seconds: float, n_classes: int,
                record: list | None = None, max_requests: int | None = None) -> Window:
    """Requests back to back, ``in_flight`` outstanding, submitted until
    ``seconds`` have passed (or ``max_requests`` were sent), then drained.
    ``record`` collects the host spans (``window``, ``h2d``, ``dispatch``,
    ``fetch_logits``, ``next_request``) on the host's clock, in ns."""
    import jax

    @contextlib.contextmanager
    def span(name):
        t = time.perf_counter_ns()
        yield
        if record is not None:
            record.append(tr.Span(name, t, time.perf_counter_ns()))

    b, depth = traffic["batch"], traffic["in_flight"]
    nb = len(st.batches)
    lat, outs, pending = [], {}, deque()
    failed = images = k = 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with span("window"):
        while True:
            while (len(pending) < depth and time.perf_counter() < t_end
                   and (max_requests is None or k < max_requests)):
                t_sub = time.perf_counter()
                with span("h2d"):
                    x = jax.device_put(st.batches[k % nb], st.x_sharding)
                with span("dispatch"):
                    y = st.forward(st.params, x)
                pending.append((k, t_sub, y))
                k += 1
            if not pending:
                break
            kid, t_sub, y = pending.popleft()
            with span("fetch_logits"):
                out = np.asarray(y)
            t_done = time.perf_counter()
            with span("next_request"):
                lat.append(t_done - t_sub)
                if out.shape != (b, n_classes) or not np.isfinite(out).all():
                    failed += 1
                outs[kid] = out
                if t_done <= t_end:
                    images += b
    return Window(lat, outs, k, failed, images, seconds)


def check(cell: Cell, st: Setup, win: Window, seed: int) -> dict:
    """Compare a seeded sample of the window's requests with the form's
    plain reference on the host CPU: {number name: {"value", "limit"}}.  Fixed
    point counts the logits that differ; float32 takes the largest gap
    over the largest reference logit."""
    import jax

    cfg, traffic, ref_cfg = cell.cfg, cell.traffic, cell.cfg["reference"]
    done = sorted(win.outputs)
    rng = np.random.default_rng([seed, 2])
    take = sorted(rng.choice(len(done), size=min(traffic["check_requests"], len(done)),
                             replace=False).tolist())
    ids = [done[i] for i in take]
    nb = len(st.batches)
    x = np.concatenate([st.batches[i % nb] for i in ids])
    got = np.concatenate([win.outputs[i] for i in ids])
    cpu = jax.devices("cpu")[0]
    weights = jax.device_put(jax.device_get(st.weights), cpu)
    with jax.default_device(cpu):
        if ref_cfg["kind"] == "fixed":
            fn = cell.form.fixed_reference(cfg, weights, st.batches[0], ref_cfg["bits"])
        else:
            fn = cell.form.float_reference(cfg, weights, ref_cfg["precision"])
        ref = np.asarray(fn(jax.device_put(x, cpu)))
    if ref_cfg["kind"] == "fixed":
        value = int(np.sum(got != ref))
    else:
        value = float(np.abs(got - ref).max() / np.abs(ref).max())
    return {cfg["check"]["name"]: {"value": value, "limit": cfg["check"]["limit"]}}


def passed(checks: dict) -> bool:
    return all(v["limit"] is not None and v["value"] <= v["limit"] for v in checks.values())


@dataclass
class Context:
    """What a per-layer reader may read (``bench/metrics/*.py``)."""

    cfg: dict
    traffic: dict
    chips: int
    peaks: dict
    counts: list  # the form's layer_counts at the cell's batch, per forward
    compile_s: float
    forwards: int  # forwards run inside the traced window
    images: int  # images completed inside the traced window
    window_s: float  # host clock, traced window
    trace: object  # bench.trace.Trace
    lo: float  # traced window on the trace's clock (ns)
    hi: float

    @property
    def ops_per_image(self) -> float:
        return sum(c["ops"] for c in self.counts) / self.traffic["batch"]

    @property
    def peak_ops(self) -> float:
        return self.peaks[self.cfg["peak"]]

    def least_s(self, kind: str) -> float:
        """Least time of one forward's layers of ``kind`` on one chip."""
        return roofline.least_time_s([c for c in self.counts if c["kind"] == kind],
                                     self.peaks, self.cfg["peak"])

    def kernel_s(self, prefix: str) -> list:
        """Per chip: device seconds of operations named ``prefix...``."""
        return [tr.time_by_prefix(ops, prefix, self.lo, self.hi) / 1e9
                for ops in self.trace.devices.values()]

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the chips."""
        per = [tr.busy_ns(ops, self.lo, self.hi) / 1e9 for ops in self.trace.devices.values()]
        return sum(per) / len(per)

    @property
    def trace_window_s(self) -> float:
        return (self.hi - self.lo) / 1e9


def reduce_trace(log_dir: Path, devices, record: list) -> tuple:
    """(trace, lo, hi): the used devices' operations, the benchmark's host
    spans moved onto the trace's clock, and the window's bounds there."""
    t = tr.read_xplane(tr.find_xplane(str(log_dir)))
    want = [f"/device:{d.platform.upper()}:{d.id}" for d in devices]
    t.devices = {k: t.devices[k] for k in want if k in t.devices}
    if not t.devices:
        raise RuntimeError(f"the trace holds no operations of {want}")
    offset = tr.align_offset([h.start for h in record if h.name == "dispatch"],
                             [m.start for m in t.modules.get(want[0], [])])
    t.host = tr.shifted(record, offset)
    win = next(h for h in t.host if h.name == "window")
    return t, win.start, win.end


def breakdown(ctx: Context) -> dict:
    ops: dict = {}
    idle: dict = {}
    for spans in ctx.trace.devices.values():
        for name, ns in tr.top_ops(spans, ctx.lo, ctx.hi, n=10 ** 6):
            ops[name] = ops.get(name, 0.0) + ns / 1e9
        for name, ns in tr.idle_by_label(spans, ctx.trace.host, ctx.lo, ctx.hi).items():
            idle[name] = idle.get(name, 0.0) + ns / 1e9 / ctx.chips
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {"device_ops": top(ops), "idle_gaps": top(idle)}


def percentile_ms(lat: list, q: float) -> float:
    return float(np.percentile(np.asarray(lat) * 1e3, q))


def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    """The cell's end-to-end metrics: ``setup_s``, ``images_per_s`` and
    ``latency_p<q>_ms`` for any q."""
    out = {}
    for m in cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name == "images_per_s":
            v = win.images_in_window / win.seconds
        elif name.startswith("latency_p") and name.endswith("_ms"):
            v = percentile_ms(win.latencies_s, float(name[len("latency_p"):-len("_ms")]))
        else:
            raise KeyError(f"no end-to-end metric {name!r}")
        out[name] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             fault=None, log=log) -> dict:
    """One run of ``cell`` on ``devices``; ``fault`` (tests only) wraps the
    compiled forward."""
    import jax

    from repro.launch.compile_cache import CacheEvents

    events = CacheEvents()
    st = build(cell, seed, devices, log=log)
    if fault is not None:
        st.forward = fault(st.forward)
    traffic = cell.traffic
    n_classes = cell.cfg["n_classes"]
    with st.mesh_ctx():
        closed_loop(st, traffic, float("inf"), n_classes, max_requests=traffic["warmup"])
        setup_s = time.perf_counter() - T_START
        for name, s in st.spans.items():
            log(f"set-up span {name}: {s:.3f} s")
        log(f"set-up: {setup_s:.3f} s; compile cache: {events}")
        record = None
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # the device's operations only: the host tracer and its
            # annotations slow each request two- to threefold; the host
            # spans are the benchmark's own (``record``)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            record = []
        try:
            win = closed_loop(st, traffic, seconds, n_classes, record=record)
        finally:
            if trace:
                jax.profiler.stop_trace()
    t_window = time.perf_counter()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    log(f"window: {win.attempted} requests, {len(win.latencies_s)} completed, "
        f"{win.failed} failed; compile cache: {events}")
    st.forward = st.params = None  # the program's state; the check needs the weights
    checks = check(cell, st, win, seed)
    log(f"reference check: {time.perf_counter() - t_window:.3f} s")
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": passed(checks) and win.failed == 0 and bool(win.outputs),
              "attempted": win.attempted, "failed": win.failed}
    if trace:
        t, lo, hi = reduce_trace(TRACE_DIR, devices, record)
        ctx = Context(
            cfg=cell.cfg, traffic=traffic, chips=len(devices),
            peaks=roofline.load_peaks(d0.device_kind),
            counts=cell.form.layer_counts(cell.cfg, traffic["batch"]),
            compile_s=st.compile_s, forwards=win.attempted,
            images=win.images_in_window, window_s=win.seconds, trace=t, lo=lo, hi=hi)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=ctx.busy_s(), window_s=ctx.trace_window_s)
        result.update(metrics=metrics, device=device, breakdown=breakdown(ctx))
    else:
        result.update(metrics=end_to_end(cell, win, setup_s), device=device)
    result["checks"] = checks
    for name, v in checks.items():
        print(f"check {name}: {v['value']} (limit {v['limit']})", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache

    devices = chips_or_exit(cell.chips)
    log(f"compile cache: {enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
