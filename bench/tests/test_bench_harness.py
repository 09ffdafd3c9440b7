"""The harness is driven by data: a configuration, a traffic mix and a
per-layer reader added as files are found by name, with no edit to
bench/run.py; and a run without the chip prints no result."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest
from bench_tiny import REPO, config, make_root, traffic

from bench import run as R

READER = '''"""Images completed in the traced window."""


def read(ctx):
    return ctx.images or None
'''


def test_new_config_traffic_and_reader_are_found_by_name(tmp_path):
    cfg = config("tiny-added-q16", "q16")
    metric = {"name": "images_seen.latency", "unit": "images", "better": "higher",
              "source": "host_clock", "layer": "whole step", "moves": "latency_p50_ms",
              "workloads": ["tiny-added-q16.odd"]}
    root = make_root(tmp_path, {"tiny-added-q16.odd": (cfg, "odd", traffic(3, 2), 1)},
                     per_layer=[metric],
                     extra_files={"bench/metrics/images_seen.py": READER})
    cell = R.load_cell("tiny-added-q16.odd", root)
    assert cell.cfg == cfg and cell.traffic == traffic(3, 2) and cell.chips == 1
    assert [m["name"] for m in cell.per_layer] == ["images_seen.latency"]
    read = cell.readers["images_seen.latency"]  # by its stem: images_seen.py
    assert read(type("Ctx", (), {"images": 12})()) == 12
    assert read(type("Ctx", (), {"images": 0})()) is None
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "latency_p95_ms"}
    with pytest.raises(KeyError, match="unknown workload"):
        R.load_cell("tiny-added-q16.even", root)


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = R.load_cell(w["name"])
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert "setup_s" in {m["name"] for m in cell.end_to_end}


def _run_script(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16-224-q16.b1",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_an_accelerator_no_result_is_printed():
    out = _run_script(REPO)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    out = _run_script(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "No module named 'repro'" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


@pytest.mark.parametrize("numerics,batch,in_flight", [("q16", 1, 1), ("f32", 4, 2)])
def test_a_tiny_cell_runs_end_to_end_on_the_cpu(tmp_path, numerics, batch, in_flight):
    name = f"tiny-{numerics}.t{batch}"
    root = make_root(tmp_path, {name: (config(f"tiny-{numerics}", numerics), f"t{batch}",
                                       traffic(batch, in_flight), 1)})
    cell = R.load_cell(name, root)
    lines = []
    res = R.run_cell(cell, 2**32 + 17, 0.5, False, jax.devices("cpu")[:1], log=lines.append)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "latency_p50_ms", "latency_p95_ms", "images_per_s"}
    assert all(m["value"] > 0 for k, m in res["metrics"].items() if k != "images_per_s")
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    check = res["checks"][cell.cfg["check"]["name"]]
    assert check["value"] <= check["limit"]
    assert any(line.startswith("  plan conv0:") for line in lines)
    assert any(line.startswith("set-up span compile:") for line in lines)


def test_a_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch):
    """The traced branch, with the trace's reduction replaced by a recorded
    one (a CPU has no device plane): every reader finds its number."""
    from bench import roofline
    from bench import trace as tr

    names = ["compile_s", "conv_roofline.latency", "fc_roofline.latency",
             "halo_exchange_ms", "device_idle_pct.latency", "mfu_pct.latency"]
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    per_layer = [{k: v for k, v in m.items() if k != "workloads"}
                 for m in bench["per_layer"] if m["name"] in names]
    root = make_root(tmp_path, {"tiny-q16.t1": (config("tiny-q16", "q16"), "t1", traffic(1), 1)},
                     per_layer=per_layer)
    S = tr.Span
    ms = 1e6
    recorded = tr.Trace(
        devices={"/device:TPU:0": [S("conv_untiled", 0, 4 * ms), S("matmul_q16", 4 * ms, 6 * ms),
                                   S("collective-permute-done", 6 * ms, 7 * ms)]},
        host=[S("window", 0, 10 * ms), S("fetch_logits", 7 * ms, 9 * ms)])
    monkeypatch.setattr(R, "reduce_trace",
                        lambda log_dir, devices, record: (recorded, 0.0, 10 * ms))
    peaks = roofline.load_peaks("TPU v5 lite")
    monkeypatch.setattr(roofline, "load_peaks", lambda kind: peaks)
    monkeypatch.setattr(R, "TRACE_DIR", tmp_path / "trace")
    cell = R.load_cell("tiny-q16.t1", root)
    res = R.run_cell(cell, 5, 0.3, True, jax.devices("cpu")[:1], log=lambda _: None)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "breakdown",
                         "checks"]
    assert set(res["metrics"]) == set(names)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["device_idle_pct.latency"] == pytest.approx(30.0)
    assert m["halo_exchange_ms"] == pytest.approx(1.0 / res["attempted"])
    assert 0 < m["conv_roofline.latency"] and 0 < m["fc_roofline.latency"]
    assert res["device"]["busy_s"] == pytest.approx(0.007)
    assert res["device"]["window_s"] == pytest.approx(0.010)
    assert res["breakdown"]["device_ops"][0] == ["conv_untiled", pytest.approx(0.004)]
    # one gap, 7-10 ms, whose middle lies in the fetch
    assert dict(res["breakdown"]["idle_gaps"]) == {"fetch_logits": pytest.approx(0.003)}
