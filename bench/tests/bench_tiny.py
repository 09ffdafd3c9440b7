"""A benchmark root for the CPU tests: this repository's ``bench/`` copied
beside a ``BENCHMARK.json`` whose cells run a tiny VGG-style network (two
3x3 convs with 2x2 pools, one hidden FC) in both numerics, so that a whole
run, reference check included, fits in a test."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "network": "tiny", "input_hw": 32, "input_ch": 3, "n_classes": 10,
    "convs": [[8, 3, 1, 1, 2], [16, 3, 1, 1, 2]], "fcs": [32],
}


#: The float limit of the tiny network, set the way the real one is, from
#: its own readings on the CPU: sound runs read 3.2e-7 to 7.8e-7 and the
#: control (``high``) 4.3e-6 to 1.1e-5 on four seeds.  Three layers
#: accumulate less rounding than VGG16's sixteen, so both read lower.
TINY_FLOAT_LIMIT = 2e-6


def config(name: str, numerics: str) -> dict:
    """A tiny configuration with the numerics of the real one."""
    real = json.loads((REPO / "bench" / "configs" / f"vgg16-224-{numerics}.json").read_text())
    real.update(TINY, name=name)
    if real["check"]["name"] == "logit_err_rel":
        real["check"] = dict(real["check"], limit=TINY_FLOAT_LIMIT)
    return real


def traffic(batch: int, in_flight: int = 1, spatial: int = 1) -> dict:
    return {"batch": batch, "in_flight": in_flight, "spatial": spatial,
            "pool": 4 * batch, "warmup": 1, "check_requests": 2}


def make_root(tmp: Path, cells: dict, per_layer: list = (), extra_files: dict = ()) -> Path:
    """``cells``: workload name -> (config dict, traffic name, traffic dict,
    chips).  Writes the configs, the traffic mixes, ``extra_files`` (path
    relative to the root -> text) and a ``BENCHMARK.json`` naming them."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    configs, workloads = {}, []
    for name, (cfg, tname, tdict, chips) in cells.items():
        cfile = f"bench/configs/{cfg['name']}.json"
        (root / cfile).write_text(json.dumps(cfg))
        (root / "bench" / "traffic" / f"{tname}.json").write_text(json.dumps(tdict))
        configs[cfg["name"]] = {"name": cfg["name"], "source": "https://arxiv.org/abs/1409.1556",
                                "file": cfile, "reduced": [], "why": "test"}
        workloads.append({"name": name, "config": cfg["name"], "traffic": tname,
                          "chips": chips, "why": "test"})
    for rel, text in dict(extra_files).items():
        (root / rel).write_text(text)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench.update(configs=list(configs.values()), workloads=workloads,
                 per_layer=list(per_layer))
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
