"""A configuration's network form: the file ``bench/networks/<form>.py``,
named by the configuration's ``"form"`` key and found by that name, as a
metric's reader is found by its stem.  Everything that depends on the
shape of a network lives in its form, so a network of a new shape is new
files only.  A form module defines:

* ``init_params(cfg, key)``: the benchmark's float32 weights from the
  seed, traceable (set-up jits it on the device);
* ``program(cfg)``: a :class:`Program`, the system under test's entry
  points for this network;
* ``float_reference(cfg, weights, precision)`` and
  ``fixed_reference(cfg, weights, calib_frames, bits)``: each a function
  from frames to float32 logits that holds the whole plain reference
  (its grids, weights on them, and forward);
* ``layer_counts(cfg, batch)``: ``[{"name", "kind", "ops", "bytes"}]``,
  one entry per layer of a forward over ``batch`` images;
* ``layer_pattern``: a regex of the layer scopes its program names;
* ``xla_layer_pattern``: a regex of those scopes whose layers the
  program computes with XLA operations, not a kernel (a pool's
  reduce-window): their operations are the layer's own work, not glue.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

NETWORKS = Path(__file__).resolve().parent / "networks"


@dataclass(frozen=True)
class Program:
    """The program's entry points for one network, its spec bound."""

    calibrate: Callable  # (template, weights, frames, base policy) -> policy
    quantize: Callable  # (template, weights, policy) -> the program's params
    plan: Callable  # (template, input shape, **placement) -> plan
    forward: Callable  # (template, params, x, policy, plan) -> logits
    layer_names: Callable  # () -> the layer names a policy's formats are keyed by


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_network_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_form(cfg: dict, networks_dir: Path = NETWORKS):
    """The module of ``networks_dir/<cfg["form"]>.py``.  A configuration
    that names no form, or a form with no file, is an error, never a
    default."""
    if "form" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} has no \"form\" key: the stem "
                       f"of its network's file in {networks_dir}")
    path = Path(networks_dir) / f"{cfg['form']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no network form {path} for configuration "
                                f"{cfg.get('name')!r}")
    return _load(path)


def every_form(networks_dir: Path = NETWORKS) -> list:
    """The modules of every form in ``networks_dir``."""
    return [_load(p) for p in sorted(Path(networks_dir).glob("*.py"))]
