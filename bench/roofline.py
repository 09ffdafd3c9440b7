"""Operations, bytes and least times of a layer-table configuration's
layers (:func:`layer_counts` and :func:`fc_params`, read through
``bench/networks/layer_table.py``), and the table of chip peaks any form's
counts are measured against (:func:`load_peaks`, :func:`least_time_s`).

Counts are of the work each layer defines, whatever implements it:

* conv: ``2 * N * Ho * Wo * k^2 * Cin * Cout`` operations; bytes are the
  logical input, weights, bias and (unpooled) output;
* FC: ``2 * M * N * K`` operations; bytes are input, weights, bias, output;

all at the configuration's storage width (``storage_bytes``: 2 for 16-bit
fixed point, 4 for float32) and at the logical channel counts (Cin = 3 for
the first conv), never the lane padding or the digit passes of an
implementation.  A layer's least time is the larger of its operations over
the peak rate and its bytes over the HBM bandwidth.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of one chip, keyed by JAX's ``device_kind``.  A kind that
    is not in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_counts(cfg: dict, batch: int) -> list[dict]:
    """One entry per conv and FC layer: ``name``, ``kind``, ``ops`` and
    ``bytes`` for a batch of ``batch`` images."""
    sb = cfg["storage_bytes"]
    hw, ch = cfg["input_hw"], cfg["input_ch"]
    out = []
    for i, (cout, k, stride, pad, pool) in enumerate(cfg["convs"]):
        ho = (hw + 2 * pad - k) // stride + 1
        ops = 2 * batch * ho * ho * k * k * ch * cout
        elems = batch * hw * hw * ch + k * k * ch * cout + cout + batch * ho * ho * cout
        out.append({"name": f"conv{i}", "kind": "conv", "ops": ops, "bytes": elems * sb})
        hw, ch = (ho // pool if pool else ho), cout
    fan = hw * hw * ch
    for i, width in enumerate((*cfg["fcs"], cfg["n_classes"])):
        ops = 2 * batch * width * fan
        elems = batch * fan + fan * width + width + batch * width
        out.append({"name": f"fc{i}", "kind": "fc", "ops": ops, "bytes": elems * sb})
        fan = width
    return out


def least_time_s(layers: list[dict], peaks: dict, peak_key: str) -> float:
    """Sum over ``layers`` of max(ops / peak, bytes / HBM bandwidth)."""
    return sum(max(layer["ops"] / peaks[peak_key],
                   layer["bytes"] / peaks["hbm_bytes_per_s"])
               for layer in layers)


def fc_params(cfg: dict) -> int:
    """Weights and biases of the FC head."""
    hw, ch = cfg["input_hw"], cfg["input_ch"]
    for cout, k, stride, pad, pool in cfg["convs"]:
        hw = (hw + 2 * pad - k) // stride + 1
        hw, ch = (hw // pool if pool else hw), cout
    fan, n = hw * hw * ch, 0
    for width in (*cfg["fcs"], cfg["n_classes"]):
        n += fan * width + width
        fan = width
    return n
