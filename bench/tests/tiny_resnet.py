"""A tiny bottleneck residual network of the ``resnet`` form for the CPU
tests: ResNet-50's shape (stem, overlapping pool, one block a stage with its
projection, strided 3x3s, average pool) at a 16th of its widths, on 32x32
frames, with the numerics of ResNet-50's q16 configuration."""
from __future__ import annotations

import json

from bench_tiny import REPO

TINY_RESNET = {
    "network": "tinyres", "input_hw": 32, "input_ch": 3, "n_classes": 10,
    "stem": [4, 7, 2, 3], "stem_pool": [3, 2, 1],
    "stages": [[1, 4, 16, 1], [1, 8, 32, 2], [1, 16, 64, 2], [1, 32, 128, 2]], "fcs": [],
}


def resnet_config(name: str) -> dict:
    """The tiny residual network with the numerics of ResNet-50's q16
    configuration."""
    real = json.loads((REPO / "bench" / "configs" / "resnet50-224-q16.json").read_text())
    real.update(TINY_RESNET, name=name)
    return real
