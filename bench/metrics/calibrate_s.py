"""Seconds of the program's ``calibrate`` span: the numerics preparation of
a fixed-point configuration (a probe quantization and an eager forward
that picks the activation grid).  None where the program records none."""
from bench import layers


def read(ctx):
    spans = [s for s in layers.program_spans() if s.name == "calibrate"]
    return spans[-1].dur_ns / 1e9 if spans else None
