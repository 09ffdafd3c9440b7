"""Seconds of the program's last ``forward`` span traced for compilation:
the Python trace of the forward that the window runs, which the
persistent compile cache cannot skip.  None where the program records
none."""
from bench import layers


def read(ctx):
    spans = [s for s in layers.program_spans() if s.name == "forward" and s.attrs.get("traced")]
    return spans[-1].dur_ns / 1e9 if spans else None
