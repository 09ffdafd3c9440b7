"""Device milliseconds per request of the operations in the residual adds'
scopes (``add{j}``: the sum, saturation and ReLU of each bottleneck's
output and shortcut), on the busiest chip.  None where the program names
no add."""
import re

from bench import layers

ADD = re.compile(r"add\d+")


def read(ctx):
    tables = layers.window_by_layer(ctx)
    if ctx.forwards == 0 or not tables:
        return None
    rows = [row for layer, row in layers.busiest(tables).items() if ADD.fullmatch(layer)]
    if not rows:
        return None
    return sum(sum(row.values()) for row in rows) / 1e6 / ctx.forwards
