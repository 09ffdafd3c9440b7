"""Device milliseconds per request of the operations around the kernels:
those in the scope of a kernel layer or of ``gather``, or in none, that
are neither a Pallas kernel nor a collective (pads, copies, converts,
relayouts; see bench/layers.py), on the busiest chip.  None where the program names no
layer."""
from bench import layers


def read(ctx):
    tables = layers.window_by_layer(ctx)
    if ctx.forwards == 0 or not layers.named(tables):
        return None
    return layers.glue_ns(layers.busiest(tables)) / 1e6 / ctx.forwards
