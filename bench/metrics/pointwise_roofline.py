"""1×1 conv kernels' share of their roofline, in %: the least time of one
forward's layers whose counts give ``k`` 1 (bench/roofline.py, at the
configuration's storage width and peak) times the forwards in the traced
window, over the device time of the Pallas kernels in those layers'
scopes, summed over the chips used.  None where the form counts no 1×1
layer or the program names none."""
from bench import layers, roofline


def read(ctx):
    pointwise = [c for c in ctx.counts if c.get("k") == 1]
    names = {c["name"] for c in pointwise}
    t = sum(row["kernel"] for table in layers.window_by_layer(ctx).values()
            for layer, row in table.items() if layer in names)
    if not pointwise or t <= 0:
        return None
    least = roofline.least_time_s(pointwise, ctx.peaks, ctx.cfg["peak"])
    return 100.0 * ctx.forwards * least / (t / 1e9)
