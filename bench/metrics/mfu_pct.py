"""Whole step's share of the chips' peak, in %: the network's operations
per image (bench/roofline.py) times the images completed in the traced
window, over the window and over chips times the configuration's peak."""


def read(ctx):
    if ctx.images == 0:
        return None
    return 100.0 * ctx.images * ctx.ops_per_image / ctx.window_s / (ctx.chips * ctx.peak_ops)
