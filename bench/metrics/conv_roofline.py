"""Conv kernels' share of their roofline, in %: the least time of one
forward's conv layers (bench/roofline.py, at the configuration's storage
width and peak) times the forwards in the traced window, over the device
time of the ``conv_*`` kernel events summed over the chips used."""


def read(ctx):
    t = sum(ctx.kernel_s("conv_"))
    if t <= 0:
        return None
    return 100.0 * ctx.forwards * ctx.least_s("conv") / t
