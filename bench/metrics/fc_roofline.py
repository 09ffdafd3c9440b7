"""FC GEMM kernels' share of their roofline, in %: the least time of one
forward's FC layers times the forwards in the traced window, over the
device time of the ``matmul_*`` kernel events summed over the chips used
(a head replicated on every chip counts every copy)."""


def read(ctx):
    t = sum(ctx.kernel_s("matmul_"))
    if t <= 0:
        return None
    return 100.0 * ctx.forwards * ctx.least_s("fc") / t
