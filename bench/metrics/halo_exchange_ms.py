"""Device milliseconds of collective-permute operations (the halo
exchanges between H slabs) per request, on the chip with the most."""


def read(ctx):
    per_chip = ctx.kernel_s("collective-permute")
    if not any(per_chip):
        return None
    return 1e3 * max(per_chip) / ctx.forwards
