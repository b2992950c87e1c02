"""Seconds from process start to the window: data made on the device,
packing, the warm job (compilation, or the compile cache's load)."""


def read(ctx):
    return ctx.setup_s
