"""Device: 1 - the union of device-busy intervals over the traced window,
mean over the chips used."""


def read(ctx):
    return ctx.idle_frac()
