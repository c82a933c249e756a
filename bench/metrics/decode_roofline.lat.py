"""Model step vs roofline: least time of the decode dispatches' live work
(bench/counts) at the chip's peaks, over their device time, in %."""


def read(ctx):
    return ctx.roofline("dispatch", "_dispatch_jit")
