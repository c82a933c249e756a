"""Prefill chunk vs roofline: least time of the prefill chunks' live work
(bench/counts) at the chip's peaks, over their device time, in %."""


def read(ctx):
    return ctx.roofline("chunk", "_chunk_jit")
