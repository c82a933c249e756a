"""Whole step: model FLOPs of every token processed in the window, over
window x chips x the chip's bf16 peak, in %."""


def read(ctx):
    return ctx.mfu()
