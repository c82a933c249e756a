"""Process start to the window's opening: imports, weights, the energy
model's calibration, compiling or loading every program, warm-up."""


def read(ctx):
    return ctx.setup_s
