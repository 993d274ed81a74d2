"""Set-up time: process start to the start of the window (import, data,
program build, warm-up; compiles or cache loads included)."""


def read(ctx):
    return ctx.setup_s
