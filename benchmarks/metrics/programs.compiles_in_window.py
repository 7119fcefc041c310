"""XLA compilations the program's ``RecompileSentinel`` saw between the
opening and the closing of the window. Must read 0."""


def read(ctx):
    return ctx.get("compiles")
