"""Set-up seconds: data made from the seed, the program built, every
shape of the window warmed (compiled, or loaded from the cache)."""


def read(ctx):
    return ctx.setup_s
