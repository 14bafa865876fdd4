"""compile_s (entry): seconds JAX spent tracing, lowering and compiling
during set-up, from its own monitoring events.  A run whose programs
all come from the persistent cache still traces them."""


def read(ctx):
    return ctx.compile_s
