"""decode_step_ms.<cell> (step programs): mean device time of one call
of the batched decode program in the traced slice: the union of its
operations' intervals over the number of its calls."""

from bench import trace as TR


def read(ctx):
    red = ctx.reduced
    if red is None:
        return None
    prog = TR.program(red, ctx.programs[0])
    if prog is None or not red.program_calls.get(prog):
        return None
    return red.program_ns[prog] / red.program_calls[prog] / 1e6
