"""prefill_tok_per_s.<cell> (step programs): prompt tokens the chunked
prefill computed in the traced slice, over the device time of the chunk
program there.  Tokens come from the program's ``prefill_chunk`` spans
that start in the slice; padding at a prompt's end is not counted."""

from bench import trace as TR


def chunk_spans(ctx):
    """(rid, first position, tokens) of each chunk started in the slice."""
    plen = {r.rid: len(r.prompt) for r in ctx.records}
    ta, tb = ctx.traced
    out = []
    for e in ctx.events:
        if (e["name"] == "prefill_chunk" and ta <= e["ts"] < tb
                and e["rid"] in plen):
            base = e["args"]["base"]
            out.append((e["rid"], base,
                        max(0, min(ctx.chunk, plen[e["rid"]] - base))))
    return out


def read(ctx):
    red = ctx.reduced
    if red is None or ctx.traced is None:
        return None
    prog = TR.program(red, ctx.programs[1])
    tokens = sum(n for _, _, n in chunk_spans(ctx))
    if prog is None or not red.program_ns.get(prog) or not tokens:
        return None
    return tokens / (red.program_ns[prog] / 1e9)
