"""step_mfu (model): the FLOPs that the tokens the step programs
processed in the traced slice require (the model family's count;
``bench/flops.py`` for the dense decoder: matmuls, attention over each
token's true context, logits where a token is sampled), over the device
time of the decode and chunk programs there times the chip's bf16 peak
(``bench/peaks.py``)."""

from bench import cell
from bench import peaks as P
from bench import trace as TR
from bench.metrics.prefill_tok_per_s import chunk_spans


def read(ctx):
    red = ctx.reduced
    if red is None or ctx.traced is None:
        return None
    progs = [TR.program(red, p) for p in ctx.programs]
    busy_ns = sum(red.program_ns.get(p, 0.0) for p in progs if p)
    if not busy_ns:
        return None
    ta, tb = ctx.traced
    F = cell.family(ctx.m["family"])
    plen = {r.rid: len(r.prompt) for r in ctx.records}
    first = {(e["rid"], e["ts"]) for e in ctx.events
             if e["name"] == "first_token"}
    work = 0.0
    for e in ctx.events:            # decode: one token per live slot
        if (e["name"] == "token" and ta <= e["ts"] < tb
                and (e["rid"], e["ts"]) not in first):
            work += F.token_flops(ctx.m, e["args"]["pos"], logits=True)
    for rid, base, n in chunk_spans(ctx):
        work += F.span_flops(ctx.m, base, base + n,
                             logits_last=base + n == plen[rid])
    peak = P.peaks(ctx.device_kind)["bf16_flops"]
    return 100.0 * work / (busy_ns / 1e9 * peak)
