"""prefix_hit_share (cache manager): prompt tokens that the prefix cache
served, as a share of the prompt tokens of the window's requests that
were admitted (a request still queued at the cut asked the cache
nothing).  The program's ``prefix_hit_tokens`` counter is read before
the window and after its last request ended; the admitted requests are
those with an ``admit`` span."""


def read(ctx):
    if "prefix_hit_tokens" not in ctx.stats1:
        return None
    hit = ctx.stats1["prefix_hit_tokens"] - ctx.stats0["prefix_hit_tokens"]
    admitted = {e["rid"] for e in ctx.events if e["name"] == "admit"
                and not e["args"].get("resume")}
    total = sum(len(r.prompt) for r in ctx.records if r.rid in admitted)
    if total == 0:
        return None
    return 100.0 * hit / total
