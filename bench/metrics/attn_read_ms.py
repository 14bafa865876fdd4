"""attn_read_ms.<cell> (kernels): device time of the decode program's
leaf operations in the model's ``attn_read`` scope (the paged attention
read: gather, scores, softmax, values) in the traced slice, per decode
call there, the calls ``decode_step_ms`` counts.  The log splits the
decode program's time per call by scope and names the largest ops that
no scope holds."""

from bench import spans as S
from bench import trace as TR

TOP = 5


def read(ctx):
    v = S.view(ctx)
    if v is None:
        return None
    prog = TR.program(ctx.reduced, ctx.programs[0])
    calls = ctx.reduced.program_calls.get(prog, 0) if prog else 0
    if not calls:
        return None
    by = S.scope_ns(v, prog, *v.window)
    total = S.union_ns((max(o.start, v.window[0]), min(o.end, v.window[1]))
                       for o in v.ops if o.program == prog)
    S.log(f"spans: decode program {prog}, {calls} calls, leaf ops "
          f"{total / calls / 1e6:.3f} ms a call: " + ", ".join(
              f"{k} {ns / calls / 1e6:.3f} ms ({100 * ns / total:.1f}%)"
              for k, ns in sorted(by.items(), key=lambda kv: -kv[1])))
    unscoped = {}
    for o in v.ops:
        if o.program == prog and v.scope_of(o) == S.UNSCOPED:
            d = min(o.end, v.window[1]) - max(o.start, v.window[0])
            unscoped[o.name] = unscoped.get(o.name, 0) + max(d, 0)
    S.log("spans: largest unscoped decode ops, ms a call: " + ", ".join(
        f"{k} {ns / calls / 1e6:.3f}" for k, ns in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:TOP]))
    if "attn_read" not in by:
        return None
    return by["attn_read"] / calls / 1e6
