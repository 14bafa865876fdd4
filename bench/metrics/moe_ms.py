"""moe_ms.<cell> (model): device time of the decode program's leaf
operations in the expert layers' scopes (``moe_route``: norm, router,
softmax, top-k; ``moe_experts``: the routed experts and the combine;
``moe_shared``: the shared experts) in the traced slice, per decode call
there.  An op's scope is read as ``bench/spans.py`` reads it, from its
``tf_op`` stat or else from the program's HLO metadata, with these
scope names.  The log gives the split by scope.  A model without
experts reads nothing."""

from typing import Dict, Optional, Tuple

from bench import spans as S
from bench import trace as TR

MOE_SCOPES = ("moe_route", "moe_experts", "moe_shared")


def moe_scope(path: str) -> Optional[str]:
    """The innermost expert-layer scope on an op's metadata path."""
    for part in reversed(path.split("/")):
        if part in MOE_SCOPES:
            return part
    return None


def _op_scopes(ctx, v, prog: str) -> Dict[Tuple[str, str], Optional[str]]:
    if "moe_scopes" not in vars(ctx):
        tf_op: Dict[Tuple[str, str], str] = {}
        for p in S._load().planes:
            if not p.name.startswith("/device:"):
                continue
            for ln in p.lines:
                if ln.name != "XLA Ops":
                    continue
                for e in ln.events:
                    st = TR._stats(e)
                    if "tf_op" in st:
                        name = e.name.split(" = ", 1)[0].lstrip("%")
                        mod = TR._SUFFIX.sub("", str(st.get("hlo_module", "")))
                        tf_op[mod, name] = str(st["tf_op"])
        meta = ctx.stats1.get("programs", {}).get(prog, {})
        ctx.moe_scopes = {
            (o.program, o.name): moe_scope(
                tf_op.get((o.program, o.name), meta.get(o.name, "")))
            for o in v.ops if o.program == prog}
    return ctx.moe_scopes


def per_call(ctx) -> Optional[Tuple[float, Dict[str, float], int]]:
    """(ms a decode call in the expert scopes, ms a call by scope, decode
    calls) in the traced slice, or None."""
    if "n_experts" not in ctx.m:
        return None
    v = S.view(ctx)
    if v is None:
        return None
    prog = TR.program(ctx.reduced, ctx.programs[0])
    calls = ctx.reduced.program_calls.get(prog, 0) if prog else 0
    if not calls:
        return None
    scope = _op_scopes(ctx, v, prog)
    lo, hi = v.window
    by: Dict[str, list] = {}
    for o in v.ops:
        s = scope.get((o.program, o.name)) if o.program == prog else None
        if s is not None:
            by.setdefault(s, []).append((max(o.start, lo), min(o.end, hi)))
    if not by:
        return None
    total = S.union_ns(iv for ivs in by.values() for iv in ivs)
    split = {k: S.union_ns(ivs) / calls / 1e6 for k, ivs in by.items()}
    return total / calls / 1e6, split, calls


def read(ctx):
    got = per_call(ctx)
    if got is None:
        return None
    ms, split, calls = got
    S.log(f"spans: expert layers {ms:.3f} ms a decode call over {calls} "
          f"calls: " + ", ".join(f"{k} {v:.3f} ms" for k, v in sorted(
              split.items(), key=lambda kv: -kv[1])))
    return ms
