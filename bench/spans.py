"""The program's spans and named scopes, placed on the device trace.

A ``--trace 1`` run has two clocks.  The program's ``Tracer`` stamps
its spans on its own clock (``time.monotonic``); the profiler stamps its
events in nanoseconds from the trace's start.  Every program span
(``ServeTelemetry.span``) opens an annotation of its name whose ``t``
argument is the ``Tracer``'s stamp at entry, so the offset between the
clocks is fitted from the annotations themselves (their median), and
any ``Tracer`` span can then be placed on the device timeline.

Device operations are attributed to the ``jax.named_scope`` the model
puts them in (``SCOPES``): from the op's ``tf_op`` stat where the
profiler gives one, else from the op's metadata path in the compiled
program's HLO text, which the batcher reports in ``stats()["programs"]``
while profiling.  Only leaf operations count: an operation that
contains another of the same device (the layer loop's ``while``) is not
a leaf.

Each reader calls ``view(ctx)``; the trace is loaded and reduced once
per run and kept on the context.
"""

from __future__ import annotations

import bisect
import dataclasses
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace as TR

SCOPES = ("kv_write", "attn_read", "attn_proj", "mlp", "logits")
UNSCOPED = "(unscoped)"
IDLE_WAIT = "serve.idle_wait"
PREFIX = "serve."
# a Tracer span and its annotation carry the same stamp; printed floats
# keep it to the nanosecond
MATCH_S = 1e-6


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class View:
    window: Tuple[int, int]              # ns, trace clock
    offset_ns: float                     # trace ns - program ns
    residuals_ns: List[float]            # each annotation's from the fit
    unmatched: int                       # annotations with no Tracer span
    ops: List[TR.Op]                     # leaf device ops, in the window
    busy: List[Tuple[int, int]]          # union of all device ops, clipped
    scope: Dict[Tuple[str, str], str]    # (program, op) -> scope
    spans: List[Tuple[int, int, str]]    # serve.* Tracer spans, trace ns

    def at(self, t_s: float) -> float:
        """A program-clock time in seconds on the trace clock (ns)."""
        return t_s * 1e9 + self.offset_ns

    def scope_of(self, op: TR.Op) -> str:
        return self.scope.get((op.program, op.name), UNSCOPED)


def view(ctx) -> Optional[View]:
    """The run's trace with the program's spans and scopes on it, or
    None when the run has no trace or no annotated program span."""
    if "spans_view" not in vars(ctx):
        ctx.spans_view = _build(ctx) if ctx.reduced is not None else None
    return ctx.spans_view


def _load():
    from jax.profiler import ProfileData
    from bench.run import TRACE_DIR
    return ProfileData.from_file(TR.find_xplane(str(TRACE_DIR)))


def _build(ctx) -> Optional[View]:
    pd = _load()
    anns = annotations(pd)
    if not anns:
        return None
    offsets = np.array([s - t * 1e9 for s, _, t in anns])
    offset = float(np.median(offsets))
    xs = [e for e in ctx.events if e.get("ph") == "X"
          and e["name"].startswith(PREFIX)]
    starts: Dict[str, List[float]] = {}
    for e in xs:
        starts.setdefault(e["name"], []).append(e["ts"])
    for v in starts.values():
        v.sort()
    unmatched = sum(not _near(starts.get(name, []), t) for _, name, t in anns)
    lo, hi = ctx.reduced.window
    spans = sorted((int(e["ts"] * 1e9 + offset),
                    int((e["ts"] + e["dur"]) * 1e9 + offset), e["name"])
                   for e in xs)
    spans = [sp for sp in spans if sp[1] > lo and sp[0] < hi]
    ops = [o for o in TR.device_ops(pd) if o.end > lo and o.start < hi]
    busy = TR.union([(max(o.start, lo), min(o.end, hi)) for o in ops])
    ops = leaves(ops)
    return View(window=(lo, hi), offset_ns=offset,
                residuals_ns=list(offsets - offset), unmatched=unmatched,
                ops=ops, busy=busy,
                scope=scopes(pd, ops, ctx.stats1.get("programs", {})),
                spans=spans)


def _near(sorted_ts: List[float], t: float) -> bool:
    i = bisect.bisect_left(sorted_ts, t - MATCH_S)
    return i < len(sorted_ts) and sorted_ts[i] <= t + MATCH_S


def annotations(pd) -> List[Tuple[int, str, float]]:
    """(start ns, name, ``t``) of every program annotation in the trace."""
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    t = TR._stats(e).get("t")
                    if isinstance(t, (int, float)):
                        out.append((int(e.start_ns), e.name, float(t)))
    return out


def leaves(ops: List[TR.Op]) -> List[TR.Op]:
    """The ops that contain no other op of their device."""
    out = []
    by_dev: Dict[str, List[TR.Op]] = {}
    for o in ops:
        by_dev.setdefault(o.device, []).append(o)
    for dev_ops in by_dev.values():
        dev_ops.sort(key=lambda o: (o.start, -o.end))
        for i, o in enumerate(dev_ops):
            nxt = dev_ops[i + 1] if i + 1 < len(dev_ops) else None
            if nxt is None or not (nxt.start < o.end and nxt.end <= o.end):
                out.append(o)
    return out


def scope_name(op_name: str) -> str:
    """The innermost of ``SCOPES`` on an op's metadata path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def scopes(pd, ops: List[TR.Op], programs: Dict[str, Dict[str, str]]
           ) -> Dict[Tuple[str, str], str]:
    """(program, op) -> scope, from each op event's ``tf_op`` stat, or
    else from the program's HLO metadata (``stats()["programs"]``)."""
    tf_op: Dict[Tuple[str, str], str] = {}
    for p in pd.planes:
        if not p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            if ln.name != "XLA Ops":
                continue
            for e in ln.events:
                st = TR._stats(e)
                if "tf_op" in st:
                    name = e.name.split(" = ", 1)[0].lstrip("%")
                    prog = TR._SUFFIX.sub("", str(st.get("hlo_module", "")))
                    tf_op[prog, name] = str(st["tf_op"])
    out = {}
    for o in ops:
        key = (o.program, o.name)
        if key in out:
            continue
        path = tf_op.get(key)
        if path is None:
            path = programs.get(o.program, {}).get(o.name, "")
        out[key] = scope_name(path)
    return out


# -- the pieces the readers share ----------------------------------------------------


def union_ns(intervals) -> float:
    return float(sum(e - s for s, e in TR.union(list(intervals))))


def scope_ns(v: View, program: str, lo: int, hi: int) -> Dict[str, float]:
    """Device time of the program's leaf ops in [lo, hi], by scope: the
    union of each scope's op intervals."""
    by: Dict[str, List[Tuple[int, int]]] = {}
    for o in v.ops:
        if o.program == program and o.end > lo and o.start < hi:
            by.setdefault(v.scope_of(o), []).append(
                (max(o.start, lo), min(o.end, hi)))
    return {k: union_ns(iv) for k, iv in by.items()}


def top_level(spans: List[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """The spans that no other span contains (the serving thread's
    spans nest: ``serve.admit`` holds the admission's pieces)."""
    out: List[Tuple[int, int, str]] = []
    for sp in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        if out and sp[1] <= out[-1][1]:
            continue
        out.append(sp)
    return out


def idle(v: View) -> List[Tuple[int, int]]:
    """The window's device idle intervals (one chip): where no device
    op runs, as ``device_idle_share`` reads it."""
    lo, hi = v.window
    edges = [lo] + [x for iv in v.busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(v: View) -> Dict[str, float]:
    """The window's device idle time (ns) under each top-level program
    span, by name, and under none (``"(no span)"``)."""
    tops = top_level(v.spans)
    starts = [sp[0] for sp in tops]
    out: Dict[str, float] = {}
    for s, e in idle(v):
        covered = 0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(tops) and tops[i][0] < e:
            a, b, name = tops[i]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                covered += d
            i += 1
        out["(no span)"] = out.get("(no span)", 0.0) + (e - s - covered)
    return out
