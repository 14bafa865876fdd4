"""host_gap_ms.<cell> (scheduler): device idle time in the traced slice
that falls inside a serving-thread program span other than
``serve.idle_wait`` (host work the device waits on: admission, table
growth, building a chunk's inputs, dispatch, emitting tokens), per
program call (decode plus chunk) in the slice.  The program's
``Tracer`` spans are placed on the trace by the clock offset fitted
from their annotations (``bench/spans.py``).  The log gives the fit's
residuals and the idle time under each top-level span and under none."""

import numpy as np

from bench import spans as S
from bench import trace as TR


def read(ctx):
    v = S.view(ctx)
    if v is None:
        return None
    calls = sum(ctx.reduced.program_calls.get(p, 0) for p in
                (TR.program(ctx.reduced, n) for n in ctx.programs) if p)
    if not calls:
        return None
    res = np.abs(np.asarray(v.residuals_ns))
    S.log(f"spans: clock offset fitted from {len(res)} annotations, "
          f"residual p50 {np.percentile(res, 50) / 1e6:.4f} ms, p99 "
          f"{np.percentile(res, 99) / 1e6:.4f} ms, max "
          f"{res.max() / 1e6:.4f} ms; {v.unmatched} with no Tracer span")
    by = S.idle_by_span(v)
    idle = sum(by.values())
    S.log(f"spans: device idle {idle / 1e6:.3f} ms of "
          f"{(v.window[1] - v.window[0]) / 1e6:.3f} ms, {calls} program "
          f"calls; by top-level span: " + ", ".join(
              f"{k} {ns / 1e6:.3f} ms ({100 * ns / max(idle, 1):.1f}%)"
              for k, ns in sorted(by.items(), key=lambda kv: -kv[1])))
    host = sum(ns for k, ns in by.items()
               if k not in (S.IDLE_WAIT, "(no span)"))
    return host / calls / 1e6
