"""attn_read_roofline.<cell> (kernels): the least time the decode
calls' attention reads require, over their ``attn_read`` device time,
in %.  For each decode call whose ``decode_step`` span lies in the
traced slice, its tokens (the ``token`` events stamped at the span's
end) read their live KV: 2 (K and V) * ``n_kv_heads`` * ``head_dim`` *
2 bytes (bf16) * ``n_layers`` per key, ``pos + 1`` keys for a token at
``pos``, and compute 4 * ``n_heads`` * ``head_dim`` * ``n_layers`` FLOPs
per key (scores and values).  The least time is the larger of the
bytes over the HBM bandwidth and the FLOPs over the bf16 peak
(``bench/peaks.py``); the log says which bound applies.  Only live keys
count, so a read of more than the live pages reads below 100%."""

import bisect

from bench import peaks as P
from bench import spans as S
from bench import trace as TR

KV_BYTES = 2          # bf16


def read(ctx):
    v = S.view(ctx)
    if v is None:
        return None
    prog = TR.program(ctx.reduced, ctx.programs[0])
    if prog is None:
        return None
    m = ctx.m
    per_key_bytes = 2 * m["n_kv_heads"] * m["head_dim"] * KV_BYTES \
        * m["n_layers"]
    per_key_flops = 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    steps = sorted((e["ts"], e["ts"] + e["dur"]) for e in ctx.events
                   if e["name"] == "decode_step" and e.get("ph") == "X")
    ends = [b for _, b in steps]
    keys = [0] * len(steps)
    for e in ctx.events:          # a first token lies outside every step
        if e["name"] != "token":
            continue
        i = bisect.bisect_left(ends, e["ts"] - S.MATCH_S)
        if i < len(steps) and steps[i][0] <= e["ts"] <= ends[i] + S.MATCH_S:
            keys[i] += e["args"]["pos"] + 1
    lo, hi = v.window
    reads = sorted((o.start, o.end) for o in v.ops if o.program == prog
                   and v.scope_of(o) == "attn_read")
    starts = [s for s, _ in reads]
    pk = P.peaks(ctx.device_kind)
    need_bytes = need_flops = 0.0
    read_ns = 0.0
    calls = 0
    for (a, b), k in zip(steps, keys):
        sa, sb = v.at(a), v.at(b)
        if sa < lo or sb > hi or not k:
            continue
        i, j = bisect.bisect_left(starts, sa), bisect.bisect_left(starts, sb)
        read_ns += S.union_ns(reads[i:j])
        need_bytes += k * per_key_bytes
        need_flops += k * per_key_flops
        calls += 1
    if not read_ns:
        return None
    t_bytes = need_bytes / pk["hbm_bytes_per_s"]
    t_flops = need_flops / pk["bf16_flops"]
    S.log(f"spans: attn_read roofline over {calls} decode calls: live KV "
          f"{need_bytes / calls / 1e9:.4f} GB a call "
          f"({t_bytes / calls * 1e3:.3f} ms at HBM bandwidth), "
          f"{need_flops / calls / 1e9:.3f} GFLOP "
          f"({t_flops / calls * 1e3:.3f} ms at the bf16 peak): the "
          f"{'bandwidth' if t_bytes >= t_flops else 'compute'} bound "
          f"applies; attn_read {read_ns / calls / 1e6:.3f} ms a call")
    return 100.0 * max(t_bytes, t_flops) / (read_ns / 1e9)
