"""latent_read_roofline.<cell> (kernels): the least time the decode
calls' latent attention reads require, over their ``attn_read`` device
time, in %.  For each decode call whose ``decode_step`` span lies in the
traced slice, its tokens (the ``token`` events stamped at the span's
end) read their live latent rows: (``kv_lora`` + ``qk_rope``) * 2 bytes
(bf16) * ``n_layers`` per key, ``pos + 1`` keys for a token at ``pos``,
and compute, per key and layer, the absorbed form's 2 * heads *
(lora + rope) FLOPs for the scores and 2 * heads * lora for the values
(``families/mla_moe.attn_key_flops``).  The least time is the larger of
the bytes over the HBM bandwidth and the FLOPs over the bf16 peak
(``bench/peaks.py``); the log says which bound applies.  Only live rows
count, so a read of more than the live pages reads below 100%.  A model
without a latent cache reads nothing."""

import bisect

from bench import peaks as P
from bench import spans as S
from bench import trace as TR
from bench.families.mla_moe import attn_key_flops

ROW_BYTES = 2          # bf16


def decode_keys(ctx, v):
    """(start, end) of each decode call in the slice on the trace clock,
    with the keys its tokens read."""
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
    return [(v.at(a), v.at(b), k) for (a, b), k in zip(steps, keys)
            if k and v.at(a) >= lo and v.at(b) <= hi]


def read(ctx):
    m = ctx.m
    if "kv_lora" not in m:
        return None
    v = S.view(ctx)
    if v is None:
        return None
    prog = TR.program(ctx.reduced, ctx.programs[0])
    if prog is None:
        return None
    per_key_bytes = (m["kv_lora"] + m["qk_rope"]) * ROW_BYTES * m["n_layers"]
    per_key_flops = attn_key_flops(m) * m["n_layers"]
    reads = sorted((o.start, o.end) for o in v.ops if o.program == prog
                   and v.scope_of(o) == "attn_read")
    starts = [s for s, _ in reads]
    read_ns = keys = 0.0
    calls = 0
    for sa, sb, k in decode_keys(ctx, v):
        i, j = bisect.bisect_left(starts, sa), bisect.bisect_left(starts, sb)
        read_ns += S.union_ns(reads[i:j])
        keys += k
        calls += 1
    if not read_ns:
        return None
    pk = P.peaks(ctx.device_kind)
    t_bytes = keys * per_key_bytes / pk["hbm_bytes_per_s"]
    t_flops = keys * per_key_flops / pk["bf16_flops"]
    S.log(f"spans: latent read roofline over {calls} decode calls: live "
          f"latent rows {keys * per_key_bytes / calls / 1e9:.4f} GB a call "
          f"({t_bytes / calls * 1e3:.3f} ms at HBM bandwidth), "
          f"{keys * per_key_flops / calls / 1e9:.3f} GFLOP "
          f"({t_flops / calls * 1e3:.3f} ms at the bf16 peak): the "
          f"{'bandwidth' if t_bytes >= t_flops else 'compute'} bound "
          f"applies; attn_read {read_ns / calls / 1e6:.3f} ms a call")
    return 100.0 * max(t_bytes, t_flops) / (read_ns / 1e9)
