"""moe_roofline.<cell> (model): the least time a decode call's expert
layers require, over their device time (``moe_ms``), in %.

The least time is the larger of two bounds, per decode call:
* bytes over the HBM bandwidth: the weights of every expert hit (the
  distinct experts a decode call routed a live token to, summed over the
  MoE layers, from the program's ``stats()["moe"]`` counters), each
  3 * hidden * ``moe_intermediate_size`` bf16 values, plus per MoE layer
  the shared experts' and the router's weights;
* FLOPs over the bf16 peak: the live tokens' active work, per MoE layer
  (``num_experts_per_tok`` routed plus the shared experts, each a
  SwiGLU, and the router).
The counters cover the window's decode calls and the device time the
traced slice's; both are per call.  The log says which bound applies.
A model without experts, or a run without the counters, reads nothing."""

import numpy as np

from bench import peaks as P
from bench import spans as S
from bench.metrics.moe_ms import per_call

W_BYTES = 2          # bf16


def read(ctx):
    if "moe" not in ctx.stats1 or "moe" not in ctx.stats0:
        return None
    got = per_call(ctx)
    if got is None:
        return None
    ms, _, _ = got
    m = ctx.m
    a, b = ctx.stats0["moe"], ctx.stats1["moe"]
    calls = b["decode_calls"] - a["decode_calls"]
    if calls <= 0:
        return None
    hit = (np.sum(b["decode_experts_hit"]) - np.sum(a["decode_experts_hit"])
           ) / calls
    # every live token makes top_k assignments in each MoE layer
    assigned = np.asarray(b["decode_tokens"]) - np.asarray(a["decode_tokens"])
    tokens = assigned[0].sum() / m["top_k"] / calls
    d, f, E = m["d_model"], m["moe_ff"], m["n_experts"]
    layers = m["n_layers"] - m["first_dense"]
    expert = 3 * d * f
    need_bytes = W_BYTES * (hit * expert
                            + layers * (m["n_shared"] * expert + d * E))
    need_flops = tokens * layers * (
        2 * (m["top_k"] + m["n_shared"]) * expert + 2 * d * E)
    pk = P.peaks(ctx.device_kind)
    t_bytes = need_bytes / pk["hbm_bytes_per_s"]
    t_flops = need_flops / pk["bf16_flops"]
    S.log(f"spans: expert roofline over {calls} decode calls: "
          f"{hit / layers:.2f} experts hit a layer, {tokens:.2f} live "
          f"tokens a call; {need_bytes / 1e9:.3f} GB of weights "
          f"({t_bytes * 1e3:.3f} ms at HBM bandwidth), "
          f"{need_flops / 1e9:.3f} GFLOP ({t_flops * 1e3:.3f} ms at the "
          f"bf16 peak): the {'bandwidth' if t_bytes >= t_flops else 'compute'}"
          f" bound applies; expert layers {ms:.3f} ms a call")
    return 100.0 * max(t_bytes, t_flops) / (ms / 1e3)
