"""Latent attention with a mixture of experts (``"family": "mla_moe"``
in a configuration's ``program`` group): DeepSeek-V2's decoder.

The dimensions are read from the published config's own keys
(``hidden_size``, ``kv_lora_rank``, ``n_routed_experts``,
``rope_scaling`` ...), so the file holds the published config as it is
run.  Settings the program does not implement are refused here rather
than run as something else.  The weights, the float32 reference
(``reference_mla_moe.py``) and the work below follow the program's
stacked layout: ``blocks/first`` holds the leading dense layers,
``blocks/rest`` the MoE layers.

Work (FLOPs, a multiply-add counts two) a token at position ``pos``
requires, per layer:

* projections: the query (d -> heads * (nope + rope)), the latent row
  (d -> lora + rope), the absorbed query (heads * nope -> heads *
  lora), the values out of the latent (heads * lora -> heads * v) and
  the output (heads * v -> d);
* attention per key in the absorbed form that the latent cache
  requires: 2 * heads * (lora + rope) for the scores and 2 * heads *
  lora for the values, over ``pos + 1`` keys;
* the dense layers' SwiGLU MLP; in a MoE layer the router (d -> E) and
  the active experts only: ``num_experts_per_tok`` routed plus the
  shared ones, each a SwiGLU of ``moe_intermediate_size``;
* logits (d -> vocab) only where a token is sampled.

Nothing here depends on how the program computes the work: experts it
runs for tokens not routed to them do not count.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench import reference_mla_moe as reference

EMBED_STD = 0.02
NORM_STD = 0.1

# model dimension -> the published key
KEYS = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "kv_lora": "kv_lora_rank",
    "qk_nope": "qk_nope_head_dim", "qk_rope": "qk_rope_head_dim",
    "v_dim": "v_head_dim", "d_ff": "intermediate_size",
    "moe_ff": "moe_intermediate_size", "n_experts": "n_routed_experts",
    "n_shared": "n_shared_experts", "top_k": "num_experts_per_tok",
    "first_dense": "first_k_dense_replace", "vocab": "vocab_size",
}
# published settings the program implements in one way only
FIXED = {"q_lora_rank": None, "moe_layer_freq": 1, "scoring_func": "softmax",
         "topk_method": "greedy", "n_group": 1, "topk_group": 1,
         "routed_scaling_factor": 1, "tie_word_embeddings": False,
         "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6}


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    bad = {k: c.get(k) for k, v in FIXED.items() if c.get(k) != v}
    if bad:
        raise ValueError(f"the program runs only {FIXED}; the configuration "
                         f"has {bad}")
    m = {dim: int(c[key]) for dim, key in KEYS.items()}
    m["norm_topk"] = bool(c["norm_topk_prob"])
    m["rope_theta"] = float(c["rope_theta"])
    y = c.get("rope_scaling") or {}
    if y and y.get("type") != "yarn":
        raise ValueError(f"rope_scaling {y!r}: the program runs YaRN only")
    m["yarn_factor"] = float(y.get("factor", 0.0))
    m["yarn_original_max"] = int(y.get("original_max_position_embeddings", 0))
    m["yarn_beta_fast"] = float(y.get("beta_fast", 32))
    m["yarn_beta_slow"] = float(y.get("beta_slow", 1))
    m["yarn_mscale"] = float(y.get("mscale", 1))
    m["yarn_mscale_all_dim"] = float(y.get("mscale_all_dim", 0))
    return m


def model_fields(c: Dict[str, Any], m: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
        family="moe", mla=True, n_layers=m["n_layers"], d_model=m["d_model"],
        n_heads=m["n_heads"], n_kv_heads=m["n_heads"],
        head_dim=m["qk_nope"] + m["qk_rope"], d_ff=m["d_ff"],
        vocab_size=m["vocab"], kv_lora_rank=m["kv_lora"],
        qk_nope_dim=m["qk_nope"], qk_rope_dim=m["qk_rope"],
        v_head_dim=m["v_dim"], n_experts=m["n_experts"],
        n_shared_experts=m["n_shared"], top_k=m["top_k"],
        moe_d_ff=m["moe_ff"], first_dense_layers=m["first_dense"],
        norm_topk_prob=m["norm_topk"], rope_theta=m["rope_theta"],
        rope_yarn_factor=m["yarn_factor"],
        rope_yarn_original_max=m["yarn_original_max"],
        rope_yarn_beta_fast=m["yarn_beta_fast"],
        rope_yarn_beta_slow=m["yarn_beta_slow"],
        rope_yarn_mscale=m["yarn_mscale"],
        rope_yarn_mscale_all_dim=m["yarn_mscale_all_dim"],
        dtype=c["program"]["dtype"])


def shapes(m: Dict[str, Any]) -> Dict:
    """The serving program's checkpoint layout, each leaf with its
    standard deviation: 1/sqrt(fan_in) for projections and the router,
    0.02 for the embedding, 0.1 for the norm weights (applied as 1 + w)."""
    d, V, h = m["d_model"], m["vocab"], m["n_heads"]
    nope, rope, lora, vd = m["qk_nope"], m["qk_rope"], m["kv_lora"], m["v_dim"]
    E, f, fs = m["n_experts"], m["moe_ff"], m["n_shared"] * m["moe_ff"]
    out = {"embed": ((V, d), EMBED_STD), "unembed": ((d, V), 1 / np.sqrt(d)),
           "final_norm": ((d,), NORM_STD)}
    for grp, n in (("first", m["first_dense"]),
                   ("rest", m["n_layers"] - m["first_dense"])):
        a = f"blocks/{grp}/attn/"
        out.update({
            a + "norm": ((n, d), NORM_STD),
            a + "wq": ((n, d, h * (nope + rope)), 1 / np.sqrt(d)),
            a + "w_dkv": ((n, d, lora + rope), 1 / np.sqrt(d)),
            a + "kv_norm": ((n, lora), NORM_STD),
            a + "w_uk": ((n, lora, h * nope), 1 / np.sqrt(lora)),
            a + "w_uv": ((n, lora, h * vd), 1 / np.sqrt(lora)),
            a + "wo": ((n, h * vd, d), 1 / np.sqrt(h * vd)),
        })
    L0, ff = m["first_dense"], m["d_ff"]
    Ln = m["n_layers"] - L0
    out.update({
        "blocks/first/mlp/norm": ((L0, d), NORM_STD),
        "blocks/first/mlp/w_gate": ((L0, d, ff), 1 / np.sqrt(d)),
        "blocks/first/mlp/w_up": ((L0, d, ff), 1 / np.sqrt(d)),
        "blocks/first/mlp/w_down": ((L0, ff, d), 1 / np.sqrt(ff)),
        "blocks/rest/moe/norm": ((Ln, d), NORM_STD),
        "blocks/rest/moe/router": ((Ln, d, E), 1 / np.sqrt(d)),
        "blocks/rest/moe/w_gate": ((Ln, E, d, f), 1 / np.sqrt(d)),
        "blocks/rest/moe/w_up": ((Ln, E, d, f), 1 / np.sqrt(d)),
        "blocks/rest/moe/w_down": ((Ln, E, f, d), 1 / np.sqrt(f)),
        "blocks/rest/moe/sh_gate": ((Ln, d, fs), 1 / np.sqrt(d)),
        "blocks/rest/moe/sh_up": ((Ln, d, fs), 1 / np.sqrt(d)),
        "blocks/rest/moe/sh_down": ((Ln, fs, d), 1 / np.sqrt(fs)),
    })
    return out


def logit_gaps(m, c, params, seqs, length, control=False):
    return reference.logit_gaps(m, params, seqs, length, control=control)


# -- work ---------------------------------------------------------------------------


def attn_key_flops(m: Dict) -> int:
    """FLOPs per key per layer of the absorbed latent read."""
    h, lora, rope = m["n_heads"], m["kv_lora"], m["qk_rope"]
    return 2 * h * (lora + rope) + 2 * h * lora


def _token_base(m: Dict) -> int:
    """FLOPs of one token outside attention's keys and the logits."""
    d, h, lora = m["d_model"], m["n_heads"], m["kv_lora"]
    nope, rope, vd = m["qk_nope"], m["qk_rope"], m["v_dim"]
    proj = 2 * (d * h * (nope + rope) + d * (lora + rope) + h * nope * lora
                + h * lora * vd + h * vd * d)
    swiglu = 2 * 3 * d
    dense = proj + swiglu * m["d_ff"]
    moe = (proj + 2 * d * m["n_experts"]
           + (m["top_k"] + m["n_shared"]) * swiglu * m["moe_ff"])
    L0 = m["first_dense"]
    return L0 * dense + (m["n_layers"] - L0) * moe


def token_flops(m: Dict, pos: int, logits: bool) -> float:
    """FLOPs one token at position ``pos`` requires."""
    out = _token_base(m) + m["n_layers"] * (pos + 1) * attn_key_flops(m)
    if logits:
        out += 2 * m["d_model"] * m["vocab"]
    return float(out)


def span_flops(m: Dict, start: int, end: int, logits_last: bool) -> float:
    """FLOPs of the tokens at positions [start, end), logits for the last
    one only when ``logits_last``: ``token_flops`` summed in closed form."""
    n = end - start
    if n <= 0:
        return 0.0
    keys = (start + end - 1) * n / 2 + n          # sum of pos + 1
    out = n * _token_base(m) + m["n_layers"] * keys * attn_key_flops(m)
    if logits_last:
        out += 2 * m["d_model"] * m["vocab"]
    return float(out)
