"""The dense decoder (``"family": "dense"`` in a configuration's
``program`` group).

A model family is one file in ``families/``, found by that name, with:

* ``dims(conf)``: the model's dimensions from the configuration file;
* ``model_fields(conf, m)``: the program's ``ModelConfig`` fields;
* ``shapes(m)``: the weight tree, ``{path: (shape, std)}``, which
  ``weights.py`` makes on the device from the seed;
* ``logit_gaps(m, conf, params, seqs, length, control)``: the plain
  reference's reading of served tokens (``reference.py``);
* ``token_flops`` and ``span_flops``: the work the tokens require
  (``flops.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from bench import flops, reference

token_flops = flops.token_flops
span_flops = flops.span_flops

EMBED_STD = 0.02
NORM_STD = 0.1

# model dimension -> the keys published configs use for it
ALIASES = {
    "n_layers": ("num_hidden_layers", "n_layer"),
    "d_model": ("hidden_size", "n_embd"),
    "n_heads": ("num_attention_heads", "n_head"),
    "n_kv_heads": ("num_key_value_heads",),
    "d_ff": ("intermediate_size", "n_inner"),
    "vocab": ("vocab_size",),
    "head_dim": ("head_dim",),
}


def _pick(c: Dict[str, Any], dim: str) -> Optional[int]:
    for k in ALIASES[dim]:
        if k in c:
            return int(c[k])
    return None


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    m = {dim: _pick(c, dim) for dim in ALIASES}
    if m["n_kv_heads"] is None:
        m["n_kv_heads"] = 1 if c.get("multi_query") else m["n_heads"]
    if m["head_dim"] is None:
        m["head_dim"] = m["d_model"] // m["n_heads"]
    m["mlp_gated"] = bool(c["program"]["mlp_gated"])
    missing = [k for k, v in m.items() if v is None]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    return m


def model_fields(c: Dict[str, Any], m: Dict[str, Any]) -> Dict[str, Any]:
    p = c["program"]
    return dict(
        family="dense", n_layers=m["n_layers"], d_model=m["d_model"],
        n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab"],
        mlp_gated=m["mlp_gated"], rope_theta=float(p["rope_theta"]),
        dtype=p["dtype"])


def shapes(m: Dict[str, Any]) -> Dict:
    """The serving program's checkpoint layout for a dense decoder (layer
    leaves stacked on a leading layer axis), with each leaf's standard
    deviation: 1/sqrt(fan_in) for projections, 0.02 for the embedding,
    and 0.1 for the norm weights, which the model applies as (1 + w)."""
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    out = {
        "embed": ((V, d), EMBED_STD),
        "unembed": ((d, V), 1 / np.sqrt(d)),
        "final_norm": ((d,), NORM_STD),
        "blocks/attn/norm": ((L, d), NORM_STD),
        "blocks/attn/wq": ((L, d, hq * hd), 1 / np.sqrt(d)),
        "blocks/attn/wk": ((L, d, hkv * hd), 1 / np.sqrt(d)),
        "blocks/attn/wv": ((L, d, hkv * hd), 1 / np.sqrt(d)),
        "blocks/attn/wo": ((L, hq * hd, d), 1 / np.sqrt(hq * hd)),
        "blocks/mlp/norm": ((L, d), NORM_STD),
        "blocks/mlp/w_up": ((L, d, f), 1 / np.sqrt(d)),
        "blocks/mlp/w_down": ((L, f, d), 1 / np.sqrt(f)),
    }
    if m["mlp_gated"]:
        out["blocks/mlp/w_gate"] = ((L, d, f), 1 / np.sqrt(d))
    return out


def logit_gaps(m, c, params, seqs, length, control=False):
    return reference.logit_gaps(m, float(c["program"]["rope_theta"]),
                                params, seqs, length, control=control)
