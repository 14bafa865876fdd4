"""Work a dense decoder requires, computed from its shapes.

FLOPs count a multiply-add as two.  A token at position p (0-based)
attends to p + 1 keys: QK^T and PV each cost 2 * (p + 1) * n_heads *
head_dim per layer.  Logits are required only where a token is sampled:
every decode token and the last token of each prompt.  Nothing here
depends on how the program computes the work, so a change that removes
work from the program does not move the denominator.
"""

from __future__ import annotations

from typing import Dict


def layer_params(m: Dict) -> int:
    d, f = m["d_model"], m["d_ff"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attn = d * hq * hd * 2 + d * hkv * hd * 2
    mlp = d * f * (3 if m["mlp_gated"] else 2)
    return attn + mlp


def params(m: Dict) -> int:
    """All parameters: layers, embedding and unembedding (norm weights
    left out)."""
    return m["n_layers"] * layer_params(m) + 2 * m["vocab"] * m["d_model"]


def token_flops(m: Dict, pos: int, logits: bool) -> float:
    """FLOPs one token at position ``pos`` requires."""
    per_layer = (2 * layer_params(m)
                 + 4 * (pos + 1) * m["n_heads"] * m["head_dim"])
    out = m["n_layers"] * per_layer
    if logits:
        out += 2 * m["d_model"] * m["vocab"]
    return float(out)


def span_flops(m: Dict, start: int, end: int, logits_last: bool) -> float:
    """FLOPs of the tokens at positions [start, end), logits for the last
    one only when ``logits_last``: sums ``token_flops`` in closed form."""
    n = end - start
    if n <= 0:
        return 0.0
    pos_sum = (start + end - 1) * n / 2          # sum of positions
    per = 2 * layer_params(m) * n + 4 * (pos_sum + n) * m["n_heads"] \
        * m["head_dim"]
    out = m["n_layers"] * per
    if logits_last:
        out += 2 * m["d_model"] * m["vocab"]
    return float(out)
