"""Plain float32 reference of DeepSeek-V2's decoder (latent attention and
a mixture of experts), and its fp8 control.

The forward pass written out from the published description (HF
``modeling_deepseek.py``: ``DeepseekV2Attention``, ``MoEGate``,
``DeepseekV2YarnRotaryEmbedding``) in ``jax.numpy``, every matrix
product at ``Precision.HIGHEST``.  It imports nothing of the program;
the weights come from ``weights.make`` with the run's seed.

  x_0 = embed[token]
  per layer:  h = rms(x) * (1 + w_norm)
              q = h Wq  (heads of nope + rope);  [c, k_r] = h W_dkv
              c = rms(c) * (1 + w_kv_norm)
              k_nope = c W_uk, v = c W_uv  (per head: the non-absorbed
              form, independent of the program's absorbed read)
              q_rope, k_r rotated by YaRN RoPE (k_r one head shared by
              all), causal softmax([q_nope, q_rope] . [k_nope, k_r]
              * scale) v, x += o Wo
              h = rms(x) * (1 + w_norm)
              dense layer:  x += (silu(h W_gate) * h W_up) W_down
              MoE layer:    p = softmax(h W_router) over every expert in
              float32; the top k experts e with gates p_e (renormalised
              only where ``norm_topk_prob``); x += sum_e p_e *
              expert_e(h) + sum of the shared experts(h), each expert a
              SwiGLU.
  logits = (rms(x) * (1 + w_final)) W_unembed,  rms with eps 1e-6.

YaRN: frequencies theta^(-2i/r) where a rope pair turns more than
``beta_fast`` times over the original context, those over ``factor``
where it turns fewer than ``beta_slow`` times, linear between; cos and
sin scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim);
the softmax scale is (nope + rope)^-1/2 * mscale(factor,
mscale_all_dim)^2, with mscale(s, m) = 0.1 m ln s + 1.  Departures from
the published model (rope pairs split in halves, the (1 + w) norms) are
listed in the configuration file; the reference follows the program
there.

Each expert runs only on the tokens routed to it: the tokens are
grouped by expert on the host and padded to a few bucket sizes, so a
layer costs what its routed tokens need.  Padding positions of a batch
are routed to no expert.  ``control=True`` also runs the forward with
every linear layer's operands rounded to fp8 (``reference._mm``).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference import (ROW_BLOCK, _hi, _mm, _rms, _vocab_block_fn,
                             _vocab_blocks)

Q_BLOCK = 256          # query rows per attention block
MIN_BUCKET = 64        # smallest padded token group of one expert


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(m: Dict) -> Tuple[np.ndarray, float, float]:
    """(frequencies of the rope head, cos/sin scale, softmax scale)."""
    r, theta = m["qk_rope"], m["rope_theta"]
    freqs = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    scale = 1.0 / math.sqrt(m["qk_nope"] + m["qk_rope"])
    f = m["yarn_factor"]
    if not f:
        return freqs.astype(np.float32), 1.0, scale

    def dim(rot):            # the pair index that turns ``rot`` times
        return r * math.log(m["yarn_original_max"] / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim(m["yarn_beta_fast"])), 0)
    hi = min(math.ceil(dim(m["yarn_beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    freqs = freqs / f * ramp + freqs * (1 - ramp)
    cs = _mscale(f, m["yarn_mscale"]) / _mscale(f, m["yarn_mscale_all_dim"])
    if m["yarn_mscale_all_dim"]:
        scale *= _mscale(f, m["yarn_mscale_all_dim"]) ** 2
    return freqs.astype(np.float32), cs, scale


def _rope(x, freqs, cs):
    """x: (b, s, d) or (b, s, h, d) at positions 0..s-1, the two halves
    of the head rotated as pairs."""
    import jax.numpy as jnp
    s, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[None] * cs, jnp.sin(ang)[None] * cs
    if x.ndim == 4:
        cos, sin = cos[:, :, None], sin[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal attention, q, k: (b, s, h, dk), v: (b, s, h, dv), in
    blocks of Q_BLOCK query rows."""
    import jax
    import jax.numpy as jnp
    b, s, h, _ = q.shape
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                        precision=_hi()) * scale
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :hi],
                               precision=_hi()))
    return jnp.concatenate(outs, 1).reshape(b, s, -1)


def _swiglu(h, gate, up, down, fp8):
    import jax
    return _mm(jax.nn.silu(_mm(h, gate, fp8)) * _mm(h, up, fp8), down, fp8)


def _f32(tree, i):
    """Layer ``i`` of a stacked weight tree, in float32."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, False).astype(jnp.float32), tree)


@functools.lru_cache(maxsize=16)
def _layer_fn(dims: Tuple[Tuple[str, object], ...], moe: bool, fp8: bool):
    """One layer up to its routed experts: attention, then (dense) the
    MLP, or (MoE) the shared experts plus the routing.  Returns (x,
    normed rows, experts, gates) with rows flattened to (b * s, ...)."""
    import jax
    import jax.numpy as jnp
    m = dict(dims)
    h_, nope, rope = m["n_heads"], m["qk_nope"], m["qk_rope"]
    lora, vd = m["kv_lora"], m["v_dim"]
    freqs, cs, scale = rope_tables(m)

    def layer(x, attn, ffn, i):
        a = _f32(attn, i)
        b, s, d = x.shape
        h = _rms(x, a["norm"])
        q = _mm(h, a["wq"], fp8).reshape(b, s, h_, nope + rope)
        dkv = _mm(h, a["w_dkv"], fp8)
        c = _rms(dkv[..., :lora], a["kv_norm"])
        k_nope = _mm(c, a["w_uk"], fp8).reshape(b, s, h_, nope)
        v = _mm(c, a["w_uv"], fp8).reshape(b, s, h_, vd)
        k_rope = _rope(dkv[..., lora:], freqs, cs)[:, :, None]
        qq = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freqs, cs)],
                             -1)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h_, rope))], -1)
        x = x + _mm(_attention(qq, kk, v, scale), a["wo"], fp8)
        f = _f32({k: v for k, v in ffn.items()
                  if k not in ("w_gate", "w_up", "w_down")} if moe else ffn,
                 i)
        h = _rms(x, f["norm"])
        if not moe:
            x = x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], fp8)
            return x, None, None, None
        x = x + _swiglu(h, f["sh_gate"], f["sh_up"], f["sh_down"], fp8)
        rows = h.reshape(b * s, d)
        p = jax.nn.softmax(_mm(rows, f["router"], fp8), axis=-1)
        gates, idx = jax.lax.top_k(p, m["top_k"])
        if m["norm_topk"]:
            gates = gates / gates.sum(-1, keepdims=True)
        return x, rows, idx, gates

    return jax.jit(layer)


@functools.lru_cache(maxsize=64)
def _expert_fn(n: int, fp8: bool):
    """Expert ``e`` of MoE layer ``i`` over ``n`` (padded) routed rows,
    its gated output added into ``acc``; padded rows point past the end
    and add nothing."""
    import jax
    import jax.numpy as jnp

    def expert(acc, rows, ffn, i, e, tok, gate):
        w = {k: jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(ffn[k], i, 0, False), e, 0,
            False).astype(jnp.float32) for k in ("w_gate", "w_up", "w_down")}
        h = rows[jnp.minimum(tok, rows.shape[0] - 1)]
        y = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], fp8)
        return acc.at[tok].add(y * gate[:, None], mode="drop")

    return jax.jit(expert, donate_argnums=(0,))


def _bucket(n: int) -> int:
    return max(MIN_BUCKET, 1 << max(n - 1, 0).bit_length())


def _routed(acc, rows, idx, gates, valid, ffn, i, fp8):
    """Every expert over the valid rows routed to it, grouped on the host."""
    import jax.numpy as jnp
    idx, gates = np.asarray(idx), np.asarray(gates)
    for e in range(int(ffn["w_gate"].shape[1])):
        r, j = np.nonzero((idx == e) & valid[:, None])
        if not len(r):
            continue
        n = _bucket(len(r))
        tok = np.full(n, rows.shape[0], np.int32)
        gate = np.zeros(n, np.float32)
        tok[:len(r)], gate[:len(r)] = r, gates[r, j]
        acc = _expert_fn(n, fp8)(acc, rows, ffn, jnp.int32(i), jnp.int32(e),
                                 jnp.asarray(tok), jnp.asarray(gate))
    return acc


def hidden(m: Dict, params, tokens: np.ndarray, lens: Sequence[int],
           fp8: bool = False):
    """Final-layer activations (before the final norm) of a (b, s) batch;
    ``lens`` are the true lengths (later positions are padding)."""
    import jax.numpy as jnp
    dims = tuple(sorted(m.items()))
    b, s = tokens.shape
    valid = (np.arange(s)[None, :] < np.asarray(lens)[:, None]).reshape(-1)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    blocks = params["blocks"]
    for i in range(m["first_dense"]):
        x, *_ = _layer_fn(dims, False, fp8)(
            x, blocks["first"]["attn"], blocks["first"]["mlp"], jnp.int32(i))
    rest = blocks["rest"]
    for i in range(m["n_layers"] - m["first_dense"]):
        x, rows, idx, gates = _layer_fn(dims, True, fp8)(
            x, rest["attn"], rest["moe"], jnp.int32(i))
        acc = _routed(jnp.zeros_like(rows), rows, idx, gates, valid,
                      rest["moe"], i, fp8)
        x = x + acc.reshape(x.shape)
    return x


def logits(m: Dict, params, tokens: np.ndarray):
    """Reference logits of a (b, s) batch: small sizes only (tests)."""
    import jax.numpy as jnp
    x = _rms(hidden(m, params, tokens, [tokens.shape[1]] * len(tokens)),
             params["final_norm"].astype(jnp.float32))
    return jnp.matmul(x, params["unembed"].astype(jnp.float32),
                      precision=_hi())


def logit_gaps(m: Dict, params, seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
               length: int, control: bool = False
               ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """For each (prompt, served) pair: the gap, at each served token, of
    its reference logit below the reference's best; and with
    ``control`` the gap of the fp8 control's own choice there (else the
    same gaps again).  As ``reference.logit_gaps``, with this model's
    forward."""
    import jax.numpy as jnp
    full = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in seqs]
    toks = np.zeros((len(full), length), np.int32)
    for b, t in enumerate(full):
        toks[b, :len(t)] = t
    lens = [len(t) for t in full]
    xs = [hidden(m, params, toks, lens)]
    if control:
        xs.append(hidden(m, params, toks, lens, fp8=True))
    rows = [(b, len(p) - 1 + j, int(s[j])) for b, (p, s) in enumerate(seqs)
            for j in range(len(s))]
    unembed = params["unembed"]
    vocab = unembed.shape[1]
    cols = _vocab_blocks(vocab)
    fn = _vocab_block_fn(cols, control)
    fnorm = params["final_norm"].astype(jnp.float32)
    gap, gap_c = [], []
    for lo in range(0, len(rows), ROW_BLOCK):
        blk = rows[lo:lo + ROW_BLOCK]
        pad = blk + [blk[-1]] * (ROW_BLOCK - len(blk))
        bi = jnp.asarray([r[0] for r in pad])
        pi = jnp.asarray([r[1] for r in pad])
        served = jnp.asarray([r[2] for r in pad])
        h, hc = _rms(xs[0][bi, pi], fnorm), _rms(xs[-1][bi, pi], fnorm)
        parts = [fn(h, hc, unembed, jnp.int32(c))
                 for c in range(0, vocab, cols)]
        ref = jnp.concatenate([r for r, _ in parts], 1)
        best = ref.max(1)
        at = jnp.arange(ROW_BLOCK)
        gap.append(np.asarray(best - ref[at, served])[:len(blk)])
        if control:
            tok = jnp.concatenate([c for _, c in parts], 1).argmax(1)
            gap_c.append(np.asarray(best - ref[at, tok])[:len(blk)])
        else:
            gap_c.append(gap[-1])
    gap, gap_c = np.concatenate(gap), np.concatenate(gap_c)
    split = np.cumsum([len(s) for _, s in seqs])[:-1]
    return np.split(gap, split), np.split(gap_c, split)
