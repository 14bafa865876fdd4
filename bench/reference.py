"""Plain float32 reference of the dense decoder, and its fp8 control.

The forward pass the serving program computes, written out in
``jax.numpy`` with every matrix product at ``Precision.HIGHEST``, so
that on a TPU float32 stays float32.  It covers grouped-query and
multi-query attention (any query group size) and the plain (GELU) and
gated (SwiGLU) MLP.  It imports nothing of the program; the weights
come from ``weights.make`` with the run's seed.

The model, as the program defines it:
  x_0 = embed[token]
  per layer:  h = rms(x) * (1 + w_norm);  q, k, v = h Wq, h Wk, h Wv
              q, k rotated by RoPE (theta, the two halves of each head
              rotated as pairs), causal softmax(q k^T / sqrt(hd)) v,
              x += o Wo
              h = rms(x) * (1 + w_norm);  x += gelu_tanh(h W_up) W_down
              (gated: x += (silu(h W_gate) * h W_up) W_down)
  logits = (rms(x) * (1 + w_final)) W_unembed,  rms with eps 1e-6.
Departures from the published models are listed in each configuration
file (``configs/*.json``); the reference follows the program there.

It runs layer by layer over a batch of whole sequences (prompt plus the
served tokens), with attention in blocks of query rows, so that only
one layer's float32 weights and one block's scores are live at a time.

``logit_gaps`` reads, at each served position, how far the served
token's reference logit lies below the reference's best.  With
``control=True`` it also runs the same forward with every linear
layer's operands rounded to fp8 (e4m3, one scale per row of the
activations and per column of the weights) and reads the gap of the
token that fp8 puts first: the control that the comparison must fail.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np

EPS = 1e-6
Q_BLOCK = 256          # query rows per attention block
ROW_BLOCK = 512        # logit rows per block (the last one is padded)


def _hi():
    import jax
    return jax.lax.Precision.HIGHEST


def _fp8(x, axis):
    """x rounded to fp8 e4m3 with one absmax scale per slice along
    ``axis`` (kept in float32)."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    import jax.numpy as jnp
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.matmul(x, w, precision=_hi())


def _rms(x, w):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1 + w)


def _rope(x, theta):
    """x: (b, s, h, d) at positions 0..s-1."""
    import jax.numpy as jnp
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs    # (s, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention, q: (b, s, hq, d), k/v: (b, s, hkv, d), in
    blocks of Q_BLOCK query rows."""
    import jax
    import jax.numpy as jnp
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d) / np.sqrt(d)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg[:, lo:hi], k[:, :hi],
                        precision=_hi())
        mask = (jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None])
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhgqk,bkhd->bqhgd", p, v[:, :hi],
                               precision=_hi()))
    return jnp.concatenate(outs, 1).reshape(b, s, hq * d)


@functools.lru_cache(maxsize=8)
def _layer_fn(dims: Tuple[Tuple[str, object], ...], theta: float,
              fp8: bool):
    import jax
    import jax.numpy as jnp
    m = dict(dims)
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]

    def layer(x, blocks, i):
        p = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False).astype(
                jnp.float32), blocks)
        b, s, _ = x.shape
        a = p["attn"]
        h = _rms(x, a["norm"])
        q = _mm(h, a["wq"], fp8).reshape(b, s, hq, hd)
        k = _mm(h, a["wk"], fp8).reshape(b, s, hkv, hd)
        v = _mm(h, a["wv"], fp8).reshape(b, s, hkv, hd)
        o = _attention(_rope(q, theta), _rope(k, theta), v)
        x = x + _mm(o, a["wo"], fp8)
        f = p["mlp"]
        h = _rms(x, f["norm"])
        if "w_gate" in f:
            hh = jax.nn.silu(_mm(h, f["w_gate"], fp8)) * _mm(h, f["w_up"],
                                                             fp8)
        else:
            hh = jax.nn.gelu(_mm(h, f["w_up"], fp8), approximate=True)
        return x + _mm(hh, f["w_down"], fp8)

    return jax.jit(layer)


@functools.lru_cache(maxsize=8)
def _vocab_block_fn(n_cols: int, control: bool):
    """Logits of normed rows over one block of the vocabulary: the
    reference's, and (control) fp8's.  The fp8 scales are per column of
    the unembedding, so a block quantizes as the whole matrix would."""
    import jax
    import jax.numpy as jnp

    def block(h, hc, unembed, lo):
        w = jax.lax.dynamic_slice_in_dim(unembed, lo, n_cols, 1).astype(
            jnp.float32)
        ref = jnp.matmul(h, w, precision=_hi())
        return ref, (_mm(hc, w, True) if control else ref)

    return jax.jit(block)


def _vocab_blocks(vocab: int, most: int = 32768) -> int:
    """Columns per vocabulary block: the largest divisor of ``vocab`` not
    above ``most``."""
    n = -(-vocab // most)
    while vocab % n:
        n += 1
    return vocab // n


def hidden(m: Dict, theta: float, params, tokens: np.ndarray,
           fp8: bool = False):
    """Final-layer activations (before the final norm) of a (b, s) batch
    of whole sequences."""
    import jax.numpy as jnp
    dims = tuple(sorted(m.items()))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["n_layers"]):
        x = _layer_fn(dims, theta, fp8)(x, params["blocks"], jnp.int32(i))
    return x


def logits(m: Dict, theta: float, params, tokens: np.ndarray):
    """Reference logits of a (b, s) batch: small sizes only (tests)."""
    import jax.numpy as jnp
    x = _rms(hidden(m, theta, params, tokens),
             params["final_norm"].astype(jnp.float32))
    return jnp.matmul(x, params["unembed"].astype(jnp.float32),
                      precision=_hi())


def logit_gaps(m: Dict, theta: float, params, seqs: Sequence[Tuple[
        np.ndarray, np.ndarray]], length: int, control: bool = False
        ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """For each (prompt, served) pair: the gap, at each served token, of
    its reference logit below the reference's best; and with
    ``control`` the gap of the fp8 control's own choice there (else the
    same gaps again)."""
    import jax.numpy as jnp
    full = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in seqs]
    # one shape per cell (every sequence padded to ``length``), so a run
    # finds the reference's programs compiled by the runs before it.
    toks = np.zeros((len(full), length), np.int32)
    for b, t in enumerate(full):
        toks[b, :len(t)] = t
    xs = [hidden(m, theta, params, toks)]
    if control:
        xs.append(hidden(m, theta, params, toks, fp8=True))
    # the rows that predicted each served token: prompt_len - 1 + j
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
