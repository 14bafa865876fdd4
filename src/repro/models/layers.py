"""Shared transformer layers: norms, RoPE, blocked attention (XLA path),
GQA/MQA/MLA attention blocks, SwiGLU MLP, and scatter-dispatch MoE.

Everything is pure-functional: ``*_decls`` builds the declarative param
tree (see ``models/params.py``), ``*_apply`` consumes the concrete dict.
Stacked leading dims (for scan-over-layers) are threaded via ``stack``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import datapack
from ..kernels.platform import paged_decode_kernel
from ..distributed.sharding import (constrain, gather_parts, part_index,
                                    psum_parts)
from .params import Decl

F32 = jnp.float32


# --- primitives -----------------------------------------------------------------


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * lax.rsqrt(var + eps)) * (1.0 + w.astype(F32))).astype(x.dtype)


def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float,
         freqs: Optional[np.ndarray] = None, mscale: float = 1.0
         ) -> jnp.ndarray:
    """x: (..., s, h, d) or (..., s, d); pos: (s,) or (b, s).  ``freqs``
    (d/2,) replaces theta's frequencies and ``mscale`` scales cos and
    sin (YaRN, ``yarn_freqs``)."""
    d = x.shape[-1]
    half = d // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[..., None].astype(F32) * jnp.asarray(freqs, F32)
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    if x.ndim - cos.ndim == 2:                        # head axis present
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention magnitude correction (deepseek-v2's
    ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(d: int, theta: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies of a head of width ``d`` (deepseek-v2's
    ``DeepseekV2YarnRotaryEmbedding``): theta's own frequencies where a
    pair turns more than ``beta_fast`` times over the original context,
    theta's / ``factor`` where it turns fewer than ``beta_slow`` times,
    and a linear blend between."""
    def corr_dim(rot):
        return d * math.log(original_max / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(corr_dim(beta_fast)), 0)
    hi = min(math.ceil(corr_dim(beta_slow)), d - 1)
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    mask = 1.0 - ramp                   # 1: keep theta's frequency
    return (extra / factor * (1 - mask) + extra * mask).astype(np.float32)


def mla_rope(cfg, x: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """RoPE over the MLA rope head: theta's, or YaRN's where the config
    scales it."""
    if not cfg.rope_yarn_factor:
        return rope(x, pos, cfg.rope_theta)
    f = cfg.rope_yarn_factor
    freqs = yarn_freqs(x.shape[-1], cfg.rope_theta, f,
                       cfg.rope_yarn_original_max, cfg.rope_yarn_beta_fast,
                       cfg.rope_yarn_beta_slow)
    mscale = (yarn_mscale(f, cfg.rope_yarn_mscale)
              / yarn_mscale(f, cfg.rope_yarn_mscale_all_dim))
    return rope(x, pos, cfg.rope_theta, freqs, mscale)


def mla_scale(cfg) -> float:
    """The MLA softmax scale: 1/sqrt(qk head width), times
    yarn_mscale(factor, mscale_all_dim)^2 under YaRN."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.rope_yarn_factor and cfg.rope_yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_yarn_factor,
                             cfg.rope_yarn_mscale_all_dim) ** 2
    return scale


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(gate.astype(F32)).astype(gate.dtype) * up


# --- blocked attention (XLA path) ------------------------------------------------


def _block_pairs(nq: int, nk_per_q, window_blocks: Optional[int]
                 ) -> np.ndarray:
    """Static (qi, ki) list for causal (+ optional banded window) blocks —
    the beyond-paper block-skipping optimization (§Perf)."""
    pairs = []
    for qi in range(nq):
        lo = 0 if window_blocks is None else max(0, qi - window_blocks)
        for ki in range(lo, qi + 1):
            pairs.append((qi, ki))
    return np.asarray(pairs, np.int32)


# module-level switch for bf16 probabilities (kept out of the custom_vjp
# signature; set per-call by attention_apply from cfg.attn_p_bf16).
_P_BF16 = [False]


def _attn_pad(q, k, v, block_q, block_k):
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    sqp, skp = datapack.round_up(sq, block_q), datapack.round_up(sk, block_k)
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    if skp != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    return q, k, v, block_q, block_k


def _blk_mask(qi, ki, block_q, block_k, q_off, sk, causal, window):
    qpos = q_off + qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = kpos < sk
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


def _ki_range(qi, nq, nk, causal, window, block_q, block_k, q_off,
              block_skip):
    """Static kv-block range for query block qi."""
    if not (block_skip and causal):
        return 0, nk
    q_lo = q_off + qi * block_q
    q_hi = q_off + (qi + 1) * block_q - 1
    hi = min(nk - 1, q_hi // block_k)
    lo = 0
    if window is not None:
        lo = max(0, (q_lo - window + 1) // block_k)
    return lo, hi + 1


def _attention_fwd_impl(q, k, v, causal, window, scale, block_q, block_k,
                        block_skip):
    """Blocked online-softmax forward.  One python-unrolled loop over q
    blocks, each with a lax.scan over its (statically bounded) kv blocks
    carrying only block-local (m, l, acc) — no full-size carries, so
    backward residuals stay O(block).  Returns (out, lse)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    g = hq // hkv
    q, k, v, block_q, block_k = _attn_pad(q, k, v, block_q, block_k)
    sqp, skp = q.shape[2], k.shape[2]
    nq, nk = sqp // block_q, skp // block_k
    q_off = sk - sq

    qg = q.reshape(b, hkv, g, sqp, d).astype(F32) * scale
    kf, vf = k.astype(F32), v.astype(F32)

    outs, lses = [], []
    for qi in range(nq):
        qb = qg[:, :, :, qi * block_q:(qi + 1) * block_q]
        lo, hi = _ki_range(qi, nq, nk, causal, window, block_q, block_k,
                           q_off, block_skip)

        def body(st, ki, qb=qb, qi=qi):
            m_p, l_p, o_p = st
            kb = lax.dynamic_slice_in_dim(kf, ki * block_k, block_k, axis=2)
            vb = lax.dynamic_slice_in_dim(vf, ki * block_k, block_k, axis=2)
            s = jnp.einsum("bhgqd,bhcd->bhgqc", qb, kb)
            mask = _blk_mask(qi, ki, block_q, block_k, q_off, sk, causal,
                             window)
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_c = jnp.max(s, -1, keepdims=True)
            m_n = jnp.maximum(m_p, m_c)
            m_safe = jnp.where(jnp.isfinite(m_n), m_n, 0.0)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
            alpha = jnp.where(jnp.isfinite(m_p), jnp.exp(m_p - m_safe), 0.0)
            l_n = l_p * alpha + jnp.sum(p, -1, keepdims=True)
            if _P_BF16[0]:
                # §Perf: bf16 probabilities into the PV matmul — halves
                # the score-matrix HBM traffic at <1e-2 output error.
                pv = jnp.einsum("bhgqc,bhcv->bhgqv",
                                p.astype(jnp.bfloat16),
                                vb.astype(jnp.bfloat16),
                                preferred_element_type=F32)
            else:
                pv = jnp.einsum("bhgqc,bhcv->bhgqv", p, vb)
            o_n = o_p * alpha + pv
            return (m_n, l_n, o_n), None

        m0 = jnp.full((b, hkv, g, block_q, 1), -jnp.inf, F32)
        l0 = jnp.zeros((b, hkv, g, block_q, 1), F32)
        o0 = jnp.zeros((b, hkv, g, block_q, dv), F32)
        (m_f, l_f, o_f), _ = lax.scan(body, (m0, l0, o0),
                                      jnp.arange(lo, hi))
        l_safe = jnp.where(l_f == 0.0, 1.0, l_f)
        outs.append(o_f / l_safe)
        m_safe = jnp.where(jnp.isfinite(m_f), m_f, 0.0)
        lses.append(m_safe + jnp.log(l_safe))

    out = jnp.concatenate(outs, axis=3)[:, :, :, :sq]
    lse = jnp.concatenate(lses, axis=3)[:, :, :, :sq]
    return (out.reshape(b, hq, sq, dv).astype(q.dtype),
            lse.reshape(b, hq, sq, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention_xla_core(q, k, v, causal, window, scale,
                        block_q, block_k, block_skip):
    """Blocked online-softmax attention in pure XLA (the dry-run path)
    with a flash-style custom VJP: backward saves only (q, k, v, out,
    lse) and recomputes scores blockwise — O(block) residual memory,
    matching the Pallas kernel's memory behavior.

    q: (b, hq, sq, d); k: (b, hkv, sk, d); v: (b, hkv, sk, dv).
    ``block_skip`` restricts the blocked loops to causally-active
    (banded, for sliding windows) block pairs — the §Perf lever.
    """
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, _ = _attention_fwd_impl(q, k, v, causal, window, scale, block_q,
                                 block_k, block_skip)
    return out


def _attention_vjp_fwd(q, k, v, causal, window, scale, block_q, block_k,
                       block_skip):
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, lse = _attention_fwd_impl(q, k, v, causal, window, scale, block_q,
                                   block_k, block_skip)
    return out, (q, k, v, out, lse)


def _attention_vjp_bwd(causal, window, scale, block_q, block_k, block_skip,
                       res, do):
    q, k, v, out, lse = res
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    in_dtype = q.dtype

    qp, kp, vp, block_q, block_k = _attn_pad(q, k, v, block_q, block_k)
    sqp, skp = qp.shape[2], kp.shape[2]
    nq, nk = sqp // block_q, skp // block_k
    q_off = sk - sq

    qg = qp.reshape(b, hkv, g, sqp, d).astype(F32)
    kf, vf = kp.astype(F32), vp.astype(F32)
    pad_q = sqp - sq
    dog = jnp.pad(do.astype(F32).reshape(b, hkv, g, sq, dv),
                  ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    og = jnp.pad(out.astype(F32).reshape(b, hkv, g, sq, dv),
                 ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    lseg = jnp.pad(lse.astype(F32).reshape(b, hkv, g, sq, 1),
                   ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    D = jnp.sum(dog * og, axis=-1, keepdims=True)        # (b,hkv,g,sqp,1)

    def p_block(qi, ki):
        qb = lax.dynamic_slice_in_dim(qg, qi * block_q, block_q, 3) * scale
        kb = lax.dynamic_slice_in_dim(kf, ki * block_k, block_k, 2)
        s = jnp.einsum("bhgqd,bhcd->bhgqc", qb, kb)
        mask = _blk_mask(qi, ki, block_q, block_k, q_off, sk, causal, window)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        lse_b = lax.dynamic_slice_in_dim(lseg, qi * block_q, block_q, 3)
        return jnp.where(jnp.isfinite(s), jnp.exp(s - lse_b), 0.0)

    # pass 1: dq per q block (loop q, scan its kv range)
    dq_blocks = []
    for qi in range(nq):
        lo, hi = _ki_range(qi, nq, nk, causal, window, block_q, block_k,
                           q_off, block_skip)

        def body(dq_b, ki, qi=qi):
            p = p_block(qi, ki)
            vb = lax.dynamic_slice_in_dim(vf, ki * block_k, block_k, 2)
            kb = lax.dynamic_slice_in_dim(kf, ki * block_k, block_k, 2)
            do_b = lax.dynamic_slice_in_dim(dog, qi * block_q, block_q, 3)
            D_b = lax.dynamic_slice_in_dim(D, qi * block_q, block_q, 3)
            dp = jnp.einsum("bhgqv,bhcv->bhgqc", do_b, vb)
            ds = p * (dp - D_b)
            return dq_b + jnp.einsum("bhgqc,bhcd->bhgqd", ds, kb) * scale, \
                None

        dq0 = jnp.zeros((b, hkv, g, block_q, d), F32)
        dq_b, _ = lax.scan(body, dq0, jnp.arange(lo, hi))
        dq_blocks.append(dq_b)
    dq = jnp.concatenate(dq_blocks, axis=3)[:, :, :, :sq]
    dq = dq.reshape(b, hq, sq, d).astype(in_dtype)

    # pass 2: dk/dv per kv block (loop kv, scan its q range)
    dk_blocks, dv_blocks = [], []
    for ki in range(nk):
        if block_skip and causal:
            # queries that can see kv block ki
            qlo = max(0, (ki * block_k - q_off) // block_q)
            if window is not None:
                k_hi_pos = (ki + 1) * block_k - 1
                qhi = min(nq - 1, (k_hi_pos + window - 1 - q_off) // block_q)
            else:
                qhi = nq - 1
            qlo, qhi = qlo, qhi + 1
        else:
            qlo, qhi = 0, nq

        def body(st, qi, ki=ki):
            dk_b, dv_b = st
            p = p_block(qi, ki)
            do_b = lax.dynamic_slice_in_dim(dog, qi * block_q, block_q, 3)
            D_b = lax.dynamic_slice_in_dim(D, qi * block_q, block_q, 3)
            vb = lax.dynamic_slice_in_dim(vf, ki * block_k, block_k, 2)
            qb = lax.dynamic_slice_in_dim(qg, qi * block_q, block_q, 3)
            dv_b = dv_b + jnp.einsum("bhgqc,bhgqv->bhcv", p, do_b)
            dp = jnp.einsum("bhgqv,bhcv->bhgqc", do_b, vb)
            ds = p * (dp - D_b)
            dk_b = dk_b + jnp.einsum("bhgqc,bhgqd->bhcd", ds, qb) * scale
            return (dk_b, dv_b), None

        dk0 = jnp.zeros((b, hkv, block_k, d), F32)
        dv0 = jnp.zeros((b, hkv, block_k, dv), F32)
        (dk_b, dv_b), _ = lax.scan(body, (dk0, dv0), jnp.arange(qlo, qhi))
        dk_blocks.append(dk_b)
        dv_blocks.append(dv_b)
    dk = jnp.concatenate(dk_blocks, axis=2)[:, :, :sk].astype(in_dtype)
    dv = jnp.concatenate(dv_blocks, axis=2)[:, :, :sk].astype(in_dtype)
    return dq, dk, dv


_attention_xla_core.defvjp(_attention_vjp_fwd, _attention_vjp_bwd)


def attention_xla(q, k, v, causal=True, window=None, scale=None,
                  block_q=512, block_k=512, block_skip=False):
    """Keyword-friendly wrapper over the custom-VJP core."""
    return _attention_xla_core(q, k, v, causal, window, scale,
                               int(block_q), int(block_k), bool(block_skip))


def attention_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_valid: jnp.ndarray,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-step decode attention over a cache.

    q: (b, hq, 1, d); k: (b, hkv, S, d); v: (b, hkv, S, dv);
    kv_valid: (b, S) bool or (S,) — which cache slots hold real keys.
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, g, sq, d).astype(F32) * scale
    s = jnp.einsum("bhgqd,bhcd->bhgqc", qg, k.astype(F32))
    if kv_valid.ndim == 1:
        mask = kv_valid[None, None, None, None, :]
    else:
        mask = kv_valid[:, None, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqc,bhcv->bhgqv", p, v.astype(F32))
    return o.reshape(b, hq, sq, -1).astype(q.dtype)


def attention_masked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     mask: jnp.ndarray,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Multi-query attention over a cache with an explicit per-row mask.

    q: (b, hq, sq, d); k, v: (b, hkv, S, dv); mask: (b, sq, S) bool.
    Generalizes ``attention_decode`` to sq > 1 (chunked prefill: a chunk
    of queries at positions pos..pos+sq-1 against the gathered cache).
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, g, sq, d).astype(F32) * scale
    s = jnp.einsum("bhgqd,bhcd->bhgqc", qg, k.astype(F32))
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqc,bhcv->bhgqv", p, v.astype(F32))
    return o.reshape(b, hq, sq, -1).astype(q.dtype)


# --- GQA attention block -----------------------------------------------------------


def attention_decls(cfg, stack: Tuple[int, ...] = ()) -> Dict[str, Decl]:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ax = ("stack",) * len(stack)
    decls = {
        "norm": Decl(stack + (d,), ax + ("embed",), init="zeros"),
        "wo": Decl(stack + (hq * hd, d), ax + ("heads", "embed")),
    }
    if cfg.fuse_qkv and hq == hkv:
        decls["wqkv"] = Decl(stack + (d, 3 * hq * hd), ax + ("embed", "heads"))
        if cfg.qkv_bias:
            decls["bqkv"] = Decl(stack + (3 * hq * hd,), ax + ("heads",),
                                 init="zeros")
    else:
        decls["wq"] = Decl(stack + (d, hq * hd), ax + ("embed", "heads"))
        decls["wk"] = Decl(stack + (d, hkv * hd), ax + ("embed", "kv_heads"))
        decls["wv"] = Decl(stack + (d, hkv * hd), ax + ("embed", "kv_heads"))
        if cfg.qkv_bias:
            decls["bq"] = Decl(stack + (hq * hd,), ax + ("heads",), init="zeros")
            decls["bk"] = Decl(stack + (hkv * hd,), ax + ("kv_heads",),
                               init="zeros")
            decls["bv"] = Decl(stack + (hkv * hd,), ax + ("kv_heads",),
                               init="zeros")
    if cfg.qk_norm:
        decls["q_norm"] = Decl(stack + (hd,), ax + (None,), init="zeros")
        decls["k_norm"] = Decl(stack + (hd,), ax + (None,), init="zeros")
    return decls


def _qkv(cfg, p, x):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if "wqkv" in p:
        qkv = x @ p["wqkv"]
        if "bqkv" in p:
            qkv = qkv + p["bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
    else:
        q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    return q, k, v


def attention_apply(cfg, p, x, *, window: Optional[int] = None,
                    theta: Optional[float] = None,
                    cache: Optional[Dict[str, jnp.ndarray]] = None,
                    pos: Optional[jnp.ndarray] = None):
    """Pre-norm attention with residual.  Train/prefill when cache is
    None; single-token decode otherwise (cache dict: k, v, and ``pos`` is
    the scalar write position).  Returns (y, new_cache)."""
    theta = theta if theta is not None else cfg.rope_theta
    b, s, d = x.shape
    h = rmsnorm(x, p["norm"])
    h = constrain(h, "batch", None, "embed")
    q, k, v = _qkv(cfg, p, h)
    if cache is None:
        positions = jnp.arange(s)
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if cfg.attn_head_constraints and cfg.n_heads % 16 == 0 \
                and (cfg.n_kv_heads % 16 == 0 or cfg.n_kv_heads == 1):
            q = constrain(q, "batch", "heads", None, None)
            k = constrain(k, "batch",
                          "kv_heads" if cfg.n_kv_heads > 1 else None,
                          None, None)
            v = constrain(v, "batch",
                          "kv_heads" if cfg.n_kv_heads > 1 else None,
                          None, None)
        _P_BF16[0] = cfg.attn_p_bf16
        o = attention_xla(q, k, v, causal=True, window=window,
                          block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                          block_skip=cfg.attn_block_skip)
        _P_BF16[0] = False
        new_cache = None
    else:
        q = rope(q, pos[None], theta)          # (b, 1, hq, hd)
        k = rope(k, pos[None], theta)
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)            # (b, hkv, 1, hd)
        v = v.transpose(0, 2, 1, 3)
        ck, cv = cache["k"], cache["v"]
        S = ck.shape[2]
        if window is not None and S == window:
            slot = pos % window                # rolling ShiftReg cache (F6)
            valid = (jnp.arange(S) < pos + 1) | (pos >= window)
            # exclude the slot being overwritten when pos >= window
        else:
            slot = pos
            valid = jnp.arange(S) <= pos
        if cfg.kv_cache_dtype == "int8":
            kq, ks = _kv_quantize(k)
            vq, vs = _kv_quantize(v)
            ck = lax.dynamic_update_slice_in_dim(ck, kq, slot, 2)
            cv = lax.dynamic_update_slice_in_dim(cv, vq, slot, 2)
            cks = lax.dynamic_update_slice_in_dim(cache["k_scale"], ks,
                                                  slot, 2)
            cvs = lax.dynamic_update_slice_in_dim(cache["v_scale"], vs,
                                                  slot, 2)
            k_full = _kv_dequantize(ck, cks, q.dtype)
            v_full = _kv_dequantize(cv, cvs, q.dtype)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        else:
            ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                 slot, 2)
            cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                 slot, 2)
            k_full, v_full = ck.astype(q.dtype), cv.astype(q.dtype)
            new_cache = {"k": ck, "v": cv}
        if cfg.decode_flash:
            # sq=1 flash fast path: kv-only grid, GQA group folded into
            # the q block, out-of-window/future kv blocks skipped.  Ring
            # layout iff the cache is the rolled sliding-window buffer.
            from ..kernels.flash_attention import flash_attention_decode
            ring = window is not None and S == window
            o = flash_attention_decode(q, k_full, v_full, pos,
                                       window=window if ring else None,
                                       ring=ring)
        else:
            o = attention_decode(q, k_full, v_full, valid)
    y = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
    y = y @ p["wo"]
    y = constrain(y, "batch", None, "embed")
    return x + y, new_cache


def _ring_page_base(pos: jnp.ndarray, page: int, n_blocks: int
                    ) -> jnp.ndarray:
    """Logical base position of each ring-table slot.

    Slot j of a ring-of-pages table holds the LATEST logical page
    l ≡ j (mod n_blocks) with l <= pos // page — the page-granular
    analogue of the dense ring cache's mask-aware slot math.  Slots whose
    reconstructed page is negative (never written yet) get a negative
    base, which readers mask via kpos >= 0.  pos: (b,); returns (b,
    n_blocks) int32.
    """
    cur = pos[:, None] // page                               # (b, 1)
    j = jnp.arange(n_blocks)[None, :]
    l = cur - ((cur - j) % n_blocks)                         # (b, nb)
    return (l * page).astype(jnp.int32)


def attention_apply_paged(cfg, p, x, *, window: Optional[int] = None,
                          theta: Optional[float] = None,
                          pages: Dict[str, jnp.ndarray],
                          block_tab: jnp.ndarray, pos: jnp.ndarray,
                          ring: bool = False,
                          last_idx: Optional[jnp.ndarray] = None,
                          cache_offset: Optional[jnp.ndarray] = None,
                          verify: bool = False,
                          layer: Tuple[Any, ...] = ()):
    """Pre-norm attention against a *paged* KV cache.

    x: (b, s, d) — s == 1 is a decode step, s > 1 a prefill chunk whose
    tokens sit at positions pos..pos+s-1.  ``pages``: the pool leaves —
    {"k", "v"} of shape (*stack, n_pages, hkv, page, hd), plus
    {"k_scale", "v_scale"} (*stack, n_pages, hkv, page, 1) when
    ``cfg.kv_cache_dtype == "int8"`` (pages carry per-position scales).
    ``layer`` indexes the leading ``stack`` axes (one index per axis,
    static or traced): the layer loop hands in the whole stack of every
    layer's pools and this layer's index, and the layer writes and reads
    its own pool inside the stack, so the stack is updated in place with
    no per-layer slice or write-back (see ``transformer._scan_blocks``).
    ``layer=()`` takes one layer's pools.
    ``block_tab``: (b, n_blocks) int32, entries >= n_pages meaning
    unallocated (writes through them drop; reads are clamped and
    masked).  ``pos``: (b,) int32 start position per row.  ``last_idx``
    (chunk mode): per-row index of the last TRUE token in the chunk —
    padded tail positions are never written.  ``cache_offset`` (chunk
    mode, prefix cache): per-row (b,) position below which the cache is
    *read-only* — a prefix-cache hit attaches shared pages whose K/V
    already exist, and the catch-up prefill must never rewrite them
    (rewriting would perturb the original writer's bits for every other
    sequence aliasing the page); writes at positions < cache_offset are
    masked to the invalid page id and dropped.

    ``ring=False`` (flat layout): logical page j lives at table entry j;
    sliding windows apply the (qpos - window, qpos] band in the mask,
    trading the window-bounded footprint for page-granular alloc/free.
    ``ring=True`` (window-bounded layout, gemma3 local layers): table
    entry j holds logical page l ≡ j (mod n_blocks) and pages are reused
    in place once the table wraps, so the layer's page count stays
    O(window/page) forever; readers reconstruct each entry's logical
    base position from ``pos`` (see ``_ring_page_base``).

    The gather reads take the pool's rows strictly before ``pos``
    concatenated with the current chunk's own K/V, so numerics mirror the
    dense path's rounding exactly: a prefill chunk (s > 1) attends its
    own positions at full precision (dense prefill never rounds
    within-prompt K/V through the cache), while a decode step (s == 1)
    attends the pool-rounded values (dense decode reads the quantized/
    bf16 cache).  On the flat layout the write touches no row before
    ``pos``, so the gather may follow it; a ring's write can relabel an
    entry the chunk still reads, so there the gather runs first.

    ``verify=True`` (speculative decode): s == k rows behave like k
    *sequential decode steps* scored at once — own K/V is pool-rounded
    (each draft token's KV would have been read back through the cache
    had it been decoded one step at a time) and the flash kernel runs at
    sq == k, so accepted tokens are bit-identical to non-speculative
    greedy decode.  Returns (y, new_pages).
    """
    theta = theta if theta is not None else cfg.rope_theta
    b, s, d = x.shape
    h = rmsnorm(x, p["norm"])
    with jax.named_scope("attn_proj"):
        q, k, v = _qkv(cfg, p, h)                    # (b, s, h*, hd)
    positions = pos[:, None] + jnp.arange(s)         # (b, s)
    pos_h = positions[:, :, None]                    # broadcast over heads
    q = rope(q, pos_h, theta).transpose(0, 2, 1, 3)
    k = rope(k, pos_h, theta).transpose(0, 2, 1, 3)  # (b, hkv, s, hd)
    v = v.transpose(0, 2, 1, 3)

    quantized = cfg.kv_cache_dtype == "int8"
    n_pages, hkv, page, hd = pages["k"].shape[len(layer):]
    n_blocks = block_tab.shape[1]
    kernel = ((s == 1 or verify) and cache_offset is None
              and paged_decode_kernel(cfg))
    bt = jnp.minimum(block_tab, n_pages - 1)
    S = n_blocks * page

    def gather(pool):
        g = pool[layer + (bt,)]                      # (b, nb, hkv, page, X)
        return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, S, -1)

    rows = None
    if ring and not kernel:
        # the PRE-write ring: gather first, and make the write wait for
        # the gathered rows (the barrier), so the pools are written in
        # place rather than copied to keep the old state readable.
        with jax.named_scope("attn_read"):
            rows = {n: gather(a) for n, a in pages.items()}
        rows, pages = lax.optimization_barrier((rows, pages))
    pk, pv = pages["k"], pages["v"]

    # --- append: scatter the chunk's K/V into the pool -------------------------
    with jax.named_scope("kv_write"):
        logical = positions // page                             # (b, s)
        if ring:
            tab_idx = logical % n_blocks
            # only pages still live at the end of the true chunk are
            # written; an in-chunk wrap must not clobber pages the NEXT
            # positions still need.
            end = pos + (last_idx if last_idx is not None
                         else jnp.full((b,), s - 1, jnp.int32))  # (b,)
            keep = logical > (end // page)[:, None] - n_blocks
        else:
            tab_idx = jnp.minimum(logical, n_blocks - 1)
            keep = logical < n_blocks
        if last_idx is not None:
            keep &= jnp.arange(s)[None, :] <= last_idx[:, None]
        if cache_offset is not None:
            keep &= positions >= cache_offset[:, None]
        wp = jnp.take_along_axis(block_tab, tab_idx, axis=1)
        wp = jnp.where(keep, wp, n_pages)            # invalid id -> dropped
        wo = positions % page

        kc = k.transpose(0, 2, 1, 3)                     # (b, s, hkv, hd)
        vc = v.transpose(0, 2, 1, 3)
        # one scatter row per (position, head): a whole (hkv, hd) window
        # per position would lead XLA to lay the pools out position-major,
        # and the read kernel's page copies would then pay a relayout of
        # the whole pool.
        at = layer + (wp[..., None], jnp.arange(hkv)[None, None, :],
                      wo[..., None])

        def put(pool, vals):
            return pool.at[at].set(vals.astype(pool.dtype), mode="drop")

        new_pages = dict(pages)
        if quantized:
            kq, ks = _kv_quantize(kc)             # int8 + (b,s,hkv,1) scale
            vq, vs = _kv_quantize(vc)
            new_pages["k"], new_pages["v"] = put(pk, kq), put(pv, vq)
            new_pages["k_scale"] = put(pages["k_scale"], ks)
            new_pages["v_scale"] = put(pages["v_scale"], vs)
        else:
            new_pages["k"], new_pages["v"] = put(pk, kc), put(pv, vc)

    # --- read ------------------------------------------------------------------
    with jax.named_scope("attn_read"):
        page_base = _ring_page_base(pos, page, n_blocks) if ring else None
        if kernel:
            # write-then-read through the block-table kernel.  The
            # verify span's writes land before the read, so ring bases key
            # off the span END — entries the span wrote hold NEW logical
            # pages (the ring table width is padded by speculate_k, so
            # every clobbered old page is strictly out-of-window for every
            # row).  At s == 1 this reduces to the plain base-from-pos.
            from ..kernels.flash_attention import (
                flash_attention_decode_paged)
            flash_base = (_ring_page_base(pos + (s - 1), page, n_blocks)
                          if ring else None)
            o = flash_attention_decode_paged(
                q, new_pages["k"], new_pages["v"], block_tab, pos,
                window=window, page_base=flash_base,
                k_scale_pages=new_pages.get("k_scale"),
                v_scale_pages=new_pages.get("v_scale"), layer=layer)
        else:
            # the pool's rows before pos + overlay the chunk's own K/V.
            if rows is None:
                rows = {n: gather(a) for n, a in new_pages.items()}
            if quantized:
                kd = _kv_dequantize(rows["k"], rows["k_scale"], q.dtype)
                vd = _kv_dequantize(rows["v"], rows["v_scale"], q.dtype)
                if s == 1 or verify:                     # pool-rounded own k/v
                    kl = _kv_dequantize(kq, ks, q.dtype).transpose(
                        0, 2, 1, 3)
                    vl = _kv_dequantize(vq, vs, q.dtype).transpose(
                        0, 2, 1, 3)
                else:
                    kl, vl = k, v
            else:
                kd = rows["k"].astype(q.dtype)
                vd = rows["v"].astype(q.dtype)
                if s == 1 or verify:
                    kl = k.astype(pk.dtype).astype(q.dtype)
                    vl = v.astype(pv.dtype).astype(q.dtype)
                else:
                    kl, vl = k, v
            if ring:
                kpos = (page_base[:, :, None]
                        + jnp.arange(page)[None, None, :]).reshape(b, S)
            else:
                kpos = jnp.broadcast_to(jnp.arange(S)[None, :], (b, S))
            K = jnp.concatenate([kd, kl], axis=2)    # (b, hkv, S + s, hd)
            V = jnp.concatenate([vd, vl], axis=2)
            kpos_cat = jnp.concatenate([kpos, positions], axis=1)  # (b, S+s)
            # gathered entries are only valid STRICTLY before the chunk
            # (stale/ring-relabeled slots carry kpos >= pos); own entries
            # cover [pos, pos+s).
            pre_ok = jnp.concatenate(
                [(kpos >= 0) & (kpos < pos[:, None]),
                 jnp.ones((b, s), bool)], axis=1)        # (b, S+s)
            mask = (kpos_cat[:, None, :] <= positions[:, :, None]) \
                & pre_ok[:, None, :]
            if window is not None:
                mask &= (kpos_cat[:, None, :]
                         > positions[:, :, None] - window)
            o = attention_masked(q, K, V, mask)
    # under shard_map TP the heads are column-sharded and wo row-sharded:
    # each shard holds a partial sum over its heads — reduce it here.
    with jax.named_scope("attn_proj"):
        y = psum_parts(o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"])
    y = constrain(y, "batch", None, "embed")
    return x + y, new_pages


def attention_paged_cache_decl(cfg, n_pages: int, page_size: int
                               ) -> Dict[str, Decl]:
    """One attention layer's shared page pool: (n_pages, hkv, page, hd).
    The pool has no batch/slot axis — slots own *pages*, not rows.
    int8 KV pools additionally carry per-position bf16 scale pages."""
    shp = (n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    ax = (None, "kv_heads", None, None)
    if cfg.kv_cache_dtype == "int8":
        sshp = (n_pages, cfg.n_kv_heads, page_size, 1)
        return {"k": Decl(shp, ax, jnp.int8, init="zeros"),
                "v": Decl(shp, ax, jnp.int8, init="zeros"),
                "k_scale": Decl(sshp, ax, jnp.bfloat16, init="zeros"),
                "v_scale": Decl(sshp, ax, jnp.bfloat16, init="zeros")}
    return {"k": Decl(shp, ax, jnp.bfloat16, init="zeros"),
            "v": Decl(shp, ax, jnp.bfloat16, init="zeros")}


def attention_cache_decl(cfg, batch: int, max_seq: int,
                         window: Optional[int] = None) -> Dict[str, Decl]:
    S = min(max_seq, window) if window else max_seq
    shp = (batch, cfg.n_kv_heads, S, cfg.head_dim)
    if window is None and cfg.decode_seq_shard:
        seq_ax = "kv_seq"            # §Perf: shard cache over 'model'
    elif window is None and batch == 1:
        seq_ax = "seq_sharded"       # long-context: shard over 'data'
    else:
        seq_ax = None
    ax = ("batch", "kv_heads", seq_ax, None)
    if cfg.kv_cache_dtype == "int8":
        sshp = (batch, cfg.n_kv_heads, S, 1)
        return {"k": Decl(shp, ax, jnp.int8, init="zeros"),
                "v": Decl(shp, ax, jnp.int8, init="zeros"),
                "k_scale": Decl(sshp, ax, jnp.bfloat16, init="zeros"),
                "v_scale": Decl(sshp, ax, jnp.bfloat16, init="zeros")}
    return {"k": Decl(shp, ax, jnp.bfloat16, init="zeros"),
            "v": Decl(shp, ax, jnp.bfloat16, init="zeros")}


def _kv_quantize(t: jnp.ndarray):
    """Per-(head, position) max-abs int8 quantization (beyond-paper KV
    compression: halves cache bytes vs bf16)."""
    scale = jnp.maximum(jnp.max(jnp.abs(t.astype(F32)), axis=-1,
                                keepdims=True), 1e-6) / 127.0
    q = jnp.clip(jnp.round(t.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(F32) * scale.astype(F32)).astype(dtype)


# --- MLA (deepseek-v2) ---------------------------------------------------------------


def mla_decls(cfg, stack: Tuple[int, ...] = ()) -> Dict[str, Decl]:
    d, hq = cfg.d_model, cfg.n_heads
    nope, rp, lora, vd = (cfg.qk_nope_dim, cfg.qk_rope_dim,
                          cfg.kv_lora_rank, cfg.v_head_dim)
    ax = ("stack",) * len(stack)
    return {
        "norm": Decl(stack + (d,), ax + ("embed",), init="zeros"),
        "wq": Decl(stack + (d, hq * (nope + rp)), ax + ("embed", "heads")),
        "w_dkv": Decl(stack + (d, lora + rp), ax + ("embed", "lora")),
        "kv_norm": Decl(stack + (lora,), ax + ("lora",), init="zeros"),
        "w_uk": Decl(stack + (lora, hq * nope), ax + ("lora", "heads")),
        "w_uv": Decl(stack + (lora, hq * vd), ax + ("lora", "heads")),
        "wo": Decl(stack + (hq * vd, d), ax + ("heads", "embed")),
    }


def mla_apply(cfg, p, x, *, cache=None, pos=None):
    b, s, d = x.shape
    hq = cfg.n_heads
    nope, rp, lora, vd = (cfg.qk_nope_dim, cfg.qk_rope_dim,
                          cfg.kv_lora_rank, cfg.v_head_dim)
    h = rmsnorm(x, p["norm"])
    q = (h @ p["wq"]).reshape(b, s, hq, nope + rp)
    dkv = h @ p["w_dkv"]
    c_kv = rmsnorm(dkv[..., :lora], p["kv_norm"])       # (b, s, lora)
    k_rope_raw = dkv[..., lora:]                        # (b, s, rp)
    if cache is None:
        positions = jnp.arange(s)
        q_nope, q_rope = q[..., :nope], mla_rope(cfg, q[..., nope:],
                                                 positions)
        k_rope = mla_rope(cfg, k_rope_raw, positions)       # shared head
        k_nope = (c_kv @ p["w_uk"]).reshape(b, s, hq, nope)
        vv = (c_kv @ p["w_uv"]).reshape(b, s, hq, vd)
        qq = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, s, hq, rp))],
            -1).transpose(0, 2, 1, 3)
        vv = vv.transpose(0, 2, 1, 3)
        qq = constrain(qq, "batch", "heads", None, None)
        kk = constrain(kk, "batch", "heads", None, None)
        vv = constrain(vv, "batch", "heads", None, None)
        o = attention_xla(qq, kk, vv, causal=True,
                          scale=mla_scale(cfg),
                          block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                          block_skip=cfg.attn_block_skip)
        new_cache = None
    else:
        # Absorbed decode on the *compressed* cache — the MLA memory win
        # (cache lora+rope per token instead of hq·(nope+vd)).
        q_nope, q_rope = q[..., :nope], mla_rope(cfg, q[..., nope:],
                                                 pos[None])
        k_rope = mla_rope(cfg, k_rope_raw, pos[None])         # (b, 1, rp)
        cc, cr = cache["c_kv"], cache["k_rope"]
        cc = lax.dynamic_update_slice_in_dim(cc, c_kv.astype(cc.dtype), pos, 1)
        cr = lax.dynamic_update_slice_in_dim(cr, k_rope.astype(cr.dtype),
                                             pos, 1)
        w_uk = p["w_uk"].reshape(lora, hq, nope)
        q_eff = jnp.einsum("bshn,lhn->bshl", q_nope.astype(F32),
                           w_uk.astype(F32))              # (b, 1, hq, lora)
        logits = (jnp.einsum("bshl,bSl->bhsS", q_eff, cc.astype(F32))
                  + jnp.einsum("bshr,bSr->bhsS", q_rope.astype(F32),
                               cr.astype(F32))) * mla_scale(cfg)
        valid = jnp.arange(cc.shape[1]) <= pos
        logits = jnp.where(valid[None, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, -1)
        ctx = jnp.einsum("bhsS,bSl->bshl", probs, cc.astype(F32))
        w_uv = p["w_uv"].reshape(lora, hq, vd)
        o = jnp.einsum("bshl,lhv->bshv", ctx, w_uv.astype(F32))
        o = o.astype(x.dtype).transpose(0, 2, 1, 3)
        new_cache = {"c_kv": cc, "k_rope": cr}
    y = o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]
    y = constrain(y, "batch", None, "embed")
    return x + y, new_cache


def mla_apply_paged(cfg, p, x, *, pages: Dict[str, jnp.ndarray],
                    block_tab: jnp.ndarray, pos: jnp.ndarray,
                    last_idx: Optional[jnp.ndarray] = None,
                    cache_offset: Optional[jnp.ndarray] = None,
                    verify: bool = False,
                    layer: Tuple[Any, ...] = ()):
    """MLA absorbed attention against a *paged* compressed latent cache.

    The pages hold the latent rows themselves — ``c_kv`` pages of shape
    (*stack, n_pages, page, kv_lora_rank) and ``k_rope`` pages of
    (*stack, n_pages, page · qk_rope_dim / W, W), a page's rope rows end
    to end in rows of W lanes (``mla_paged_cache_decl``); there is no
    per-head axis at all, so a page costs ``page · (lora + rope)`` bf16
    values (the MLA memory win, page-granular).  ``layer`` indexes the
    leading stack axes, as in ``attention_apply_paged``: the layer
    writes and reads its own pool inside the stack.  x: (b, s, d) —
    s == 1 decode, s > 1 a prefill chunk at positions pos..pos+s-1.
    Reads mirror the dense rounding: a chunk attends its own rows at
    full precision, decode attends the pool-rounded (bf16) rows.  The
    read gathers after the write, which touches no row before ``pos``,
    and takes only rows before ``pos`` from the pool.  ``verify=True``
    (speculative decode): the s == k span behaves like k sequential
    decode steps — own latent rows are pool-rounded so accepted tokens
    stay bit-identical to non-speculative greedy decode.  Returns
    (y, new_pages).
    """
    b, s, d = x.shape
    hq = cfg.n_heads
    nope, rp, lora, vd = (cfg.qk_nope_dim, cfg.qk_rope_dim,
                          cfg.kv_lora_rank, cfg.v_head_dim)
    h = rmsnorm(x, p["norm"])
    with jax.named_scope("attn_proj"):
        q = (h @ p["wq"]).reshape(b, s, hq, nope + rp)
        dkv = h @ p["w_dkv"]
        c_kv = rmsnorm(dkv[..., :lora], p["kv_norm"])        # (b, s, lora)
    positions = pos[:, None] + jnp.arange(s)                 # (b, s)
    k_rope = mla_rope(cfg, dkv[..., lora:], positions)
    q_nope = q[..., :nope]
    q_rope = mla_rope(cfg, q[..., nope:], positions[:, :, None])

    cp, rpool = pages["c_kv"], pages["k_rope"]
    n_pages, page, lora_local = cp.shape[len(layer):]
    n_blocks = block_tab.shape[1]
    # under shard_map TP the latent pool is sharded over the lora dim
    # (lora_local = lora / tp) while w_dkv/kv_norm stay replicated: every
    # shard computes the FULL latent row, writes only its slice, and the
    # read below gathers the slices back (a bit-exact concat in
    # axis-index order — the ISSUE's all_gather at the attention
    # boundary).  k_rope pages are replicated (no head/latent dim).
    sharded_latent = lora_local != lora

    # append: scatter latent rows (padded chunk tails write nowhere;
    # positions below cache_offset live in shared prefix pages and are
    # read-only — see attention_apply_paged).
    with jax.named_scope("kv_write"):
        logical = positions // page
        keep = logical < n_blocks
        if last_idx is not None:
            keep &= jnp.arange(s)[None, :] <= last_idx[:, None]
        if cache_offset is not None:
            keep &= positions >= cache_offset[:, None]
        wp = jnp.take_along_axis(block_tab,
                                 jnp.minimum(logical, n_blocks - 1), axis=1)
        wp = jnp.where(keep, wp, n_pages)
        wo = positions % page
        lanes = rpool.shape[-1]
        el = wo[..., None] * rp + jnp.arange(rp)            # (b, s, rp)
        c_kv_loc = c_kv
        if sharded_latent:
            c_kv_loc = lax.dynamic_slice_in_dim(
                c_kv, part_index() * lora_local, lora_local, axis=-1)
        new_pages = {
            "c_kv": cp.at[layer + (wp, wo)].set(c_kv_loc.astype(cp.dtype),
                                                mode="drop"),
            # element e of a page's rope rows is at (e // W, e % W).
            "k_rope": rpool.at[layer + (wp[..., None], el // lanes,
                                        el % lanes)].set(
                k_rope.astype(rpool.dtype), mode="drop"),
        }

    # absorbed query: scores against a latent row are q_nope W_uk^T c.
    with jax.named_scope("attn_proj"):
        w_uk = p["w_uk"].reshape(lora, hq, nope)
        q_eff = jnp.einsum("bshn,lhn->bshl", q_nope.astype(F32),
                           w_uk.astype(F32))             # (b, s, hq, lora)

    # read: the pool's rows before pos + own-chunk overlay.
    with jax.named_scope("attn_read"):
        bt = layer + (jnp.minimum(block_tab, n_pages - 1),)
        S = n_blocks * page
        cc = new_pages["c_kv"][bt].reshape(b, S, lora_local)
        if sharded_latent:
            cc = gather_parts(cc, axis=-1)               # back to full lora
        cc = cc.astype(F32)
        cr = new_pages["k_rope"][bt].reshape(b, S, rp).astype(F32)
        if s == 1 or verify:                             # pool-rounded rows
            cl = c_kv.astype(cp.dtype).astype(F32)
            rl = k_rope.astype(rpool.dtype).astype(F32)
        else:
            cl, rl = c_kv.astype(F32), k_rope.astype(F32)
        CC = jnp.concatenate([cc, cl], axis=1)           # (b, S + s, lora)
        CR = jnp.concatenate([cr, rl], axis=1)
        kpos = jnp.broadcast_to(jnp.arange(S)[None, :], (b, S))
        kpos_cat = jnp.concatenate([kpos, positions], axis=1)
        pre_ok = jnp.concatenate([kpos < pos[:, None],
                                  jnp.ones((b, s), bool)], axis=1)
        valid = (kpos_cat[:, None, :] <= positions[:, :, None]) \
            & pre_ok[:, None, :]                         # (b, s, S+s)
        logits = (jnp.einsum("bshl,bSl->bhsS", q_eff, CC)
                  + jnp.einsum("bshr,bSr->bhsS", q_rope.astype(F32), CR)) \
            * mla_scale(cfg)
        logits = jnp.where(valid[:, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, -1)
        ctx = jnp.einsum("bhsS,bSl->bshl", probs, CC)
    with jax.named_scope("attn_proj"):
        w_uv = p["w_uv"].reshape(lora, hq, vd)
        o = jnp.einsum("bshl,lhv->bshv", ctx,
                       w_uv.astype(F32)).astype(x.dtype)
        # TP: query heads column-sharded, wo row-sharded -> partial sums.
        y = psum_parts(o.reshape(b, s, hq * vd) @ p["wo"])
    y = constrain(y, "batch", None, "embed")
    return x + y, new_pages


def mla_paged_cache_decl(cfg, n_pages: int, page_size: int
                         ) -> Dict[str, Decl]:
    """One MLA layer's latent page pool: rows of the compressed cache,
    paged over the sequence — (n_pages, page, lora) + the shared rope
    head, a page's rope rows end to end in rows of W = 128 lanes (fewer
    where a page holds less): (n_pages, page · rope_dim / W, W).  A rope
    row (64 wide in DeepSeek-V2) fills half a lane row, so a TPU stores
    a (n_pages, page, rope_dim) pool with the page axis minor, and the
    layer loop then relayouts the whole pool into and out of every call
    and writes it by a loop of single rows."""
    w = math.gcd(page_size * cfg.qk_rope_dim, 128)
    return {
        "c_kv": Decl((n_pages, page_size, cfg.kv_lora_rank),
                     (None, None, "lora"), jnp.bfloat16, init="zeros"),
        "k_rope": Decl((n_pages, page_size * cfg.qk_rope_dim // w, w),
                       (None, None, None), jnp.bfloat16, init="zeros"),
    }


def mla_cache_decl(cfg, batch: int, max_seq: int) -> Dict[str, Decl]:
    seq_ax = "seq_sharded" if batch == 1 else None
    return {
        "c_kv": Decl((batch, max_seq, cfg.kv_lora_rank),
                     ("batch", seq_ax, "lora"), jnp.bfloat16, init="zeros"),
        "k_rope": Decl((batch, max_seq, cfg.qk_rope_dim),
                       ("batch", seq_ax, None), jnp.bfloat16, init="zeros"),
    }


# --- MLP / MoE ------------------------------------------------------------------------


def mlp_decls(cfg, stack: Tuple[int, ...] = (), d_ff: Optional[int] = None
              ) -> Dict[str, Decl]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ax = ("stack",) * len(stack)
    decls = {
        "norm": Decl(stack + (d,), ax + ("embed",), init="zeros"),
        "w_up": Decl(stack + (d, f), ax + ("embed", "ff")),
        "w_down": Decl(stack + (f, d), ax + ("ff", "embed")),
    }
    if cfg.mlp_gated:
        decls["w_gate"] = Decl(stack + (d, f), ax + ("embed", "ff"))
    return decls


@jax.named_scope("mlp")
def mlp_apply(cfg, p, x):
    h = rmsnorm(x, p["norm"])
    h = constrain(h, "batch", None, "embed")
    if "w_gate" in p:
        hh = swiglu(h @ p["w_gate"], h @ p["w_up"])
    else:
        hh = jax.nn.gelu((h @ p["w_up"]).astype(F32)).astype(h.dtype)
    hh = constrain(hh, "batch", None, "ff")
    # TP: w_up/w_gate column-sharded over ff, w_down row-sharded.
    y = psum_parts(hh @ p["w_down"])
    y = constrain(y, "batch", None, "embed")
    return x + y


def moe_decls(cfg, stack: Tuple[int, ...] = ()) -> Dict[str, Decl]:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ax = ("stack",) * len(stack)
    decls = {
        "norm": Decl(stack + (d,), ax + ("embed",), init="zeros"),
        "router": Decl(stack + (d, E), ax + ("embed", None), std=0.02),
        "w_gate": Decl(stack + (E, d, f), ax + ("experts", "embed", "ff")),
        "w_up": Decl(stack + (E, d, f), ax + ("experts", "embed", "ff")),
        "w_down": Decl(stack + (E, f, d), ax + ("experts", "ff", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        decls.update({
            "sh_gate": Decl(stack + (d, fs), ax + ("embed", "ff")),
            "sh_up": Decl(stack + (d, fs), ax + ("embed", "ff")),
            "sh_down": Decl(stack + (fs, d), ax + ("ff", "embed")),
        })
    return decls


MOE_BLOCK = 256        # tokens a dropless expert pass holds at once


def _moe_route(cfg, p, x2):
    """Each token's top-k experts and their gates (float32), (T, k).

    ``norm_topk_prob`` (the published key): a softmax over the k chosen
    logits, i.e. the top-k renormalised to sum to one; else (deepseek-v2)
    the top k of a softmax over every expert, kept as they are, with the
    router's logits taken in float32."""
    if cfg.norm_topk_prob:
        logits = (x2 @ p["router"]).astype(F32)          # (T, E)
        gates, idx = lax.top_k(logits, cfg.top_k)
        return idx, jax.nn.softmax(gates, axis=-1)
    logits = jnp.matmul(x2.astype(F32), p["router"].astype(F32),
                        precision=lax.Precision.HIGHEST)
    gates, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    return idx, gates


def _moe_dispatch_combine(cfg, p, x2, dtype):
    """Capacity-bounded scatter dispatch + expert SwiGLU + gather combine
    for one token group.  Returns the combined output (T, d)."""
    T, d = x2.shape
    E, k, f = cfg.n_experts, cfg.top_k, cfg.moe_d_ff

    idx, gates = _moe_route(cfg, p, x2)                  # (T, k)
    gates = gates.astype(dtype)

    cap = int(np.ceil(T * k / E * cfg.capacity_factor))
    cap = datapack.round_up(max(cap, 8), 8)

    flat_e = idx.reshape(-1)                             # (T·k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T·k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1)
    pos_in_e = jnp.take_along_axis(pos_in_e, flat_e[:, None], 1)[:, 0]
    keep = pos_in_e < cap

    x_rep = jnp.repeat(x2, k, axis=0)                    # (T·k, d)
    safe_pos = jnp.where(keep, pos_in_e, cap - 1)
    disp = jnp.zeros((E, cap, d), dtype)
    disp = disp.at[flat_e, safe_pos].add(
        jnp.where(keep[:, None], x_rep, 0))
    return disp, (flat_e, safe_pos, keep, gates)


def _moe_combine(T, k, d, out_e, meta, dtype):
    flat_e, safe_pos, keep, gates = meta
    y_rep = out_e[flat_e, safe_pos] * keep[:, None]
    return (y_rep.reshape(T, k, d) * gates[..., None]).sum(1).astype(dtype)


def _moe_dropless(cfg, p, x2):
    """Every routed assignment kept: each expert's capacity is the
    call's tokens, so every expert runs over every token and each token
    gathers its own k results.  A token's output depends on its own row
    alone, never on the other tokens of the call.  The serving steps'
    tokens (the slots, or one prefill chunk) fit one block, and at decode
    every expert's weights are read once whether it is hit or not;
    longer inputs (a whole prompt's prefill) run in blocks of
    ``MOE_BLOCK`` tokens, so the (E, tokens, ff) intermediate stays
    bounded."""
    T, d = x2.shape
    k = cfg.top_k
    with jax.named_scope("moe_route"):
        idx, gates = _moe_route(cfg, p, x2)              # (T, k)

    def block(xb, ib, gb):
        n = xb.shape[0]
        hh = swiglu(jnp.einsum("td,edf->etf", xb, p["w_gate"]),
                    jnp.einsum("td,edf->etf", xb, p["w_up"]))
        out_e = jnp.einsum("etf,efd->etd", hh, p["w_down"])   # (E, n, d)
        meta = (ib.reshape(-1), jnp.repeat(jnp.arange(n), k),
                jnp.ones((n * k,), bool), gb.astype(xb.dtype))
        return _moe_combine(n, k, d, out_e, meta, xb.dtype)

    with jax.named_scope("moe_experts"):
        if T <= MOE_BLOCK:
            return block(x2, idx, gates), idx
        nb = -(-T // MOE_BLOCK)
        pad = ((0, nb * MOE_BLOCK - T), (0, 0))
        y = lax.map(lambda a: block(*a), tuple(
            jnp.pad(a, pad).reshape(nb, MOE_BLOCK, -1)
            for a in (x2, idx, gates)))
    return y.reshape(nb * MOE_BLOCK, d)[:T], idx


def moe_apply(cfg, p, x, *, dropless: bool = False):
    """Top-k MoE.  Returns (x + y, each token's experts (b, s, k)).

    ``dropless`` (every mode but training): no routed assignment is
    dropped (``_moe_dropless``), so a request's output never depends on
    what else is in the call.  Otherwise (training) capacity-bounded
    scatter dispatch (EP over 'model'), which drops what passes
    ``capacity_factor``.

    Baseline: one global dispatch — the (E, C, d) tensor has no
    data-sharded dim, so expert compute replicates across the data axis
    (the naive formulation; kept as the recorded baseline).

    ``cfg.moe_groups = G`` (beyond-paper, §Perf): tokens are split into G
    groups sharded over (pod, data); dispatch/combine vmap over groups so
    the expert einsums carry a data-parallel group dim — true DP×EP.
    """
    b, s, d = x.shape
    E, k, f = cfg.n_experts, cfg.top_k, cfg.moe_d_ff
    with jax.named_scope("moe_route"):
        h = rmsnorm(x, p["norm"])
    x2 = h.reshape(b * s, d)
    T = b * s
    G = cfg.moe_groups

    if dropless:
        y, idx = _moe_dropless(cfg, p, x2)
        y = y.reshape(b, s, d)
    elif G and T % G == 0 and T // G >= 8:
        xg = x2.reshape(G, T // G, d)
        xg = constrain(xg, "moe_groups", None, "embed")
        disp, meta = jax.vmap(
            lambda xx: _moe_dispatch_combine(cfg, p, xx, x.dtype))(xg)
        disp = constrain(disp, "moe_groups", "experts", None, "embed")
        hh = swiglu(jnp.einsum("gecd,edf->gecf", disp, p["w_gate"]),
                    jnp.einsum("gecd,edf->gecf", disp, p["w_up"]))
        hh = constrain(hh, "moe_groups", "experts", None, "ff")
        out_e = jnp.einsum("gecf,efd->gecd", hh, p["w_down"])
        # NOTE §Perf iteration C (refuted): re-sharding out_e to group
        # owners before the combine gather just moves the same payload
        # into an earlier all-to-all and costs ~11%% more collective
        # time; GSPMD's gather placement is already near-optimal here.
        out_e = constrain(out_e, "moe_groups", "experts", None, "embed")
        y = jax.vmap(lambda oe, mt: _moe_combine(T // G, k, d, oe, mt,
                                                 x.dtype))(out_e, meta)
        y = y.reshape(b, s, d)
        idx = meta[0].reshape(b, s, k)
    else:
        disp, meta = _moe_dispatch_combine(cfg, p, x2, x.dtype)
        disp = constrain(disp, "experts", None, "embed")
        hh = swiglu(jnp.einsum("ecd,edf->ecf", disp, p["w_gate"]),
                    jnp.einsum("ecd,edf->ecf", disp, p["w_up"]))
        hh = constrain(hh, "experts", None, "ff")
        out_e = jnp.einsum("ecf,efd->ecd", hh, p["w_down"])
        out_e = constrain(out_e, "experts", None, "embed")
        y = _moe_combine(T, k, d, out_e, meta, x.dtype).reshape(b, s, d)
        idx = meta[0]

    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            y = y + (swiglu(h @ p["sh_gate"], h @ p["sh_up"])
                     @ p["sh_down"])
    # TP (shard_map serving): experts keep their full set per shard but
    # the ff dim is column-sharded (router replicated -> identical
    # routing), so expert + shared-expert outputs are partial sums over
    # the manual axis; one reduce covers both.
    y = psum_parts(y)
    y = constrain(y, "batch", None, "embed")
    # Load-balance auxiliary loss (Switch-style) is returned via closure-
    # free side channel: recomputed in the train loop if needed; here we
    # keep the block pure.
    return x + y, idx.reshape(b, s, k)
