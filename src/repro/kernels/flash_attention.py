"""Tiled online-softmax (flash) attention for TPU.

TPU-native design (hardware-adaptation notes):

* Grid = (batch·q_heads, q_blocks, kv_blocks) with the kv dim innermost —
  TPU grids execute sequentially, so the kv loop carries the online-
  softmax state (m, l, acc) in VMEM scratch across grid steps.  This is
  the Pallas idiom for FlashAttention-style accumulation (no atomics, no
  shared-memory reductions as on GPU — the sequential grid IS the loop).
* BlockSpecs tile (block_q × head_dim) / (block_k × head_dim) into VMEM;
  block sizes are lane/sublane aligned via ``repro.core.datapack`` (F5 —
  one width constant re-tiles the kernel).
* The online-softmax merge of per-block partials is the ``LogSumExp``
  functor of F7 (``repro.core.treereduce``) in streaming form.
* Causal/sliding-window blocks that are fully masked are skipped with
  ``pl.when`` — the block-level analogue of hlslib's compile-time-checked
  constant taps: the window (F6) is static, so skipping is static too.

GQA is supported by index-mapping kv blocks with head // group.

Tensor-parallel serving (``cfg.mesh_shape``, docs/serving.md) runs
these kernels UNCHANGED inside the ``shard_map`` body: attention is
embarrassingly parallel over heads, so each shard sees the same shapes
it would on one device, just with ``n_heads/tp`` query heads and
``n_kv_heads/tp`` KV heads (page pools arrive pre-sharded over the
head axis, block tables replicated).  The GQA ``head // group`` map
stays valid because query and KV heads shard by the SAME factor —
enforced launch-side by ``distributed.sharding.validate_shardable``.
No collective appears until after the kernel, at the wo projection.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import datapack
from .platform import interpret_mode

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, kv_len: int, q_offset: int):
    jq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Static-ish activity test: with equal block sizes, block (jq, jk) can
    # contribute iff kv block start <= last query position, and (window)
    # kv block end > first query position - window.
    q_start = jq * block_q + q_offset           # absolute position of row 0
    q_last = q_start + block_q - 1
    k_start = jk * block_k
    k_last = k_start + block_k - 1
    active = jnp.bool_(True)
    if causal:
        active &= k_start <= q_last
    if window is not None:
        active &= k_last > q_start - window

    @pl.when(active)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows (all NEG_INF): keep exp() finite.
        p = jnp.exp(s - m_new)                            # (bq, bk)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                   # rescale old partials
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                   # fully-masked rows -> 0
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (b, hq, sq, d); k, v: (b, hkv, sk, d).  Returns (b, hq, sq, d).

    Decode-style calls (sq < sk) align queries to the end of the kv
    sequence, matching ``ref.attention_ref``.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    sq_pad = datapack.round_up(sq, block_q)
    sk_pad = datapack.round_up(sk, block_k)
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0)))

    bh = b * hq
    q4 = q.reshape(bh, sq_pad, d)
    k4 = k.reshape(b * hkv, sk_pad, d)
    v4 = v.reshape(b * hkv, sk_pad, d)
    grid = (bh, sq_pad // block_q, sk_pad // block_k)

    q_offset = sk - sq  # decode alignment

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=sk, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda i, j, kk, g=group, hh=hq: (
                             (i // hh) * (hh // g) + (i % hh) // g, kk, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda i, j, kk, g=group, hh=hq: (
                             (i // hh) * (hh // g) + (i % hh) // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q4, k4, v4)

    out = out.reshape(b, hq, sq_pad, d)
    return out[:, :, :sq, :]


# --- decode-specialized entry point (sq == 1 fast path) -------------------------------
#
# Serving decode attends ONE query per sequence against a long cache; the
# general kernel above would spend its q_blocks grid dim on a single
# (padded) row.  The decode kernel instead:
#
# * uses a kv-only grid (b·hkv, kv_blocks) — the sequential kv dim still
#   carries the online-softmax state in VMEM scratch;
# * shares kv heads across the GQA group WITHOUT materializing the
#   broadcast: the q block holds the whole group (group, d), so k/v are
#   fetched once per kv head and hit every query head in the group;
# * skips kv blocks that cannot contribute (entirely in the future, or
#   entirely outside the sliding window) via ``pl.when`` on the
#   scalar-prefetched position — the block-skipping analogue of the
#   static tap-skipping in the prefill kernel, but driven by the decode
#   position that is known before the grid step runs.


def _flash_decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                         acc_scr, *, scale: float, window: Optional[int],
                         ring: bool, block_k: int, kv_len: int, hkv: int):
    i = pl.program_id(0)
    jk = pl.program_id(1)
    nk = pl.num_programs(1)
    pos = pos_ref[i // hkv]

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = jk * block_k
    if ring:
        # ring layout: once pos >= window every slot is live, so only the
        # warm-up phase (pos < window) can skip future blocks.
        active = (k_start <= pos) | (pos >= window)
    else:
        active = k_start <= pos                     # skip future blocks
        if window is not None:
            active &= k_start + block_k - 1 > pos - window  # out-of-window

    @pl.when(active)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (group, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (group, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < kv_len
        if ring:
            valid &= (kpos <= pos) | (pos >= window)
        else:
            valid &= kpos <= pos
            if window is not None:
                valid &= kpos > pos - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           pos: jnp.ndarray,
                           window: Optional[int] = None,
                           ring: bool = False,
                           scale: Optional[float] = None,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Single-step (sq == 1) decode attention over a KV cache.

    q: (b, hq, 1, d); k, v: (b, hkv, S, d); ``pos``: int32 scalar or (b,)
    — the position being decoded (cache entries <= pos are live).

    ``ring=True`` means k/v use the rolling ring layout of sliding-window
    caches (slot = position % window, S == window): every slot is valid
    once pos >= window.  ``ring=False`` with ``window`` applies the usual
    (pos - window, pos] band.  Returns (b, hq, 1, d).
    """
    b, hq, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"decode fast path requires sq == 1, got {sq}")
    _, hkv, S, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if ring and window is None:
        raise ValueError("ring layout requires a window size")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)

    block_k = min(block_k, S)
    S_pad = datapack.round_up(S, block_k)
    if S_pad != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))

    bh = b * hkv
    # group dim folded into the q block: kv fetched once per kv head.
    q3 = q[:, :, 0, :].reshape(b, hkv, group, d).reshape(bh, group, d)
    k3 = k.reshape(bh, S_pad, d)
    v3 = v.reshape(bh, S_pad, d)
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(
        _flash_decode_kernel, scale=scale, window=window, ring=ring,
        block_k=block_k, kv_len=S, hkv=hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, S_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, group, d), lambda i, kk, pos_ref: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kk, pos_ref: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kk, pos_ref: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, d),
                               lambda i, kk, pos_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, group, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(pos_arr, q3, k3, v3)

    return out.reshape(b, hq, d)[:, :, None, :]


# --- paged decode (block-table gather via scalar prefetch) ----------------------------
#
# The paged KV cache stores (page, kv_head, page_size, d) tiles in one
# shared pool; a per-sequence block table maps logical page j to its
# physical page id.  The decode kernel keeps the kv-only sequential grid
# of ``flash_attention_decode`` but *gathers* its kv blocks through the
# scalar-prefetched block table: the BlockSpec index map reads
# ``bt_ref[seq, j]`` to pick which physical page to DMA next, so the
# dense (b, S, d) cache view is never materialized — pages stream
# HBM -> VMEM exactly like contiguous blocks would.  Logical pages whose
# start is past ``pos`` are skipped via ``pl.when`` (their table entries
# may be unallocated; callers clamp them so the prefetched index is
# always a fetchable page).


def _flash_decode_paged_kernel(*refs, scale: float, window: Optional[int],
                               page: int, hkv: int, group: int, sq: int,
                               has_base: bool, quantized: bool):
    """Refs: [pos, bt(, page_base)] prefetch, [q, k, v(, ks, vs)] inputs,
    o output, (m, l, acc) scratch — optional refs keyed by the static
    ``has_base``/``quantized`` flags.

    ``sq`` > 1 is the speculative-verify span: the q block carries
    sq·group rows (query position-major), row rr belonging to query
    position ``pos + rr // group`` — each gets its own causal band, so
    one pass over the block table scores every position of the span."""
    n_pre = 3 if has_base else 2
    pos_ref = refs[0]
    pb_ref = refs[2] if has_base else None
    q_ref, k_ref, v_ref = refs[n_pre:n_pre + 3]
    ks_ref = refs[n_pre + 3] if quantized else None
    vs_ref = refs[n_pre + 4] if quantized else None
    o_ref = refs[-4]
    m_scr, l_scr, acc_scr = refs[-3:]

    i = pl.program_id(0)
    jk = pl.program_id(1)
    nk = pl.num_programs(1)
    pos = pos_ref[i // hkv]

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # ``page_base`` (ring-of-pages groups): the logical base position of
    # table entry jk, reconstructed by the caller — negative for slots
    # never written.  Flat layouts keep the static jk * page base.
    k_start = pb_ref[i // hkv, jk] if has_base else jk * page
    active = k_start <= pos + (sq - 1)                # skip future pages
    if has_base:
        active &= k_start >= 0                        # skip unwritten slots
    if window is not None:
        active &= k_start + page - 1 > pos - window   # skip out-of-window

    @pl.when(active)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale      # (sq·group, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (page, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # int8 pages dequantize in VMEM: per-position bf16 scales.
            k = k * ks_ref[0, 0].astype(jnp.float32)
            v = v * vs_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (sq·group, page)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # row rr of the block is query position pos + rr // group.
        qpos = pos + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // group
        valid = kpos <= qpos
        if has_base:
            valid &= kpos >= 0
        if window is not None:
            valid &= kpos > qpos - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray,
                                 block_tab: jnp.ndarray, pos: jnp.ndarray,
                                 window: Optional[int] = None,
                                 page_base: Optional[jnp.ndarray] = None,
                                 k_scale_pages: Optional[jnp.ndarray] = None,
                                 v_scale_pages: Optional[jnp.ndarray] = None,
                                 scale: Optional[float] = None,
                                 interpret: Optional[bool] = None
                                 ) -> jnp.ndarray:
    """Decode attention over a *paged* KV cache.

    q: (b, hq, sq, d) — sq == 1 is the plain decode step; sq > 1 is a
    speculative *verify* span whose rows sit at positions
    pos..pos+sq-1, each with its own causal band (one grid pass over
    the block table scores all sq positions); k_pages/v_pages:
    (n_pages, hkv, page, d) shared
    pools; block_tab: (b, n_blocks) int32 physical page per logical page
    (unallocated entries are clamped into [0, n_pages) — they are
    skipped/masked, but the index map still has to name a fetchable
    page); pos: (b,) int32 position of the first query row.  ``window``
    applies the per-row (qpos - window, qpos] band on *logical*
    positions.

    ``page_base`` (optional, (b, n_blocks) int32): per-entry logical
    base position for ring-of-pages window groups, where table entry j
    holds logical page ``l ≡ j (mod n_blocks)``; negative bases mark
    never-written slots.  Defaults to the flat ``j * page``.

    ``k_scale_pages``/``v_scale_pages`` (optional, (n_pages, hkv, page,
    1) bf16): per-position scales for int8 pools — pages dequantize
    in VMEM right after the gather, so the dense bf16 view is never
    materialized in HBM.  Returns (b, hq, sq, d), matching
    ``ref.paged_attention_ref``.
    """
    b, hq, sq, d = q.shape
    n_pages, hkv, page, _ = k_pages.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if (k_scale_pages is None) != (v_scale_pages is None):
        raise ValueError("k/v scale pages must be passed together")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    has_base = page_base is not None
    quantized = k_scale_pages is not None

    n_blocks = block_tab.shape[1]
    bh = b * hkv
    rows = sq * group
    # Fold (b, hq, sq, d) position-major into (bh, sq·group, d): block
    # row rr belongs to query position rr // group, head group rr % group.
    q3 = (q.reshape(b, hkv, group, sq, d).transpose(0, 1, 3, 2, 4)
          .reshape(bh, rows, d))
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    bt = jnp.minimum(block_tab.astype(jnp.int32), n_pages - 1)

    kernel = functools.partial(
        _flash_decode_paged_kernel, scale=scale, window=window, page=page,
        hkv=hkv, group=group, sq=sq, has_base=has_base, quantized=quantized)

    n_pre = 3 if has_base else 2

    def _qmap(i, jk, *prefs):
        return (i, 0, 0)

    def _pmap(i, jk, *prefs, h=hkv):
        return (prefs[1][i // h, jk], i % h, 0, 0)

    in_specs = [pl.BlockSpec((1, rows, d), _qmap),
                # the paged gather: physical page picked by the block table.
                pl.BlockSpec((1, 1, page, d), _pmap),
                pl.BlockSpec((1, 1, page, d), _pmap)]
    inputs = [q3, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page, 1), _pmap),
                     pl.BlockSpec((1, 1, page, 1), _pmap)]
        inputs += [k_scale_pages, v_scale_pages]

    prefetch = [pos_arr, bt]
    if has_base:
        prefetch.append(jnp.asarray(page_base, jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,                    # pos, bt(, page_base)
        grid=(bh, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, d), _qmap),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, rows, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(*prefetch, *inputs)

    return (out.reshape(b, hkv, sq, group, d).transpose(0, 1, 3, 2, 4)
            .reshape(b, hq, sq, d))
