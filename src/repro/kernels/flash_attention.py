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
from typing import Any, Optional, Tuple

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


# --- paged decode (block-table DMA over the live pages) -------------------------------
#
# The paged KV cache stores (page, kv_head, page_size, d) tiles in one
# shared pool; a per-sequence block table maps logical page j to its
# physical page id.  The decode read walks, per slot, only the pages
# that hold its live positions — ``ceil((pos + sq) / page)`` of them,
# from the first in-window page when the layer has a flat window, the
# whole window-bounded table on a ring of pages — so its cost follows
# the context, not the table width.
#
# * The grid is one step per slot.  The pools stay in HBM
#   (``memory_space=ANY``), stacked over layers as the layer loop carries
#   them; the layer's index is one more scalar prefetch, so no layer's
#   pool is sliced out of the stack.  A loop inside the step fetches
#   compute blocks of ``P`` pages with one async copy per page, which
#   covers every KV head at once because ``(hkv, page, d)`` is
#   contiguous in the pool.
# * Copies are double-buffered: block j+1 streams while block j is
#   scored, and a slot's last block prefetches the next live slot's
#   first, so the pipeline does not drain between slots.  The loop body
#   is one straight run (start the next copies, wait for this block,
#   score it) with no branch, so the scheduler may overlap the copies'
#   issue, one descriptor per page, with the matmuls.  Idle slots (the
#   first entry their walk reads is unallocated) take no step of the
#   chain.  The buffer holding the next block is carried across grid
#   steps in SMEM, so the grid runs sequentially.
# * ``P`` follows from the bytes of one page across heads, aiming at
#   about ``_BLOCK_BYTES`` of K (and as much of V) a block, and from the
#   page size, filling at least one 128-lane row of scores: 8 pages of
#   32 KiB for 8 heads of 128 in bf16, 32 pages of 4 KiB for one head.
#   Smaller blocks pay a loop trip and a wait more often; larger ones
#   score more masked columns past the last live page.
# * Scores use the online softmax of the kernels above, in f32, with
#   the matmul operands in the query's dtype.

_BLOCK_BYTES = 128 * 1024
_LANES = 128


def pages_per_block(page_bytes: int, page: int, n_blocks: int) -> int:
    """Pages per compute block: the power of two nearest below
    ``_BLOCK_BYTES / page_bytes``, but at least enough pages to fill a
    128-lane row of scores, and at most the table width ``n_blocks``."""
    p = max(1, _BLOCK_BYTES // page_bytes, _LANES // page)
    return min(1 << (p.bit_length() - 1), n_blocks)


def _walk(pos, *, sq: int, page: int, n_blocks: int, ppb: int,
          window: Optional[int], ring: bool):
    """(first table entry, entries past the last) a slot at ``pos``
    reads; works on a scalar in the kernel and on the (b,) positions in
    the wrapper."""
    if ring:
        return jnp.zeros_like(pos), jnp.full_like(pos, n_blocks)
    j1 = jnp.minimum((pos + sq + page - 1) // page, n_blocks)
    if window is None:
        return jnp.zeros_like(pos), j1
    # blocks stay aligned to multiples of ppb entries, so a block's
    # scale columns are an aligned slice of the slot's row.
    j0 = jnp.maximum(pos - window + 1, 0) // page
    return jnp.minimum(j0, j1 - 1) // ppb * ppb, j1


def _flash_decode_paged_kernel(*refs, scale: float, window: Optional[int],
                               page: int, hkv: int, group: int, sq: int,
                               n_blocks: int, n_pages: int, ppb: int,
                               has_base: bool, quantized: bool):
    """Refs: [pos, bt, live, order, layer(, page_base)] prefetch (bt and
    page_base flattened to (b·n_blocks,); ``order[0]`` the first live
    slot, ``order[i + 1]`` the live slot after slot i, b for none;
    ``layer[0]`` the layer's index into the stacked pools),
    [q, k, v(, ks, vs)] inputs — the (n_layers, n_pages, hkv, page, d)
    pools in HBM, the slot's scales as (1, hkv, n·ppb·page) rows — o
    output, then scratch: the
    double-buffered page blocks kbuf, vbuf, their DMA semaphores, the
    (m, l, acc) online-softmax state and the SMEM index of the buffer
    that holds the next block.  Optional refs are keyed by the static
    ``has_base`` / ``quantized`` flags.

    ``sq`` > 1 is the speculative-verify span: the q block carries
    sq·group rows per KV head (query position-major), row rr belonging
    to query position ``pos + rr // group``, each with its own causal
    band, so one walk over the pages scores every position of the
    span."""
    n_pre = 6 if has_base else 5
    n_in = 5 if quantized else 3
    pos_ref, bt_ref, live_ref, order_ref, layer_ref = refs[:5]
    pb_ref = refs[5] if has_base else None
    q_ref, k_hbm, v_hbm = refs[n_pre:n_pre + 3]
    ks_ref, vs_ref = refs[n_pre + 3:n_pre + n_in] if quantized else (None,) * 2
    (o_ref, kbuf, vbuf, sems, m_scr, l_scr, acc_scr,
     nxt_ref) = refs[n_pre + n_in:]

    i = pl.program_id(0)
    n_slots = pl.num_programs(0)
    cols = ppb * page
    span = functools.partial(_walk, sq=sq, page=page, n_blocks=n_blocks,
                             ppb=ppb, window=window, ring=has_base)

    def start(slot, blk, buf):
        """One copy per page of block ``blk`` of ``slot``, covering every
        KV head.  Pages past the slot's span repeat its last page (their
        columns are masked); an unallocated entry inside it reads page
        n_pages - 1."""
        j0, j1 = span(pos_ref[slot])
        for p in range(ppb):
            j = jnp.minimum(j0 + blk * ppb + p, j1 - 1)
            src = jnp.minimum(bt_ref[slot * n_blocks + j], n_pages - 1)
            for pool, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
                pltpu.make_async_copy(pool.at[layer_ref[0], src],
                                      dst.at[buf, p], sems.at[buf]).start()

    def wait(buf):
        # a wait counts the destination's bytes: any page will do.
        for p in range(ppb):
            for pool, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
                pltpu.make_async_copy(pool.at[0, 0], dst.at[buf, p],
                                      sems.at[buf]).wait()

    @pl.when(i == 0)
    def _init():
        nxt_ref[0] = 0

    @pl.when(live_ref[i] == 0)
    def _idle():                                   # reads nothing
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[i] != 0)
    def _live():
        @pl.when(order_ref[0] == i)
        def _first():                  # no live slot before it prefetched
            start(i, 0, nxt_ref[0])

        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        pos = pos_ref[i]
        j0, j1 = span(pos)
        nb_i = (j1 - j0 + ppb - 1) // ppb
        after = order_ref[i + 1]                   # next live slot, or b
        cdt = q_ref.dtype
        rows = sq * group
        qs = [q_ref[0, h].astype(cdt) for h in range(hkv)]  # (rows, d)
        # query position of each row (row rr is position pos + rr // group)
        qpos = pos + (jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                      // group if sq > 1 else 0)

        def body(blk, buf):
            # one straight run, so the scheduler overlaps the next
            # block's copies with this block's matmuls: the next block
            # of this slot, else the next live slot's first, else (the
            # last live slot) its own first again, drained below.
            last = blk + 1 == nb_i
            start(jnp.where(last & (after < n_slots), after, i),
                  jnp.where(last, 0, blk + 1), 1 - buf)
            wait(buf)
            score(blk, buf)
            return 1 - buf

        def score(blk, buf):
            first = j0 + blk * ppb                     # table entry of col 0
            col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            if has_base:
                # ring-of-pages: the logical base of each entry, rebuilt
                # by the caller; negative for never-written slots and
                # past the table.
                base = jnp.zeros((1, cols), jnp.int32)
                for p in range(ppb):
                    j = first + p
                    b_p = pb_ref[i * n_blocks + jnp.minimum(j, n_blocks - 1)]
                    b_p = jnp.where(j < n_blocks, b_p, -(1 << 30))
                    base = jnp.where(col // page == p, b_p, base)
                kpos = base + col % page
                valid = (kpos >= 0) & (kpos <= qpos)
            else:
                kpos = first * page + col
                valid = (kpos <= qpos) & (kpos < n_blocks * page)
            if window is not None:
                valid &= kpos > qpos - window
            valid = jnp.broadcast_to(valid, (rows, cols))

            def load(x_buf, h):
                x = x_buf[buf, :, h]                     # (ppb, page, d)
                return x.reshape(cols, x.shape[-1]).astype(cdt)

            for h in range(hkv):
                s = jax.lax.dot_general(
                    qs[h], load(kbuf, h), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quantized:
                    # int8 pages: per-position scales act on score
                    # columns (K) and probability columns (V), so pages
                    # enter the MXU as they are stored.
                    at = pl.ds(pl.multiple_of(first * page, cols), cols)
                    ks, vs = (r[0, pl.ds(h, 1), at].astype(jnp.float32)
                              for r in (ks_ref, vs_ref))
                    s = s * ks
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                p_ = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = l_scr[h] * alpha + jnp.sum(p_, -1, keepdims=True)
                if quantized:
                    p_ = p_ * vs
                acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                    p_.astype(cdt), load(vbuf, h), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_scr[h] = m_new

        buf = jax.lax.fori_loop(0, nb_i, body, nxt_ref[0])
        nxt_ref[0] = buf

        @pl.when(after >= n_slots)
        def _drain():
            wait(buf)

        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def flash_attention_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray,
                                 block_tab: jnp.ndarray, pos: jnp.ndarray,
                                 window: Optional[int] = None,
                                 page_base: Optional[jnp.ndarray] = None,
                                 k_scale_pages: Optional[jnp.ndarray] = None,
                                 v_scale_pages: Optional[jnp.ndarray] = None,
                                 scale: Optional[float] = None,
                                 interpret: Optional[bool] = None,
                                 layer: Tuple[Any, ...] = ()
                                 ) -> jnp.ndarray:
    """Decode attention over a *paged* KV cache.

    q: (b, hq, sq, d) — sq == 1 is the plain decode step; sq > 1 is a
    speculative *verify* span whose rows sit at positions
    pos..pos+sq-1, each with its own causal band (one walk over the
    pages scores all sq positions); k_pages/v_pages: (*stack, n_pages,
    hkv, page, d) shared pools, read at ``layer`` (one index per stack
    axis, static or traced; ``()`` for one layer's (n_pages, hkv, page,
    d) pools): the kernel copies pages out of the stack, so no layer's
    pool is sliced out of it; block_tab: (b, n_blocks) int32 physical page
    per logical page (entries >= n_pages are unallocated: they may
    follow a slot's live positions, past which the walk never reads; an
    unallocated entry inside the walk reads page n_pages - 1, and a slot
    whose first walked entry is unallocated is idle: it reads nothing
    and returns zeros); pos: (b,) int32 position of the first query
    row.  ``window`` applies the per-row (qpos - window,
    qpos] band on *logical* positions, and the walk starts at the first
    page inside it.

    ``page_base`` (optional, (b, n_blocks) int32): per-entry logical
    base position for ring-of-pages window groups, where table entry j
    holds logical page ``l ≡ j (mod n_blocks)``; negative bases mark
    never-written slots.  The walk then covers the whole
    (window-bounded) table.  Defaults to the flat ``j * page``.

    ``k_scale_pages``/``v_scale_pages`` (optional, (*stack, n_pages,
    hkv, page, 1) bf16): per-position scales for int8 pools — each
    slot's scales are gathered into one row per KV head and applied in
    VMEM to the score
    and probability columns, so no dequantized view is materialized in
    HBM.  Returns (b, hq, sq, d), matching ``ref.paged_attention_ref``
    on every position that lies in an allocated page.
    """
    b, hq, sq, d = q.shape
    stack = k_pages.shape[:len(layer)]
    n_pages, hkv, page, _ = k_pages.shape[len(layer):]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if (k_scale_pages is None) != (v_scale_pages is None):
        raise ValueError("k/v scale pages must be passed together")
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    has_base = page_base is not None
    quantized = k_scale_pages is not None
    # the kernel walks one leading layer axis: the stack axes merge into
    # it (free: they are the major dims) at the row-major index of layer.
    lyr = jnp.int32(0)
    for i, n in zip(layer, stack):
        lyr = lyr * n + jnp.asarray(i, jnp.int32)

    def one_axis(pool):
        return None if pool is None else pool.reshape(
            (-1,) + pool.shape[len(layer):])

    k_pages, v_pages, k_scale_pages, v_scale_pages = map(
        one_axis, (k_pages, v_pages, k_scale_pages, v_scale_pages))

    n_blocks = block_tab.shape[1]
    rows = sq * group
    ppb = pages_per_block(hkv * page * d * k_pages.dtype.itemsize, page,
                          n_blocks)
    # Fold (b, hq, sq, d) position-major into (b, hkv, sq·group, d): row
    # rr belongs to query position rr // group, head group rr % group.
    q4 = (q.reshape(b, hkv, group, sq, d).transpose(0, 1, 3, 2, 4)
          .reshape(b, hkv, rows, d))
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    bt = block_tab.astype(jnp.int32)
    # a slot is live when the first entry its walk reads is allocated:
    # pages are allocated from the sequence's start and a flat group
    # frees none while the slot lives (the page of the current position
    # may not be yet); an idle slot's row is all unallocated.
    j0, _ = _walk(pos_arr, sq=sq, page=page, n_blocks=n_blocks, ppb=ppb,
                  window=window, ring=has_base)
    live = jnp.take_along_axis(bt, j0[:, None], axis=1)[:, 0] < n_pages
    # order[k]: the first live slot from slot k on, b where there is
    # none — order[0] the first live slot, order[i + 1] the one after i.
    idx = jnp.where(live, jnp.arange(b, dtype=jnp.int32), b)
    order = jax.lax.cummin(jnp.append(idx, jnp.int32(b)), reverse=True)
    prefetch = [pos_arr, bt.reshape(-1), live.astype(jnp.int32), order,
                lyr.reshape(1)]
    if has_base:
        prefetch.append(jnp.asarray(page_base, jnp.int32).reshape(-1))
    inputs = [q4, k_pages, v_pages]
    cols = ppb * page

    def _qmap(i, *prefs):
        return (i, 0, 0, 0)

    qspec = pl.BlockSpec((1, hkv, rows, d), _qmap)
    in_specs = [qspec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    if quantized:
        # each slot's scales as one row per KV head, by table entry and
        # padded to whole blocks: a block's scales are a lane slice.
        width = -(-n_blocks // ppb) * cols
        bt = jnp.minimum(block_tab, n_pages - 1)

        def scale_rows(sp):
            g = sp[lyr, bt][..., 0].transpose(0, 2, 1, 3)  # (b,hkv,nb,page)
            g = g.reshape(b, hkv, n_blocks * page)
            return jnp.pad(g, ((0, 0), (0, 0), (0, width - g.shape[-1])))

        inputs += [scale_rows(k_scale_pages), scale_rows(v_scale_pages)]
        in_specs += [pl.BlockSpec((1, hkv, width),
                                  lambda i, *prefs: (i, 0, 0))] * 2

    kernel = functools.partial(
        _flash_decode_paged_kernel, scale=scale, window=window, page=page,
        hkv=hkv, group=group, sq=sq, n_blocks=n_blocks, n_pages=n_pages,
        ppb=ppb, has_base=has_base, quantized=quantized)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, hkv, page, d), k_pages.dtype),
            pltpu.VMEM((2, ppb, hkv, page, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, d), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        # the buffer index and the in-flight copy cross grid steps.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(interpret),
    )(*prefetch, *inputs)

    return (out.reshape(b, hkv, sq, group, d).transpose(0, 1, 3, 2, 4)
            .reshape(b, hq, sq, d))
