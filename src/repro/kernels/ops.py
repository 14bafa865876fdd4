"""Public jit'd wrappers over the Pallas kernels with XLA fallbacks.

The framework calls these; ``use_pallas`` selects the Pallas kernel vs
the pure-XLA reference path (what the 512-device dry-run lowers — Mosaic
cannot lower on CPU host devices, and the XLA path's HLO is the roofline
input; see DESIGN.md §9).  Whether a kernel is compiled or interpreted
is not a caller's choice: ``kernels.platform.interpret_mode`` interprets
on the CPU backend only.

These wrappers are shard-oblivious: under mesh-sharded serving
(docs/serving.md) they execute inside a ``shard_map`` body on
shard-local shapes (heads / ff already divided by tp) and never emit
collectives themselves — the psum/all_gather boundaries live in
``models.layers`` via ``distributed.sharding.psum_parts``/
``gather_parts``, so every kernel here stays a pure per-shard map.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .ssd_scan import ssd_scan
from .stencil import stencil2d
from .treereduce_kernel import tree_row_reduce


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                   "block_q", "block_k"))
def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              use_pallas: bool = False, block_q: int = 128,
              block_k: int = 128):
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k)
    return ref.attention_ref(q, k, v, causal=causal, window=window)


@partial(jax.jit, static_argnames=("window", "use_pallas"))
def paged_decode_attention(q, k_pages, v_pages, block_tab, pos, *,
                           window: Optional[int] = None,
                           page_base=None, k_scale_pages=None,
                           v_scale_pages=None, use_pallas: bool = False):
    """Paged-KV decode attention: q (b,hq,sq,d) against (n_pages, hkv,
    page, d) pools gathered through (b, n_blocks) block tables.  sq == 1
    is the plain decode step; sq > 1 is a speculative verify span at
    positions pos..pos+sq-1, each row with its own causal band.
    ``page_base`` carries ring-of-pages logical bases (window-bounded
    groups); ``*_scale_pages`` dequantize int8 pools in-kernel."""
    if use_pallas:
        from .flash_attention import flash_attention_decode_paged
        return flash_attention_decode_paged(q, k_pages, v_pages, block_tab,
                                            pos, window=window,
                                            page_base=page_base,
                                            k_scale_pages=k_scale_pages,
                                            v_scale_pages=v_scale_pages)
    return ref.paged_attention_ref(q, k_pages, v_pages, block_tab, pos,
                                   window=window, page_base=page_base,
                                   k_scale_pages=k_scale_pages,
                                   v_scale_pages=v_scale_pages)


@partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd(x, dt, A, B, C, *, chunk: int = 64, use_pallas: bool = False):
    """Batched SSD: x (b,s,h,dh), dt (b,s,h), A (h,), B/C (b,s,ds)."""
    if use_pallas:
        return ssd_scan(x, dt, A, B, C, chunk=chunk)
    y, _ = jax.vmap(
        lambda xx, dd, bb, cc: ref.ssd_chunked_ref(xx, dd, A, bb, cc,
                                                   chunk=chunk),
        in_axes=(0, 0, 0, 0))(x, dt, B, C)
    return y


@partial(jax.jit, static_argnames=("use_pallas", "block_rows"))
def stencil(x, *, use_pallas: bool = False, block_rows: int = 128):
    if use_pallas:
        return stencil2d(x, block_rows=block_rows)
    return ref.stencil2d_ref(x)


@partial(jax.jit, static_argnames=("op", "use_pallas"))
def row_reduce(x, *, op: str = "add", use_pallas: bool = False):
    if use_pallas:
        return tree_row_reduce(x, op=op)
    return ref.rowreduce_ref(x, op=op)


@partial(jax.jit, static_argnames=("use_pallas",))
def kv_quant(x, *, use_pallas: bool = False):
    """Row-wise int8 KV quantization: (rows, d) -> (int8, bf16 scales)."""
    if use_pallas:
        from .kv_quant import kv_quantize
        return kv_quantize(x)
    from ..models.layers import _kv_quantize
    return _kv_quantize(x)


@partial(jax.jit, static_argnames=("use_pallas",))
def fused_rmsnorm(x, w, *, use_pallas: bool = False):
    if use_pallas:
        from .rmsnorm_kernel import rmsnorm
        return rmsnorm(x, w)
    from ..models.layers import rmsnorm as rms_ref
    return rms_ref(x, w)
