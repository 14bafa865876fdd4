"""int8 KV-cache quantization kernel (the F5 DataPack story in silicon).

Per-(row) max-abs symmetric int8 quantization of KV tensors: one VMEM
pass computes the row max (a lane-level F7 tree reduction on the VPU),
scales, rounds, and emits int8 values + bf16 scales.  Tiles are
DataPack-aligned: the row block is a sublane multiple, head_dim is the
lane-aligned trailing dim.

Used by the §Perf int8 decode path (`kv_cache_dtype="int8"`): the XLA
formulation lives in ``models/layers._kv_quantize``; this kernel is the
TPU hot-path equivalent, validated against it in interpret mode.

The *paged* int8 KV cache reuses exactly this granularity: each page
pool carries int8 ``k``/``v`` pages plus bf16 ``k_scale``/``v_scale``
pages of shape (n_pages, hkv, page, 1) — one scale per (head, position)
row, matching the (rows, 1) scales emitted here — and
``flash_attention_decode_paged`` applies them in VMEM to the score and
probability columns of each page it copies (see ``models/cache_layouts`` and
``models/layers.attention_apply_paged``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import datapack
from .platform import interpret_mode


def _quant_kernel(x_ref, q_ref, s_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                  # (br, d)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # row max (VPU tree)
    scale = jnp.maximum(amax, eps) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(s_ref.dtype)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def kv_quantize(x: jnp.ndarray, block_rows: int = 256, eps: float = 1e-6,
                interpret: Optional[bool] = None):
    """x: (rows, d) -> (int8 (rows, d), bf16 scales (rows, 1))."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    rp = datapack.round_up(rows, block_rows)
    if rp != rows:
        x = jnp.pad(x, ((0, rp - rows), (0, 0)))
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, eps=eps),
        grid=(rp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rp, d), jnp.int8),
                   jax.ShapeDtypeStruct((rp, 1), jnp.bfloat16)],
        interpret=interpret_mode(interpret),
    )(x)
    return q[:rows], s[:rows]


def kv_dequantize(q: jnp.ndarray, s: jnp.ndarray, dtype=jnp.bfloat16,
                  block_rows: int = 256, interpret: Optional[bool] = None):
    rows, d = q.shape
    block_rows = min(block_rows, rows)
    rp = datapack.round_up(rows, block_rows)
    if rp != rows:
        q = jnp.pad(q, ((0, rp - rows), (0, 0)))
        s = jnp.pad(s, ((0, rp - rows), (0, 0)))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, d), dtype),
        interpret=interpret_mode(interpret),
    )(q, s)
    return out[:rows]
