"""Fused RMSNorm kernel: one VMEM pass per row block (read x once,
write y once) instead of the XLA decomposition's separate
square/mean/rsqrt/mul materializations.

The row-mean of squares is a lane-level balanced reduction (F7); the
(1 + w) weighting follows the models' convention (`layers.rmsnorm` is
the oracle — gemma-style zero-centered gains).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import datapack
from .platform import interpret_mode


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                    # (br, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))
                  ).astype(o_ref.dtype)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6,
            block_rows: int = 256, interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (rows, d); w: (d,).  Returns normalized x in x.dtype."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    rp = datapack.round_up(rows, block_rows)
    if rp != rows:
        x = jnp.pad(x, ((0, rp - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, d), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, w)
    return out[:rows]
