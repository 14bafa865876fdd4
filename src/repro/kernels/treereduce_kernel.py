"""Lane-level balanced tree reduction (paper §III-D) as a Pallas kernel.

The hlslib ``TreeReduce`` guarantees a balanced binary combine tree in
hardware.  The TPU analogue: reduce a row of N lanes by ⌈log2 N⌉ halving
steps — each step a full-width vector op on the VPU — instead of a
serial accumulation chain.  The combine order is *static and balanced*,
so results are bit-reproducible across backends and block sizes (tested
against both the oracle and ``repro.core.treereduce.tree_reduce``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import datapack
from .platform import interpret_mode
from ..core import treereduce as tr


_OPS = {"add": (jnp.add, 0.0), "max": (jnp.maximum, -jnp.inf)}


def _tree_kernel(x_ref, o_ref, *, op: str, n_logical: int):
    combine, ident = _OPS[op]
    x = x_ref[...].astype(jnp.float32)               # (br, Np2)
    if n_logical < x.shape[-1]:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(lane < n_logical, x, ident)
    # Balanced halving: ⌈log2 N⌉ combines, each a full-width vector op.
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = combine(x[:, :half], x[:, half:])
    o_ref[...] = jnp.broadcast_to(x, o_ref.shape).astype(o_ref.dtype)


def tree_row_reduce(x: jnp.ndarray, op: str = "add", block_rows: int = 256,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Reduce the last axis of (rows, N) with a guaranteed balanced tree.

    Output is (rows,).  N is padded to a power of two with the operator
    identity (tree stays balanced; identity legs are no-ops), mirroring
    ``core.treereduce.tree_reduce``.
    """
    rows, n = x.shape
    combine, ident = _OPS[op]
    p2 = 1 << (n - 1).bit_length()
    if p2 != n:
        x = jnp.pad(x, ((0, 0), (0, p2 - n)), constant_values=ident)
    block_rows = min(block_rows, rows)
    rp = datapack.round_up(rows, block_rows)
    if rp != rows:
        x = jnp.pad(x, ((0, rp - rows), (0, 0)), constant_values=ident)

    out = pl.pallas_call(
        functools.partial(_tree_kernel, op=op, n_logical=n),
        grid=(rp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, p2), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 1), x.dtype),
        interpret=interpret_mode(interpret),
    )(x)
    return out[:rows, 0]
