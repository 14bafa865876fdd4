"""Mamba2 SSD (state-space duality) chunked scan for TPU.

Hardware adaptation: the CUDA Mamba2 kernel leans on warp-level shuffles
for the intra-chunk scan.  On TPU we use the *duality* itself as the
adaptation: the chunked form turns the recurrence into MXU-shaped
matmuls — (Q×Q)·(Q×dh) intra-chunk "attention" plus a small (ds×dh)
carried state — and the sequential Pallas grid carries the state across
chunks in VMEM scratch (same idiom as the flash kernel's online
softmax).  No shuffle analogue is needed; the systolic array does the
work.  The carried state is a literal F6 shift register of depth 1 over
chunks; the decay-weighted combine is the F7 functor pattern.

Layout: one grid row per (batch·head); chunk index innermost.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import datapack
from .platform import interpret_mode


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_scr, *,
                chunk: int):
    i, jc = pl.program_id(0), pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0].astype(jnp.float32)         # (Q, dh)
    dt = dt_ref[0].astype(jnp.float32)       # (Q, 1)  [lane-padded]
    A = a_ref[i]                             # scalar for this head (SMEM)
    B = b_ref[0].astype(jnp.float32)         # (Q, ds)
    C = c_ref[0].astype(jnp.float32)         # (Q, ds)

    dtA = dt * A                             # (Q, 1)
    # Prefix sums as matmuls with a lower-triangular ones matrix (Mosaic
    # has no cumsum): cum as a column and, contracting the other way, as
    # a row, so the pairwise differences need no transpose.
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    qq_mask = cols <= rows
    tri = qq_mask.astype(jnp.float32)
    cum = jax.lax.dot_general(tri, dtA, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q, 1)
    cum_row = jax.lax.dot_general(dtA, tri, (((0,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    total = jnp.sum(dtA, axis=0, keepdims=True)                    # (1, 1)
    # Intra-chunk quadratic term on the MXU.
    L = jnp.where(qq_mask, jnp.exp(cum - cum_row), 0.0)  # (Q, Q)
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    W = G * L
    xdt = x * dt                             # (Q, dh)
    y = jax.lax.dot_general(W, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # Inter-chunk: y += exp(cum) * (C @ S)
    S = s_scr[...]                           # (ds, dh)
    y += jnp.exp(cum) * jax.lax.dot_general(
        C, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    # State update: S' = exp(total) S + B^T diag(exp(total-cum)·dt) x
    decay_last = jnp.exp(total - cum)        # (Q, 1)
    s_scr[...] = S * jnp.exp(total) + jax.lax.dot_general(
        B * (decay_last * dt), x,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, chunk: int = 64,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (b, s, h, dh); dt: (b, s, h); A: (h,); B, C: (b, s, ds)
    [ngroups = 1].  Returns y: (b, s, h, dh).  ``s % chunk == 0``.
    """
    b, s, h, dh = x.shape
    ds = B.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} not a multiple of chunk {chunk}")
    n = s // chunk

    # Lay out as (b·h, s, ·) rows so one grid row owns one head's scan.
    xr = x.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, s, 1)
    ar = jnp.broadcast_to(A[None, :], (b, h)).reshape(b * h)
    br = jnp.broadcast_to(B[:, None], (b, h, s, ds)).reshape(b * h, s, ds)
    cr = jnp.broadcast_to(C[:, None], (b, h, s, ds)).reshape(b * h, s, ds)

    grid = (b * h, n)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, ds), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, ds), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((ds, dh), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(xr, dtr, ar, br, cr)

    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
