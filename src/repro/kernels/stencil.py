"""2D 4-point stencil via the shift-register pattern (paper Listing 6).

The hlslib version streams elements through a ``ShiftRegister<T, N, 1,
2N-1, 2N>`` and taps north/west/east/south.  TPU adaptation: the VPU is
a 2D vector unit, so instead of a scalar-per-cycle register chain we
tile *rows* into VMEM and realize the taps as whole-row shifts:

* north/south taps = neighbouring row blocks — expressed by passing the
  input three times with index maps (i-1, i, i+1), the Pallas idiom for
  halo exchange (a BlockSpec cannot overlap blocks);
* west/east taps = lane shifts within a row block.

The tap *offsets* are static (compile-time), matching hlslib's
compile-time-checked constant-offset access; `repro.core.shiftreg.ShiftReg`
is the software-emulation twin used by the dataflow example.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import datapack
from .platform import interpret_mode


def _stencil_kernel(prev_ref, cur_ref, next_ref, o_ref, *, block_rows: int,
                    n_rows: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)
    cur = cur_ref[...].astype(jnp.float32)            # (br, W)

    # North tap: rows shifted down by one; row 0 comes from prev block's
    # last row (zero at the global boundary).
    north_in = jnp.roll(cur, 1, axis=0)
    first_from_prev = prev_ref[...][-1:].astype(jnp.float32)
    first = jnp.where(i == 0, jnp.zeros_like(first_from_prev),
                      first_from_prev)
    north = jnp.concatenate([first, north_in[1:]], axis=0)

    # South tap: rows shifted up; last row from next block's first row.
    south_in = jnp.roll(cur, -1, axis=0)
    last_from_next = next_ref[...][:1].astype(jnp.float32)
    last = jnp.where(i == ni - 1, jnp.zeros_like(last_from_next),
                     last_from_next)
    south = jnp.concatenate([south_in[:-1], last], axis=0)

    # West/east taps: lane shifts with zero boundary.
    west = jnp.pad(cur, ((0, 0), (1, 0)))[:, :-1]
    east = jnp.pad(cur, ((0, 0), (0, 1)))[:, 1:]

    o_ref[...] = (0.25 * (north + south + west + east)).astype(o_ref.dtype)


def stencil2d(x: jnp.ndarray, block_rows: int = 128,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """One Jacobi sweep of the 4-point stencil; zero boundary."""
    H, W = x.shape
    block_rows = min(block_rows, H)
    Hp = datapack.round_up(H, block_rows)
    if Hp != H:
        x = jnp.pad(x, ((0, Hp - H), (0, 0)))
    grid = (Hp // block_rows,)
    n = Hp // block_rows

    out = pl.pallas_call(
        functools.partial(_stencil_kernel, block_rows=block_rows, n_rows=H),
        grid=grid,
        in_specs=[
            # prev / cur / next row blocks (halo via multi-ref indexing).
            pl.BlockSpec((block_rows, W),
                         lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, W),
                         lambda i, n=n: (jnp.minimum(i + 1, n - 1), 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Hp, W), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, x, x)
    return out[:H]


def stencil2d_iterated(x: jnp.ndarray, iters: int, block_rows: int = 128,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Iterated sweeps — the cyclic-dataflow workload of paper §II-C (same
    memory read and written every iteration)."""
    interpret = interpret_mode(interpret)

    def body(_, x):
        return stencil2d(x, block_rows=block_rows, interpret=interpret)
    return jax.lax.fori_loop(0, iters, body, x) if not interpret else \
        functools.reduce(lambda a, _: stencil2d(a, block_rows, interpret),
                         range(iters), x)
