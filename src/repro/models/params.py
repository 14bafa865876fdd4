"""Declarative parameter trees — one source of truth for init, sharding
specs, and abstract (dry-run) shapes.

Model code declares each tensor once as a ``Decl`` (shape + logical axes
+ init).  Three interpreters consume the same tree:

* ``init_params``      -> concrete arrays (deterministic per path), built
                          on the devices that hold them
* ``abstract_params``  -> ShapeDtypeStructs (the dry-run's no-allocation path)
* ``param_specs``      -> PartitionSpecs via the logical-axis rules

This is the F1 principle (configuration separated from source): sharding
lives in the rule table, not the model definition.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import spec_for


@dataclasses.dataclass(frozen=True)
class Decl:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"       # normal | zeros | ones
    std: Optional[float] = None  # override stddev for normal

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} / axes {self.axes} rank "
                             "mismatch")


def _is_decl(x) -> bool:
    return isinstance(x, Decl)


def stack_one(d: Decl, n: int) -> Decl:
    """Prepend a length-n "stack" axis (scan-over-layers layout)."""
    return Decl((n,) + d.shape, ("stack",) + d.axes, d.dtype, d.init, d.std)


def stack_decls(tree, n: int):
    return jax.tree.map(lambda d: stack_one(d, n), tree, is_leaf=_is_decl)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _fan_in(shape: Tuple[int, ...]) -> int:
    if len(shape) >= 2:
        return shape[-2]
    return shape[-1]


@functools.partial(jax.jit,
                   static_argnames=("shape", "dtype", "init", "sharding"))
def _init_leaf(key, std, *, shape, dtype, init, sharding):
    if init == "zeros":
        x = jnp.zeros(shape, dtype)
    elif init == "ones":
        x = jnp.ones(shape, dtype)
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        if dtype == jnp.float32:
            # XLA would fold ``std`` into the draw's own constant multiply
            # and move the last bit; the barrier keeps float32 leaves equal
            # to the plain eager draw.  Narrower dtypes fuse the draw into
            # the cast, so no float32 temporary of the leaf is ever held.
            x = jax.lax.optimization_barrier(x)
        x = (x * std).astype(dtype)
    if sharding is not None:
        # partitions the generation too: each device draws only its shard.
        x = jax.lax.with_sharding_constraint(x, sharding)
    return x


def init_params(decls, seed: int = 0, *, dtype=None, shardings=None):
    """Deterministic init: every leaf's key derives from its tree path, so
    adding/removing parameters never silently reshuffles others (a
    checkpoint-compat property the fault-tolerance layer relies on).

    Each leaf is built by one jitted initializer on the devices that hold
    it.  ``shardings`` (a tree matching ``decls`` of ``Sharding``s, or
    None for the default device) gives each device only its shard, so a
    sharded model never exists whole on one device.  ``dtype`` is the
    stored dtype of floating leaves (None keeps the declared one):
    serving passes the compute dtype, training keeps float32 masters.
    The values drawn do not depend on ``shardings``."""

    def leaf(path, d: Decl, sharding=None):
        h = int.from_bytes(
            hashlib.sha256(f"{seed}:{_path_str(path)}".encode()).digest()[:8],
            "little")
        floating = jnp.issubdtype(d.dtype, jnp.floating)
        out = jnp.dtype(dtype if dtype is not None and floating else d.dtype)
        std = d.std if d.std is not None else 1.0 / np.sqrt(_fan_in(d.shape))
        return _init_leaf(jax.random.key(h % (2 ** 63)), np.float32(std),
                          shape=tuple(d.shape), dtype=out, init=d.init,
                          sharding=sharding)

    if shardings is None:
        return jax.tree_util.tree_map_with_path(leaf, decls,
                                                is_leaf=_is_decl)
    return jax.tree_util.tree_map_with_path(leaf, decls, shardings,
                                            is_leaf=_is_decl)


def abstract_params(decls):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), decls,
        is_leaf=_is_decl)


def param_specs(decls, mesh=None):
    """Specs from logical axes with shape-aware assignment: a mesh axis
    that does not divide its dimension is skipped without being consumed
    (jit argument shardings must divide evenly — e.g. a batch-1 cache
    can't shard over 'data'; 40 kv heads can't take 'model', which then
    falls through to the kv_seq dim)."""
    return jax.tree.map(lambda d: spec_for(d.axes, mesh, d.shape), decls,
                        is_leaf=_is_decl)


def param_count(decls) -> int:
    return sum(int(np.prod(d.shape))
               for d in jax.tree.leaves(decls, is_leaf=_is_decl))


def cast_tree(params, dtype):
    return jax.tree.map(
        lambda p: p.astype(dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
