"""Random weights of a model, made on the device from the seed.

One jitted call builds the whole tree in the dtype it is served in.  The
tree is the one the model's family describes (``families/<family>.py``,
``shapes``), which is the serving program's checkpoint layout;
``run.py`` checks it against the program's own parameter shapes before
it hands it over.  The reference makes the same tree again with this
same call, so it never reads an array the program has held.

Every leaf is uniform in [-sqrt(3) std, sqrt(3) std], so its standard
deviation is the ``std`` its family gives.  Uniform draws are exact bit
manipulations, so the values do not depend on how XLA fuses the call.
"""

from __future__ import annotations

import functools
import zlib
from typing import Dict, Tuple

import numpy as np


def nest(flat: Dict[str, object]) -> Dict:
    tree: Dict = {}
    for path, v in flat.items():
        node = tree
        *head, leaf = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[leaf] = v
    return tree


def _seed_words(seed: int) -> Tuple[int, int]:
    seed = int(seed)
    return seed % (2 ** 31), (seed // 2 ** 31) % (2 ** 31)


@functools.lru_cache(maxsize=8)
def _maker(dims: Tuple[Tuple[str, object], ...], dtype: str):
    import jax
    import jax.numpy as jnp
    from bench import cell
    m = dict(dims)
    leaves = cell.family(m["family"]).shapes(m)

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        flat = {}
        for path, (shape, std) in sorted(leaves.items()):
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) % 2 ** 31)
            a = float(np.sqrt(3.0) * std)
            flat[path] = jax.random.uniform(
                k, shape, jnp.float32, -a, a).astype(dtype)
        return nest(flat)

    return jax.jit(make)


def make(m: Dict[str, object], seed: int, dtype: str = "bfloat16"):
    """The weight tree for model dims ``m`` (``cell.model_dims``) from
    ``seed``, on the default device, in ``dtype``."""
    import jax.numpy as jnp
    lo, hi = _seed_words(seed)
    return _maker(tuple(sorted(m.items())), dtype)(
        jnp.int32(lo), jnp.int32(hi))
