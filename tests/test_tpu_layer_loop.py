"""The layer loop updates the stacked KV pools in place, on the chip.

The benchmark's step programs are compiled for a described ``v5e:2x2``
chip at the cells' shapes (the fixture of ``test_tpu_compile.py``; every
test skips where no topology can be described).  The layer scan carries
every layer's pools whole, and each layer writes and reads its own pool
inside the stack.  So no op of a compiled program copies a whole stacked
pool or one layer's pool, slices a layer's pool out of the stack or
writes one back into it, and the program needs little memory besides
its arguments: a fraction of the pools' bytes where a second copy of the
pools used to sit.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.models import params, registry
from repro.serve.serve_loop import (make_chunk_prefill_step,
                                    make_paged_decode_step, moe_load_init)
from test_tpu_compile import one_chip  # noqa: F401  (the described chip)

# an HLO instruction: name, result type(s), opcode, operand names
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\((.*)$")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _program(cell_name, program, one_chip):
    """(compiled program, its pools' leaves) for a cell's configuration:
    bf16 weights, the cell's slots, table width and pool size, and the
    expert load where the model has experts, as the batcher runs it."""
    from bench import cell
    conf = cell.config(cell_name)
    cfg = cell.model_config(conf, cell_name)
    sv = conf["serving"]
    slots, max_seq, page = sv["n_slots"], sv["max_seq"], sv["kv_page_size"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    weights = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16 if jnp.issubdtype(
            a.dtype, jnp.floating) else a.dtype), registry.abstract(cfg))
    pools = shaped(params.abstract_params(registry.paged_cache_decls(
        cfg, sv["n_pages"], page)))
    (group,) = pools
    i32 = sds((slots,), jnp.int32)
    args = (weights, pools, {group: sds((slots, max_seq // page),
                                        jnp.int32)},
            i32, i32, i32, sds((slots,), jnp.bool_))
    kw, load = {}, ()
    if cfg.n_experts:
        kw = {"count_moe": True}
        load = (shaped(jax.eval_shape(lambda: moe_load_init(cfg))),)
    if program == "decode":
        fn = make_paged_decode_step(cfg, max_seq, page, **kw)
    else:
        chunk = sv.get("prefill_chunk") or 16
        fn = make_chunk_prefill_step(cfg, chunk, max_seq, page, **kw)
        one, s = sds((1,), jnp.int32), sds((), jnp.int32)
        args += (sds((1, chunk), jnp.int32), one, one, s,
                 sds((), jnp.bool_), s, s, s)
    return fn.lower(*args, *load).compile(), jax.tree.leaves(pools)


def _squeeze(shape):
    return tuple(d for d in shape if d != 1)


def _pool_traffic(text, pools):
    """The program's ops that copy a whole stacked pool or one layer's
    pool, slice one out, or write one back: copies and dynamic slices by
    their result, dynamic-update-slices by their update.  Shapes compare
    without their unit axes, which bitcasts add and remove."""
    shapes = {_squeeze(a.shape) for a in pools} | {_squeeze(a.shape[1:])
                                                   for a in pools}
    result, found = {}, []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, typ, op, rest = m.groups()
        dims = [_squeeze(int(d) for d in s.split(",") if d)
                for s in _SHAPE.findall(typ)]
        result[name] = dims[0] if dims else ()
        if op in ("copy", "copy-start", "dynamic-slice"):
            hit = any(d in shapes for d in dims)
        elif op == "dynamic-update-slice":
            update = re.findall(r"%([^,\s)]+)", rest)[1]
            hit = result.get(update) in shapes
        else:
            continue
        if hit:
            found.append(f"{op} {name} {typ[:60]}")
    return found


@pytest.mark.parametrize("cell_name, program", [
    ("minitron-4b", "decode"), ("minitron-4b", "chunk"),
    ("deepseek-v2-lite", "decode")])
def test_step_programs_update_pools_in_place(one_chip, monkeypatch,
                                             cell_name, program):
    """chat's decode (through the block-table kernel, as the platform
    picks it on a TPU) and chunk programs over 32 layers of 1,664 pages,
    and doc-qa's decode over 9 layers of 18,000 latent pages."""
    # the described chip is not the default backend of this process.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, pools = _program(cell_name, program, one_chip)
    text = compiled.as_text()
    if cell_name == "minitron-4b" and program == "decode":
        assert 'custom_call_target="tpu_custom_call"' in text
    assert _pool_traffic(text, pools) == []
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pools)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.15 * pool_bytes, (temp, pool_bytes)
