"""The main-path Pallas kernels compile for a TPU v5e at published widths.

Nothing runs: each kernel is lowered for a described ``v5e:2x2`` chip and
handed to the TPU compiler, which refuses what interpret mode accepts
(misaligned tiles, too much VMEM or SMEM).  The topology is described
inside a fixture, so importing or collecting this file never loads the
TPU library, and where no topology can be described every test skips.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.configs.base import smoke_variant
from repro.kernels.flash_attention import (flash_attention_decode,
                                           flash_attention_decode_paged)
from repro.kernels.ssd_scan import ssd_scan
from repro.models import params, registry
from repro.models.cache_layouts import ring_blocks
from repro.serve.serve_loop import make_paged_decode_step

SLOTS, MAX_SEQ, PAGE = 4, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel was not compiled for TPU"


def _paged(one_chip, hq, hkv, d, kv_dtype, *, window=None, ring=False,
           scales=False, slots=SLOTS, n_blocks=None, n_pages=None,
           layers=None):
    """The kernel over one layer's pools, or with ``layers`` over the
    stacked pools the layer loop carries, read at a traced layer."""
    n_blocks = n_blocks or (ring_blocks(window, PAGE) if ring
                            else MAX_SEQ // PAGE)
    n_pages = n_pages or slots * n_blocks
    stack = (layers,) if layers else ()
    pool = stack + (n_pages, hkv, PAGE, d)
    shapes = [((slots, hq, 1, d), jnp.bfloat16), (pool, kv_dtype),
              (pool, kv_dtype), ((slots, n_blocks), jnp.int32),
              ((slots,), jnp.int32), ((), jnp.int32)]
    if ring:
        shapes.append(((slots, n_blocks), jnp.int32))
    if scales:
        shapes += [(stack + (n_pages, hkv, PAGE, 1), jnp.bfloat16)] * 2

    def fn(q, kp, vp, bt, pos, layer, *rest):
        base = rest[0] if ring else None
        ks, vs = rest[-2:] if scales else (None, None)
        return flash_attention_decode_paged(
            q, kp, vp, bt, pos, window=window, page_base=base,
            k_scale_pages=ks, v_scale_pages=vs, interpret=False,
            layer=(layer,) if layers else ())

    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_compiles_at_minitron_widths(one_chip, kv):
    """minitron-4b: 24 query heads over 8 KV heads (group 3), d 128."""
    if kv == "bf16":
        _paged(one_chip, 24, 8, 128, jnp.bfloat16)
    else:
        _paged(one_chip, 24, 8, 128, jnp.int8, scales=True)


@pytest.mark.parametrize("slots, n_blocks, n_pages, hq, hkv, layers", [
    (16, 256, 1664, 24, 8, 32),   # minitron-4b: max_seq 4096, page 16
    (64, 128, 8192, 48, 1, 11),   # granite-34b: max_seq 2048, one KV head
], ids=["minitron", "granite"])
def test_paged_decode_compiles_at_bench_shapes(one_chip, slots, n_blocks,
                                               n_pages, hq, hkv, layers):
    """The benchmark's serving shapes: every slot's block table and
    position in scalar memory, every layer's pools stacked in HBM as the
    layer loop carries them, read at a traced layer."""
    _paged(one_chip, hq, hkv, 128, jnp.bfloat16, slots=slots,
           n_blocks=n_blocks, n_pages=n_pages, layers=layers)


def test_paged_decode_step_takes_the_kernel_on_tpu(one_chip, monkeypatch):
    """With ``decode_flash`` unset the platform picks the read: lowered
    for the described chip (smoke widths, heads of 128 lanes), the paged
    decode step holds the kernel, and the kernel's op carries the
    ``attn_read`` scope the device-trace metrics read."""
    cfg = dataclasses.replace(smoke_variant(configs.get("minitron-4b")),
                              head_dim=128)
    assert cfg.decode_flash is None
    # the described chip is not the default backend of this process.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, max_seq, n_pages = 4, 256, 64

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    i32 = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    args = (shaped(registry.abstract(cfg)),
            shaped(params.abstract_params(registry.paged_cache_decls(
                cfg, n_pages, PAGE))),
            {"kv": jax.ShapeDtypeStruct((slots, max_seq // PAGE), jnp.int32,
                                        sharding=one_chip)},
            i32, i32, i32,
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip))
    text = make_paged_decode_step(cfg, max_seq, PAGE).lower(
        *args).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls, "the paged decode step holds no kernel"
    assert all(re.search(r'op_name="[^"]*attn_read', ln) for ln in calls)


def test_paged_decode_compiles_at_gemma3_widths_with_ring(one_chip):
    """gemma3-12b local layers: 16 over 8 heads, d 256, 1024-token
    window on a ring of pages with per-entry logical bases."""
    _paged(one_chip, 16, 8, 256, jnp.bfloat16, window=1024, ring=True)


def test_dense_decode_compiles_at_minitron_widths(one_chip):
    fn = functools.partial(flash_attention_decode, interpret=False)
    cache = ((SLOTS, 8, MAX_SEQ, 128), jnp.bfloat16)
    _compile(fn, one_chip, ((SLOTS, 24, 1, 128), jnp.bfloat16), cache,
             cache, ((SLOTS,), jnp.int32))


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    """mamba2-1p3b: d_inner 4096 as 64 heads of 64, state 128, chunk 256."""
    b, s, h, dh, ds = 1, 1024, 64, 64, 128
    fn = functools.partial(ssd_scan, chunk=256, interpret=False)
    _compile(fn, one_chip, ((b, s, h, dh), jnp.float32),
             ((b, s, h), jnp.float32), ((h,), jnp.float32),
             ((b, s, ds), jnp.float32), ((b, s, ds), jnp.float32))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_docqa_programs_fit_the_chip(one_chip, program):
    """The ``deepseek-v2-lite.doc-qa`` cell's decode and chunk programs
    at published widths (9 layers, 64 experts of 1,408, latent 512 +
    rope 64, vocabulary 102,400), bf16 weights, with the cell's
    ``n_slots`` and ``n_pages``: the TPU compiler accepts them, so they
    fit the chip's HBM (it refuses a program that does not, as it does
    the decode program over three times the pages, whose pools and
    weights alone exceed the chip), and the expert layers and the latent
    read keep their scopes."""
    from bench import cell
    from repro.serve.serve_loop import make_chunk_prefill_step, moe_load_init
    conf = cell.config("deepseek-v2-lite")
    cfg = cell.model_config(conf, "deepseek-v2-lite")
    sv = conf["serving"]
    slots, max_seq, page = sv["n_slots"], sv["max_seq"], sv["kv_page_size"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert sv["n_pages"] >= 5400      # 8 cached documents + 32 slots' own
    weights = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16 if jnp.issubdtype(
            a.dtype, jnp.floating) else a.dtype), registry.abstract(cfg))
    i32 = sds((slots,), jnp.int32)

    def state(n_pages):
        pools = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                             params.abstract_params(registry.paged_cache_decls(
                                 cfg, n_pages, page)))
        return (weights, pools, {"latent": sds((slots, max_seq // page),
                                               jnp.int32)},
                i32, i32, i32, sds((slots,), jnp.bool_))
    load = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                        jax.eval_shape(lambda: moe_load_init(cfg)))
    if program == "decode":
        fn = make_paged_decode_step(cfg, max_seq, page, count_moe=True)
        args = state(sv["n_pages"]) + (load,)
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            fn.lower(*state(3 * sv["n_pages"]), load).compile()
    else:
        chunk = sv["prefill_chunk"]
        fn = make_chunk_prefill_step(cfg, chunk, max_seq, page,
                                     count_moe=True)
        one = sds((1,), jnp.int32)
        s = sds((), jnp.int32)
        args = state(sv["n_pages"]) + (sds((1, chunk), jnp.int32), one, one,
                                       s, sds((), jnp.bool_), s, s, s, load)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    for scope in ("attn_read", "kv_write", "moe_route", "moe_experts",
                  "moe_shared"):
        assert scope in text, scope
