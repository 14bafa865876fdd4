"""Serving: prefill/decode step builders + a simple generation driver.

``make_serve_steps`` builds the two jitted entry points the dry-run
lowers for the ``prefill_*`` / ``decode_*`` / ``long_*`` shape cells:

* ``prefill(params, batch)``            -> (logits_last, cache)
* ``decode(params, cache, tokens, pos)`` -> (logits, cache)

Caches are declarative (``registry.cache_decls``) so shardings come from
the same logical-axis rules as parameters — the MLA compressed cache and
the sliding-window ring caches are just different Decl trees.

Serving fast path (hlslib-style: keep the hot loop inside the pipeline):
``make_sampling_serve_steps`` fuses token *sampling* into the jitted
steps, so each call returns int32 token ids instead of a full vocab row
of logits.  The per-token device->host transfer drops from
``4·vocab`` bytes/slot to 4 bytes/slot, and XLA is free to fuse the
unembed matmul with the argmax/categorical reduction — the logits never
materialize in host memory at all.  ``greedy_generate`` drives this fused
path; the raw-logits builders remain for the dry-run and for callers that
post-process distributions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..distributed import sharding
from ..models import registry
from ..models import params as PP


# --- mesh-sharded paged serving (tensor parallelism under shard_map) ------------------
#
# ``cfg.mesh_shape`` puts the three paged step programs below under
# ``jax.shard_map`` on a serving mesh (launch.mesh.serving_mesh): model
# parameters and KV page pools shard over the LAST mesh axis
# (cfg.tp_axis) per the DEFAULT_RULES logical-axis table, block tables
# and slot state stay replicated, and the step body runs the unchanged
# per-shard model with explicit collectives (sharding.psum_parts /
# gather_parts) at the attention / FF projection boundaries.  The body
# sees a *shard-local* ModelConfig (heads / kv-heads / ff divided by the
# tp extent) so every reshape in models.layers is automatically
# per-shard; the MLA latent pool shards over the lora dim and is
# detected from the pool shape inside mla_apply_paged.  Token streams
# stay bit-identical to the 1-device path for float32 configs: the
# sharded matmuls split only *output* columns (contraction dims are
# never sharded), psum adds per-shard partials in fixed axis order, and
# gathers are pure concats.


@functools.lru_cache(maxsize=16)
def _mesh_cache(mesh_shape: Tuple[int, ...], tp_axis: str):
    from ..launch.mesh import serving_mesh
    return serving_mesh(mesh_shape, tp_axis)


def serving_mesh_for(cfg: ModelConfig):
    """(mesh, tp_axis) for the sharded paged path; (None, None) when
    cfg.mesh_shape is empty (the plain single-device path)."""
    if not cfg.mesh_shape:
        return None, None
    sharding.validate_shardable(cfg, int(cfg.mesh_shape[-1]))
    return _mesh_cache(tuple(cfg.mesh_shape), cfg.tp_axis), cfg.tp_axis


def shard_local_cfg(cfg: ModelConfig) -> ModelConfig:
    """The per-shard view of the model the shard_map body runs: column-
    sharded dims (query/kv heads, ff) divided by the tp extent so the
    layer reshapes are shard-local.  MLA keeps the FULL kv_lora_rank
    (w_dkv / kv_norm stay replicated; only the latent *pool* shards) and
    vocab_size stays full (the per-shard logits tile is detected against
    padded_vocab and gathered).  mesh_shape is cleared so the local cfg
    can never recursively build sharded steps."""
    t = int(cfg.mesh_shape[-1]) if cfg.mesh_shape else 1
    kw: Dict[str, Any] = {"mesh_shape": ()}
    if t > 1:
        kw["n_heads"] = cfg.n_heads // t
        kw["d_ff"] = cfg.d_ff // t
        if cfg.moe_d_ff:
            kw["moe_d_ff"] = cfg.moe_d_ff // t
        if not cfg.mla:
            kw["n_kv_heads"] = cfg.n_kv_heads // t
    return dataclasses.replace(cfg, **kw)


def paged_sharding_specs(cfg: ModelConfig, page_size: int, mesh):
    """(param_specs, pool_specs) PartitionSpec trees for the sharded
    paged path, both derived from the same Decl logical axes via the
    DEFAULT_RULES table — with two serving-specific rule overrides:

    * params: ``experts -> None`` (expert-parallel dispatch is deferred
      until the mesh work settles — expert ff dims column-shard over
      'model' instead, matching the dense MLP) and the token-embedding
      table is forced replicated (its vocab dim is *gathered by token
      id*, which a row-sharded table cannot serve; the unembed
      projection stays vocab-column-sharded).
    * pools: ``lora -> 'model'`` so MLA latent pages shard over the
      compressed dim (per-layer w_dkv keeps lora -> None from the param
      pass, staying replicated).  GQA/int8 pools shard over their
      kv_heads axis straight from the default table; k_rope / scale
      page axes are untouched.
    """
    with sharding.use_rules(experts=None):
        p_specs = PP.param_specs(registry.decls(cfg), mesh)
    if "embed" in p_specs:
        p_specs["embed"] = P()
    from ..models.cache_layouts import get_layout
    layout = get_layout(cfg, page_size)
    pool_decls = registry.paged_cache_decls(
        cfg, {g.name: 1 for g in layout.groups}, page_size)
    with sharding.use_rules(lora=("model",)):
        pool_specs = PP.param_specs(pool_decls, mesh)
    return p_specs, pool_specs


def named_shardings(mesh, specs):
    """A PartitionSpec tree as NamedShardings on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def serving_params(cfg: ModelConfig, seed: int = 0):
    """Random weights from ``seed`` for serving, stored in ``cfg.dtype``
    (the dtype the forward computes in, so no step re-casts them) and
    built where they live: each shard on its own device of the serving
    mesh when ``cfg.mesh_shape`` names one, else on the default device.
    Training keeps ``registry.init``'s float32 master weights."""
    mesh, _ = serving_mesh_for(cfg)
    shardings = None
    if mesh is not None:
        p_specs, _ = paged_sharding_specs(cfg, cfg.kv_page_size, mesh)
        shardings = named_shardings(mesh, p_specs)
    return registry.init(cfg, seed, dtype=cfg.dtype, shardings=shardings)


def make_serve_steps(cfg: ModelConfig, batch: int, max_seq: int,
                     mesh: Optional[Mesh] = None):
    decls = registry.decls(cfg)
    cache_d = registry.cache_decls(cfg, batch, max_seq)
    ab_cache = PP.abstract_params(cache_d)
    c_specs = PP.param_specs(cache_d, mesh)
    p_specs = PP.param_specs(decls, mesh)

    def prefill(params, batch_in):
        logits, cache = registry.forward(cfg, params, batch_in,
                                         mode="prefill", cache_len=max_seq)
        return logits, cache

    def decode(params, cache, tokens, pos):
        batch_in = dict(tokens)
        logits, cache = registry.forward(cfg, params, batch_in,
                                         mode="decode", cache=cache, pos=pos)
        return logits, cache

    if mesh is None:
        return (jax.jit(prefill), jax.jit(decode, donate_argnums=(1,)),
                ab_cache, None)

    ns = functools.partial(named_shardings, mesh)
    batch_axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and batch % (prod * mesh.shape[a]) == 0:
            batch_axes.append(a)
            prod *= mesh.shape[a]
    bspec = NamedSharding(mesh, P(tuple(batch_axes)) if batch_axes else P())
    pre = jax.jit(prefill, in_shardings=(ns(p_specs), bspec),
                  out_shardings=(bspec, ns(c_specs)))
    dec = jax.jit(decode,
                  in_shardings=(ns(p_specs), ns(c_specs), bspec, None),
                  out_shardings=(bspec, ns(c_specs)),
                  donate_argnums=(1,))
    return pre, dec, ab_cache, (ns(p_specs), ns(c_specs))


def _sample_last(logits_last: jnp.ndarray, key, temperature: float
                 ) -> jnp.ndarray:
    """On-device sampling of the last-position logits.

    logits_last: (b, Vp) or (b, K, Vp) for the audio family.  Static
    ``temperature``: 0 -> argmax (key unused, DCE'd by jit); > 0 ->
    temperature-scaled categorical.
    """
    if temperature > 0:
        return jax.random.categorical(
            key, logits_last / temperature).astype(jnp.int32)
    return jnp.argmax(logits_last, axis=-1).astype(jnp.int32)


@functools.lru_cache(maxsize=32)
def make_sampling_serve_steps(cfg: ModelConfig, batch: int, max_seq: int,
                              temperature: float = 0.0):
    """Fused sample-in-decode step builders (the serving fast path).

    * ``prefill(params, batch_in, last_pos, key)`` -> (tokens, cache)
    * ``decode(params, cache, tokens, pos, key)``  -> (tokens, cache)

    Both return int32 token ids (shape (b,), or (b, K) for audio) — not
    logits — so the only per-step host transfer is a small int vector.
    ``last_pos`` is the per-sequence index of the true last prompt token,
    enabling right-padded (bucketed) prompts.  The decode step donates the
    cache so slot state stays device-resident with no copies.

    Builders are lru_cached by (cfg, batch, max_seq, temperature): driving
    many generations against one model reuses the same compiled steps.
    """

    def prefill(params, batch_in, last_pos, key):
        logits, cache = registry.forward(cfg, params, batch_in,
                                         mode="prefill", cache_len=max_seq,
                                         last_pos=last_pos)
        return _sample_last(logits[:, -1], key, temperature), cache

    def decode(params, cache, tokens, pos, key):
        batch_in = dict(tokens)
        logits, cache = registry.forward(cfg, params, batch_in,
                                         mode="decode", cache=cache, pos=pos)
        return _sample_last(logits[:, -1], key, temperature), cache

    return (jax.jit(prefill), jax.jit(decode, donate_argnums=(1,)))


# --- paged serving steps (page-pool KV + block tables) --------------------------------
#
# The continuous batcher's paged mode drives two jitted programs:
#
# * ``make_paged_decode_step`` — ONE batched call advances every slot
#   (pos is a per-slot vector, so no vmap is needed: the paged attention
#   path handles per-row positions natively).  Inactive slots have their
#   block-table rows masked to the invalid page id, so their cache writes
#   scatter-drop and cannot corrupt pages that were freed and reallocated
#   to a request that is still mid-admission.
# * ``make_chunk_prefill_step`` — one prompt *chunk* for one slot, at a
#   single compiled shape per chunk size (vs the dense path's
#   n_slots-row padded prefill per pow2 bucket).  The final chunk also
#   installs the slot's decode state (first sampled token, position,
#   budget, active flag) on device, gated by the traced ``is_final`` flag
#   so both chunk kinds share one compiled program.
#
# Pools and block tables are dicts keyed by the layout's page groups
# (``models.cache_layouts``): {"kv"} for flat GQA/int8 layouts,
# {"local", "global"} for gemma3, {"latent"} for MLA.


def _shard_wrap(cfg: ModelConfig, page_size: int, fn, n_extra_in: int,
                n_extra_out: int, donate: Tuple[int, ...]):
    """jit a paged step body, under ``jax.shard_map`` when the cfg names
    a serving mesh: params + KV pools follow their PartitionSpec trees,
    every other input/output (block tables, slot vectors, token
    payloads) is replicated (``P()`` works as a pytree prefix over the
    per-group dicts).  ``check_vma=False``: the body's outputs are made
    replicated by explicit psum/gather collectives inside helpers that
    degrade to identity off the mesh, so the body carries no
    varying-axis annotations for the checker to follow.  Donation
    carries over unchanged — donated leaves are resharded in place."""
    mesh, axis = serving_mesh_for(cfg)
    if mesh is None:
        return jax.jit(fn, donate_argnums=donate)
    p_specs, pool_specs = paged_sharding_specs(cfg, page_size, mesh)

    def body(*args):
        with sharding.manual_axis(axis):
            return fn(*args)

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, pool_specs) + (P(),) * n_extra_in,
        out_specs=(pool_specs,) + (P(),) * n_extra_out,
        check_vma=False)
    return jax.jit(sm, donate_argnums=donate)


def _paged_decode_forward(cfg: ModelConfig, page_size: int):
    """The decode-step forward shared by the decode and logits steps:
    inactive slots' table rows are masked to the invalid page id, then
    one batched forward returns (logits (n_slots, 1, Vp), new pools)."""
    from ..models.cache_layouts import get_layout
    layout = get_layout(cfg, page_size)
    fcfg = shard_local_cfg(cfg)

    def forward(params, pools, block_tab, last_tok, pos, active,
                with_routes=False):
        bt = {}
        for g in layout.groups:
            n_pages = jax.tree.leaves(pools[g.name])[0].shape[
                layout.page_axis(g.name)]
            bt[g.name] = jnp.where(active[:, None], block_tab[g.name],
                                   n_pages)
        cache = {"pages": pools, "block_tab": bt}
        return registry.forward(
            fcfg, params, {"tokens": last_tok[:, None]}, mode="decode",
            cache=cache, pos=pos, with_routes=with_routes)

    return forward


# --- expert load (stats()["moe"]) ------------------------------------------------------
#
# With ``count_moe`` the decode and chunk programs take one more argument
# and hand it back updated: the expert load, accumulated on the device
# and read only when the batcher's stats() asks.  Per MoE layer: the
# live tokens routed to each expert by decode calls and by prefill
# chunks, the distinct experts hit per decode call summed, and the
# decode calls.


def moe_load_init(cfg: ModelConfig):
    """The zero expert load of ``cfg``'s MoE layers."""
    n = cfg.n_layers - (cfg.first_dense_layers if cfg.mla else 0)
    z = functools.partial(jnp.zeros, dtype=jnp.int32)
    return {"decode": z((n, cfg.n_experts)),
            "prefill": z((n, cfg.n_experts)), "hit": z((n,)),
            "calls": z(())}


def _routed(routes, valid, n_experts):
    """Tokens routed to each expert, (layers, E), over the valid tokens:
    ``routes`` (layers, b, s, k), ``valid`` (b, s) int32."""
    oh = jax.nn.one_hot(routes, n_experts, dtype=jnp.int32)
    return (oh * valid[None, :, :, None, None]).sum((1, 2, 3))


@functools.lru_cache(maxsize=32)
def make_paged_decode_step(cfg: ModelConfig, max_seq: int, page_size: int,
                           count_moe: bool = False):
    """Jitted batched decode over paged KV: advances all slots at once.
    ``count_moe``: the expert load is the last argument and output."""
    forward = _paged_decode_forward(cfg, page_size)
    i32 = jnp.int32

    def step_fn(params, pools, block_tab, last_tok, pos, remaining, active,
                *load):
        res = forward(params, pools, block_tab, last_tok, pos, active,
                      with_routes=count_moe)
        logits, new_pools = res[:2]
        live = active.astype(i32)
        with jax.named_scope("logits"):
            nxt = jnp.argmax(logits[:, -1], -1).astype(i32)
        nxt = jnp.where(active, nxt, last_tok)
        pos = jnp.where(active, pos + 1, pos)
        remaining = jnp.where(active, remaining - 1, remaining)
        finished = active & ((remaining <= 0) | (pos >= max_seq - 1))
        active = active & ~finished
        out = jnp.stack([nxt, finished.astype(i32)])   # (2, n_slots)
        if not count_moe:
            return new_pools, nxt, pos, remaining, active, out
        (ld,) = load
        n = _routed(res[2], live[:, None], cfg.n_experts)
        ld = dict(ld, decode=ld["decode"] + n,
                  hit=ld["hit"] + (n > 0).sum(-1, dtype=i32),
                  calls=ld["calls"] + 1)
        return new_pools, nxt, pos, remaining, active, out, ld

    n = int(count_moe)
    return _shard_wrap(cfg, page_size, step_fn, n_extra_in=5 + n,
                       n_extra_out=5 + n,
                       donate=(1, 3, 4, 5, 6) + ((7,) if n else ()))


@functools.lru_cache(maxsize=32)
def make_paged_logits_step(cfg: ModelConfig, page_size: int):
    """The next decode step's logits, (n_slots, Vp) float32, from a
    batcher's paged state (``params, pools, block_tab, last_tok, pos,
    active``) without advancing it: the decode step's own forward, for
    comparing read paths or a mesh against one device.  Returns
    (pools with the step's K/V written, logits); nothing is donated."""
    forward = _paged_decode_forward(cfg, page_size)

    def logits_fn(params, pools, block_tab, last_tok, pos, active):
        logits, new_pools = forward(params, pools, block_tab, last_tok, pos,
                                    active)
        return new_pools, logits[:, -1].astype(jnp.float32)

    return _shard_wrap(cfg, page_size, logits_fn, n_extra_in=4,
                       n_extra_out=1, donate=())


@functools.lru_cache(maxsize=32)
def make_spec_verify_step(cfg: ModelConfig, k: int, max_seq: int,
                          page_size: int):
    """Jitted batched speculative *verify* step: one (b, k) forward
    scores a k-token span per slot in a single call.

    Row layout per slot: ``tokens[:, 0]`` is the last committed token
    (position ``pos``), ``tokens[:, 1:1+n_draft]`` the drafted tokens,
    and the remaining columns padding (any value — they are agreement-
    masked).  Greedy argmax of logits row i predicts position
    ``pos + i + 1``; the accepted length is the longest prefix of drafts
    agreeing with those predictions, and every verify commits at least
    one token (the plain-decode equivalent: n_draft == 0 rows commit
    exactly 1, so ONE compiled program serves mixed spec/non-spec
    batches).  KV for the whole span is written by the forward.

    The scratch redirection happens *inside* the jit: ``copy_src`` /
    ``copy_dst`` name the old -> scratch page copies per group and
    ``swap_rows``/``swap_cols``/``swap_vals`` the block-table entries to
    repoint (all fixed-length, padded with out-of-range indices that
    ``mode="drop"`` discards), so the whole round — copy, swap, span
    forward, agreement — is ONE dispatch and the batcher's own device
    table is never touched by speculation (rollback is free; only
    commits scatter it afterwards).

    Host transfer: one (k + 2, n_slots) int32 — k prediction rows, the
    per-slot commit count, and the finished flags."""
    from ..models.cache_layouts import get_layout
    layout = get_layout(cfg, page_size)
    fcfg = shard_local_cfg(cfg)
    i32 = jnp.int32

    def verify_fn(params, pools, block_tab, tokens, n_draft, pos,
                  remaining, active, copy_src, copy_dst, swap_rows,
                  swap_cols, swap_vals):
        pools = dict(pools)
        bt = {}
        for g in layout.groups:
            ax = layout.page_axis(g.name)
            n_pages = jax.tree.leaves(pools[g.name])[0].shape[ax]
            si = jnp.clip(copy_src[g.name], 0, n_pages - 1)
            di = copy_dst[g.name]
            pools[g.name] = jax.tree.map(
                lambda a, si=si, di=di, ax=ax: a.at[
                    (slice(None),) * ax + (di,)].set(
                    jnp.take(a, si, axis=ax), mode="drop"),
                pools[g.name])
            tab = block_tab[g.name].at[
                swap_rows[g.name], swap_cols[g.name]].set(
                swap_vals[g.name], mode="drop")
            bt[g.name] = jnp.where(active[:, None], tab, n_pages)
        cache = {"pages": pools, "block_tab": bt}
        logits, new_pools = registry.forward(
            fcfg, params, {"tokens": tokens}, mode="verify", cache=cache,
            pos=pos)
        preds = jnp.argmax(logits, -1).astype(i32)          # (n, k)
        # drafts agree while they match the model's own greedy argmax.
        agree = (tokens[:, 1:] == preds[:, :-1]) \
            & (jnp.arange(k - 1)[None, :] < n_draft[:, None])
        acc = jnp.sum(jnp.cumprod(agree.astype(i32), axis=1), axis=1)
        commit = jnp.minimum(jnp.minimum(acc + 1, remaining),
                             jnp.maximum(max_seq - 1 - pos, 1))
        commit = jnp.where(active, commit, 0)
        last = jnp.take_along_axis(
            preds, jnp.clip(commit - 1, 0, k - 1)[:, None], axis=1)[:, 0]
        last_tok = jnp.where(active, last, tokens[:, 0])
        pos = pos + commit
        remaining = remaining - commit
        finished = active & ((remaining <= 0) | (pos >= max_seq - 1))
        active = active & ~finished
        out = jnp.concatenate(
            [preds.T, commit[None, :], finished.astype(i32)[None, :]])
        return new_pools, last_tok, pos, remaining, active, out

    return _shard_wrap(cfg, page_size, verify_fn, n_extra_in=11,
                       n_extra_out=5, donate=(1, 5, 6, 7))


@functools.lru_cache(maxsize=32)
def make_chunk_prefill_step(cfg: ModelConfig, chunk: int, max_seq: int,
                            page_size: int, count_moe: bool = False):
    """Jitted single-request prefill chunk against the paged cache.

    ``cache_offset`` (traced scalar) is the prefix-cache read-only
    boundary: positions below it live in shared prefix pages and are
    never rewritten (0 = plain chunked prefill; one compiled program
    serves both the cold and the cache-hit path).  ``count_moe``: the
    expert load is the last argument and output."""
    from ..models.cache_layouts import get_layout
    layout = get_layout(cfg, page_size)
    fcfg = shard_local_cfg(cfg)
    i32 = jnp.int32

    def chunk_fn(params, pools, block_tab, last_tok, pos, remaining, active,
                 tokens, pos0, last_in_chunk, slot_idx, is_final, plen,
                 max_new, cache_offset, *load):
        n_slots = jax.tree.leaves(block_tab)[0].shape[0]
        bt_row = {g.name: jax.lax.dynamic_index_in_dim(
            block_tab[g.name], slot_idx, 0) for g in layout.groups}
        cache = {"pages": pools, "block_tab": bt_row}
        res = registry.forward(
            fcfg, params, {"tokens": tokens}, mode="chunk", cache=cache,
            pos=pos0, last_pos=last_in_chunk,
            cache_offset=jnp.broadcast_to(cache_offset, (1,)),
            with_routes=count_moe)
        logits, new_pools = res[:2]
        with jax.named_scope("logits"):
            tok0 = jnp.argmax(logits[0, -1], -1).astype(i32)
        # final chunk installs the slot's decode state; non-final chunks
        # scatter-drop (idx == n_slots) and leave every vector untouched.
        idx = jnp.where(is_final, slot_idx, n_slots)
        last_tok = last_tok.at[idx].set(tok0, mode="drop")
        pos = pos.at[idx].set(plen, mode="drop")
        remaining = remaining.at[idx].set(max_new - 1, mode="drop")
        alive = (max_new > 1) & (plen < max_seq - 1)
        active = active.at[idx].set(alive, mode="drop")
        if not count_moe:
            return new_pools, last_tok, pos, remaining, active, tok0
        (ld,) = load
        prompt = jnp.arange(chunk)[None, :] <= last_in_chunk[:, None]
        ld = dict(ld, prefill=ld["prefill"] + _routed(
            res[2], prompt.astype(i32), cfg.n_experts))
        return new_pools, last_tok, pos, remaining, active, tok0, ld

    n = int(count_moe)
    return _shard_wrap(cfg, page_size, chunk_fn, n_extra_in=13 + n,
                       n_extra_out=5 + n,
                       donate=(1, 3, 4, 5, 6) + ((15,) if n else ()))


def greedy_generate(cfg: ModelConfig, params, prompt_batch: Dict,
                    steps: int, max_seq: int, temperature: float = 0.0,
                    seed: int = 0):
    """CPU-runnable generation driver (examples + integration tests).

    Runs on the fused sample-in-decode fast path: every jitted call
    returns int32 token ids, so the host never sees a logits row."""
    tok = prompt_batch["tokens"]
    b = tok.shape[0]
    prompt_len = tok.shape[1] + (cfg.vision_patches
                                 if cfg.family == "vlm" else 0)
    pre, dec = make_sampling_serve_steps(cfg, b, max_seq,
                                         temperature=temperature)
    key = jax.random.key(seed)
    key, sub = jax.random.split(key)
    last_pos = jnp.full((b,), prompt_len - 1, jnp.int32)
    nxt, cache = pre(params, prompt_batch, last_pos, sub)
    out = []
    pos = prompt_len
    extras = {k: v for k, v in prompt_batch.items()
              if k in ("cond",)}
    for _ in range(steps):
        if cfg.family == "audio":
            tokens = nxt.reshape(b, 1, cfg.n_codebooks)
        else:
            tokens = nxt.reshape(b, 1)
        out.append(np.asarray(tokens))       # 4 bytes/slot, not a vocab row
        key, sub = jax.random.split(key)
        nxt, cache = dec(params, cache,
                         {"tokens": tokens, **extras}, jnp.int32(pos), sub)
        pos += 1
    return np.concatenate(out, axis=1)
