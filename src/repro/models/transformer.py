"""Decoder-LM assembly for every assigned family.

One generic machine: a *block builder* per family returns
``(decls, apply, cache_decl, n_groups)``; the forward pass scans blocks
with stacked params (HLO stays O(one group) — granite's 88 layers
compile as fast as 2).  Modes:

* ``train``   — full-sequence causal forward, logits everywhere.
* ``prefill`` — same compute, but every attention block also emits its
  KV (ring-rolled for sliding-window layers) and SSM blocks their final
  states; returns (last-position logits, cache).
* ``decode``  — one token in, cache updated functionally.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..distributed.sharding import constrain, gather_parts
from . import layers as L
from . import ssm as S
from .params import Decl, stack_decls as P_stack_decls

F32 = jnp.float32
# the serving step programs' modes: their MoE layers hand each token's
# experts out beside the cache (MoE layers drop nothing in any mode but
# training).
SERVING_MODES = ("decode", "chunk", "verify")


def _tree_idx(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


_stack_decls = P_stack_decls


# --- mode-aware sub-blocks (add prefill cache emission) -----------------------------


def _attn_block(cfg, p, x, *, window, theta, cache, pos, mode,
                cache_len: Optional[int] = None,
                last_pos: Optional[jnp.ndarray] = None,
                block_tab: Optional[jnp.ndarray] = None,
                ring: bool = False,
                cache_offset: Optional[jnp.ndarray] = None,
                layer: Tuple[Any, ...] = ()):
    if mode in ("decode", "chunk", "verify"):
        if block_tab is not None:
            return L.attention_apply_paged(
                cfg, p, x, window=window, theta=theta, pages=cache,
                block_tab=block_tab, pos=pos, ring=ring,
                last_idx=last_pos if mode == "chunk" else None,
                cache_offset=cache_offset if mode == "chunk" else None,
                verify=mode == "verify", layer=layer)
        if mode in ("chunk", "verify"):
            raise NotImplementedError(f"{mode} mode requires a paged cache")
        return L.attention_apply(cfg, p, x, window=window, theta=theta,
                                 cache=cache, pos=pos)
    y, _ = L.attention_apply(cfg, p, x, window=window, theta=theta)
    if mode == "train":
        return y, None
    # prefill: recompute kv (cheap vs attention itself) to emit the cache.
    b, s, _ = x.shape
    h = L.rmsnorm(x, p["norm"])
    _, k, v = L._qkv(cfg, p, h)
    k = L.rope(k, jnp.arange(s), theta).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)                     # (b, hkv, s, hd)
    Sc = cache_len or s
    if window is not None and Sc == window:
        # Mask-aware ring emission: slot j holds the key of the LAST true
        # position p <= last_pos with p % w == j.  For right-padded
        # (bucketed) prompts the padding therefore never lands in a live
        # ring slot, so any bucket length works — including buckets
        # larger than the window.  With last_pos == s-1 (no padding) this
        # reduces exactly to the old roll-by-(s % w) layout.  Slots with
        # no true position yet (short prompts) hold garbage that decode
        # masks via its warm-up valid mask.
        last = (last_pos.astype(jnp.int32) if last_pos is not None
                else jnp.full((b,), s - 1, jnp.int32))          # (b,)
        j = jnp.arange(window)
        pj = last[:, None] - ((last[:, None] - j[None, :]) % window)
        idx = jnp.clip(pj, 0, s - 1)                            # (b, w)
        k = jnp.take_along_axis(k, idx[:, None, :, None], axis=2)
        v = jnp.take_along_axis(v, idx[:, None, :, None], axis=2)
    elif Sc > s:
        pad = ((0, 0), (0, 0), (0, Sc - s), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = L._kv_quantize(k)
        vq, vs = L._kv_quantize(v)
        return y, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return y, {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}


def _mla_block(cfg, p, x, *, cache, pos, mode, cache_len=None,
               block_tab=None, last_pos=None, cache_offset=None, layer=()):
    if block_tab is not None and mode in ("decode", "chunk", "verify"):
        return L.mla_apply_paged(
            cfg, p, x, pages=cache, block_tab=block_tab, pos=pos,
            last_idx=last_pos if mode == "chunk" else None,
            cache_offset=cache_offset if mode == "chunk" else None,
            verify=mode == "verify", layer=layer)
    if mode in ("chunk", "verify"):
        raise NotImplementedError(f"{mode} mode requires a paged cache")
    if mode == "decode":
        return L.mla_apply(cfg, p, x, cache=cache, pos=pos)
    y, _ = L.mla_apply(cfg, p, x)
    if mode == "train":
        return y, None
    b, s, _ = x.shape
    h = L.rmsnorm(x, p["norm"])
    dkv = h @ p["w_dkv"]
    lora = cfg.kv_lora_rank
    c_kv = L.rmsnorm(dkv[..., :lora], p["kv_norm"])
    k_rope = L.mla_rope(cfg, dkv[..., lora:], jnp.arange(s))
    Sc = cache_len or s
    if Sc > s:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, Sc - s), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, Sc - s), (0, 0)))
    return y, {"c_kv": c_kv.astype(jnp.bfloat16),
               "k_rope": k_rope.astype(jnp.bfloat16)}


def _mamba_block(cfg, p, x, *, cache, pos, mode):
    if mode == "decode":
        return S.mamba2_apply(cfg, p, x, cache=cache, pos=pos)
    if mode == "train":
        y, _ = S.mamba2_apply(cfg, p, x)
        return y, None
    # prefill: recompute the scan keeping final states.
    from ..kernels import ref as kref
    b, s, _ = x.shape
    din, ds, hd, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_headdim, cfg.ssm_heads
    xn = L.rmsnorm(x, p["norm"])
    zxbcdt = xn @ p["w_in"]
    z, xbc_raw, dt_raw = S._split_in(cfg, zxbcdt)
    conv_state = xbc_raw[:, -(cfg.ssm_conv - 1):].astype(F32)
    xbc = S._conv_train(xbc_raw, p["conv_w"], p["conv_b"])
    x_ssm = xbc[..., :din].reshape(b, s, h, hd).astype(F32)
    B = xbc[..., din:din + ds].astype(F32)
    C = xbc[..., din + ds:].astype(F32)
    dt = jax.nn.softplus(dt_raw.astype(F32) + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))
    y, ssd_state = jax.vmap(
        lambda xx, dd, bb, cc: kref.ssd_chunked_ref(
            xx, dd, A, bb, cc, chunk=cfg.ssm_chunk),
        in_axes=(0, 0, 0, 0))(x_ssm, dt, B, C)
    y = y + p["D"].astype(F32)[None, None, :, None] * x_ssm
    y = y.reshape(b, s, din).astype(x.dtype)
    y = L.rmsnorm(y * jax.nn.silu(z.astype(F32)).astype(x.dtype),
                  p["gate_norm"])
    out = x + constrain(y @ p["w_out"], "batch", None, "embed")
    return out, {"conv": conv_state, "ssd": ssd_state}


# --- family block builders ------------------------------------------------------------


def dense_blocks(cfg):
    Ln = cfg.n_layers
    decls = {"attn": L.attention_decls(cfg, (Ln,)),
             "mlp": L.mlp_decls(cfg, (Ln,))}

    def apply(cfg, p, x, cache, pos, mode, cache_len=None, last_pos=None,
              block_tab=None, cache_offset=None, layer=()):
        w = cfg.sliding_window
        cl = min(cache_len, w) if (w and cache_len) else cache_len
        x, nc = _attn_block(cfg, p["attn"], x, window=w,
                            theta=cfg.rope_theta, cache=cache, pos=pos,
                            mode=mode, cache_len=cl, last_pos=last_pos,
                            block_tab=block_tab, cache_offset=cache_offset,
                            layer=layer)
        x = L.mlp_apply(cfg, p["mlp"], x)
        return x, nc

    def cache_decl(batch, max_seq):
        base = L.attention_cache_decl(cfg, batch, max_seq, cfg.sliding_window)
        return _stack_decls(base, Ln)

    return decls, apply, cache_decl, Ln


def gemma3_blocks(cfg):
    G, per = cfg.group_layout          # (8 groups, 6 layers: 5 local + 1 global)
    n_local = cfg.local_global_pattern
    decls = {"attn": L.attention_decls(cfg, (G, per)),
             "mlp": L.mlp_decls(cfg, (G, per))}

    def layer_kind(i):
        if i < n_local:
            return cfg.sliding_window, cfg.rope_theta
        return None, cfg.rope_theta_global

    def apply(cfg, p, x, cache, pos, mode, cache_len=None, last_pos=None,
              block_tab=None, cache_offset=None, layer=()):
        # Paged serving: ``block_tab`` is the {"local", "global"} table
        # dict and ``cache`` the per-group page pools, which a layer
        # writes and reads at ``layer`` + its index within its kind.
        # Local (sliding-window) layers run the ring-of-pages layout —
        # their page count stays window-bounded — while global layers use
        # the flat growing layout.
        paged = block_tab is not None and mode in ("decode", "chunk",
                                                   "verify")
        if paged:
            cache = dict(cache)
        local_caches, global_caches = [], []
        for i in range(per):
            pi = _tree_idx(p, i)
            window, theta = layer_kind(i)
            if paged:
                kind, j = (("local", i) if i < n_local
                           else ("global", i - n_local))
                x, cache[kind] = _attn_block(
                    cfg, pi["attn"], x, window=window, theta=theta,
                    cache=cache[kind], pos=pos, mode=mode, last_pos=last_pos,
                    block_tab=block_tab[kind], ring=kind == "local",
                    cache_offset=cache_offset, layer=layer + (j,))
                x = L.mlp_apply(cfg, pi["mlp"], x)
                continue
            if cache is not None and mode == "decode":
                ci = (_tree_idx(cache["local"], i) if i < n_local
                      else _tree_idx(cache["global"], i - n_local))
            else:
                ci = None
            cl = None
            if cache_len is not None:
                cl = min(cache_len, window) if window else cache_len
            x, nc = _attn_block(cfg, pi["attn"], x, window=window,
                                theta=theta, cache=ci, pos=pos, mode=mode,
                                cache_len=cl, last_pos=last_pos)
            x = L.mlp_apply(cfg, pi["mlp"], x)
            if nc is not None:
                (local_caches if i < n_local else global_caches).append(nc)
        new_cache = cache if paged else None
        if local_caches:
            new_cache = {
                "local": jax.tree.map(lambda *xs: jnp.stack(xs),
                                      *local_caches),
                "global": jax.tree.map(lambda *xs: jnp.stack(xs),
                                       *global_caches),
            }
        return x, new_cache

    def cache_decl(batch, max_seq):
        w = cfg.sliding_window
        loc = L.attention_cache_decl(cfg, batch, min(max_seq, w), w)
        glo = L.attention_cache_decl(cfg, batch, max_seq, None)
        per_group = {"local": _stack_decls(loc, n_local),
                     "global": _stack_decls(glo, per - n_local)}
        return _stack_decls(per_group, G)

    return decls, apply, cache_decl, G


def _with_routes(nc, idx, mode):
    """A MoE layer's scan output: in the serving modes its cache and
    each token's experts (split again by ``_split_routes``)."""
    return (nc, idx) if mode in SERVING_MODES else nc


def _split_routes(ys, mode):
    return ys if mode in SERVING_MODES else (ys, None)


def moe_blocks(cfg):
    """phi3.5-style: every layer attention + MoE."""
    Ln = cfg.n_layers
    decls = {"attn": L.attention_decls(cfg, (Ln,)),
             "moe": L.moe_decls(cfg, (Ln,))}

    def apply(cfg, p, x, cache, pos, mode, cache_len=None, last_pos=None,
              block_tab=None, cache_offset=None, layer=()):
        w = cfg.sliding_window
        cl = min(cache_len, w) if (w and cache_len) else cache_len
        x, nc = _attn_block(cfg, p["attn"], x, window=w,
                            theta=cfg.rope_theta, cache=cache, pos=pos,
                            mode=mode, cache_len=cl, last_pos=last_pos,
                            block_tab=block_tab, cache_offset=cache_offset,
                            layer=layer)
        x, idx = L.moe_apply(cfg, p["moe"], x,
                             dropless=mode != "train")
        return x, _with_routes(nc, idx, mode)

    def cache_decl(batch, max_seq):
        return _stack_decls(
            L.attention_cache_decl(cfg, batch, max_seq, cfg.sliding_window),
            Ln)

    return decls, apply, cache_decl, Ln


def deepseek_blocks(cfg):
    """MLA attention; first layer(s) dense MLP, the rest MoE + shared."""
    Ld, Ln = cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers
    decls = {
        "first": {"attn": L.mla_decls(cfg, (Ld,)),
                  "mlp": L.mlp_decls(cfg, (Ld,), d_ff=cfg.d_ff)},
        "rest": {"attn": L.mla_decls(cfg, (Ln,)),
                 "moe": L.moe_decls(cfg, (Ln,))},
    }

    def apply_first(cfg, p, x, cache, pos, mode, cache_len=None,
                    last_pos=None, block_tab=None, cache_offset=None,
                    layer=()):
        x, nc = _mla_block(cfg, p["attn"], x, cache=cache, pos=pos,
                           mode=mode, cache_len=cache_len,
                           block_tab=block_tab, last_pos=last_pos,
                           cache_offset=cache_offset, layer=layer)
        x = L.mlp_apply(cfg, p["mlp"], x)
        return x, nc

    def apply_rest(cfg, p, x, cache, pos, mode, cache_len=None,
                   last_pos=None, block_tab=None, cache_offset=None,
                   layer=()):
        x, nc = _mla_block(cfg, p["attn"], x, cache=cache, pos=pos,
                           mode=mode, cache_len=cache_len,
                           block_tab=block_tab, last_pos=last_pos,
                           cache_offset=cache_offset, layer=layer)
        x, idx = L.moe_apply(cfg, p["moe"], x,
                             dropless=mode != "train")
        return x, _with_routes(nc, idx, mode)

    def cache_decl(batch, max_seq):
        base = L.mla_cache_decl(cfg, batch, max_seq)
        return {"first": _stack_decls(base, Ld),
                "rest": _stack_decls(base, Ln)}

    return decls, (apply_first, apply_rest), cache_decl, (Ld, Ln)


def mamba2_blocks(cfg):
    Ln = cfg.n_layers
    decls = {"ssm": S.mamba2_decls(cfg, (Ln,))}

    def apply(cfg, p, x, cache, pos, mode, cache_len=None, last_pos=None,
              block_tab=None, cache_offset=None):
        return _mamba_block(cfg, p["ssm"], x, cache=cache, pos=pos, mode=mode)

    def cache_decl(batch, max_seq):
        return _stack_decls(S.mamba2_cache_decl(cfg, batch), Ln)

    return decls, apply, cache_decl, Ln


def zamba2_blocks(cfg):
    """Mamba2 backbone + ONE shared attention+MLP block (weights reused —
    the Zamba trick; in hlslib terms a single PE module instantiated once
    and streamed through six times).  Layout: G groups of
    ``shared_attn_every`` mamba layers each followed by the shared block,
    plus a mamba-only tail.  Each shared-block *application site* keeps
    its own KV cache (the weights are shared; the activations are not).
    """
    k = cfg.shared_attn_every
    G = cfg.n_layers // k
    tail = cfg.n_layers - G * k
    decls = {"ssm_groups": S.mamba2_decls(cfg, (G, k)),
             "shared_attn": L.attention_decls(cfg, ()),
             "shared_mlp": L.mlp_decls(cfg, ())}
    if tail:
        decls["ssm_tail"] = S.mamba2_decls(cfg, (tail,))

    def apply_group(cfg, p_g, shared, x, cache, pos, mode, cache_len=None,
                    last_pos=None, block_tab=None, cache_offset=None):
        mamba_caches = []
        for i in range(k):
            ci = (_tree_idx(cache["ssm"], i)
                  if cache is not None and mode == "decode" else None)
            x, nc = _mamba_block(cfg, _tree_idx(p_g, i), x, cache=ci,
                                 pos=pos, mode=mode)
            if nc is not None:
                mamba_caches.append(nc)
        attn_cache = (cache["attn"] if cache is not None and mode == "decode"
                      else None)
        x, attn_nc = _attn_block(cfg, shared["attn"], x, window=None,
                                 theta=cfg.rope_theta, cache=attn_cache,
                                 pos=pos, mode=mode, cache_len=cache_len,
                                 last_pos=last_pos)
        x = L.mlp_apply(cfg, shared["mlp"], x)
        new_cache = None
        if mamba_caches:
            new_cache = {"ssm": jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *mamba_caches),
                         "attn": attn_nc}
        return x, new_cache

    def cache_decl(batch, max_seq):
        grp = {"ssm": _stack_decls(S.mamba2_cache_decl(cfg, batch), k),
               "attn": L.attention_cache_decl(cfg, batch, max_seq, None)}
        out = {"groups": _stack_decls(grp, G)}
        if tail:
            out["tail"] = _stack_decls(S.mamba2_cache_decl(cfg, batch), tail)
        return out

    return decls, apply_group, cache_decl, (G, k, tail)


def musicgen_blocks(cfg):
    """Self-attention + cross-attention (to the conditioning stub) + MLP."""
    Ln = cfg.n_layers
    d, hq, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    cross = {
        "norm": Decl((Ln, d), ("stack", "embed"), init="zeros"),
        "wq": Decl((Ln, d, hq * hd), ("stack", "embed", "heads")),
        "wk": Decl((Ln, d, hq * hd), ("stack", "embed", "heads")),
        "wv": Decl((Ln, d, hq * hd), ("stack", "embed", "heads")),
        "wo": Decl((Ln, hq * hd, d), ("stack", "heads", "embed")),
    }
    decls = {"attn": L.attention_decls(cfg, (Ln,)),
             "cross": cross,
             "mlp": L.mlp_decls(cfg, (Ln,))}

    def cross_apply(p, x, cond):
        b, s, _ = x.shape
        lc = cond.shape[1]
        h = L.rmsnorm(x, p["norm"])
        q = (h @ p["wq"]).reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
        kk = (cond @ p["wk"]).reshape(b, lc, hq, hd).transpose(0, 2, 1, 3)
        vv = (cond @ p["wv"]).reshape(b, lc, hq, hd).transpose(0, 2, 1, 3)
        o = L.attention_decode(q, kk, vv, jnp.ones((lc,), bool))
        y = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ p["wo"]
        return x + constrain(y, "batch", None, "embed")

    def apply(cfg, p, x, cond, cache, pos, mode, cache_len=None,
              last_pos=None, block_tab=None, cache_offset=None):
        x, nc = _attn_block(cfg, p["attn"], x, window=None,
                            theta=cfg.rope_theta, cache=cache, pos=pos,
                            mode=mode, cache_len=cache_len,
                            last_pos=last_pos)
        x = cross_apply(p["cross"], x, cond)
        x = L.mlp_apply(cfg, p["mlp"], x)
        return x, nc

    def cache_decl(batch, max_seq):
        return _stack_decls(L.attention_cache_decl(cfg, batch, max_seq), Ln)

    return decls, apply, cache_decl, Ln


# --- top-level model ------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _family(cfg):
    builders = {"dense": dense_blocks, "moe": moe_blocks,
                "ssm": mamba2_blocks, "hybrid": zamba2_blocks,
                "vlm": dense_blocks, "audio": musicgen_blocks}
    if cfg.local_global_pattern:
        return gemma3_blocks(cfg)
    if cfg.family == "moe" and cfg.mla:
        return deepseek_blocks(cfg)
    return builders[cfg.family](cfg)


def model_decls(cfg) -> Dict[str, Any]:
    d, Vp = cfg.d_model, cfg.padded_vocab
    decls: Dict[str, Any] = {
        "final_norm": Decl((d,), ("embed",), init="zeros"),
    }
    if cfg.family == "audio":
        decls["embed"] = Decl((cfg.n_codebooks, Vp, d),
                              ("codebooks", "vocab", "embed"),
                              std=cfg.embed_std)
        decls["unembed"] = Decl((cfg.n_codebooks, d, Vp),
                                ("codebooks", "embed", "vocab"))
    else:
        decls["embed"] = Decl((Vp, d), ("vocab", "embed"), std=cfg.embed_std)
        decls["unembed"] = Decl((d, Vp), ("embed", "vocab"))
    if cfg.family == "vlm":
        decls["vis_proj"] = Decl((cfg.vision_dim, d), (None, "embed"))
    decls["blocks"] = _family(cfg)[0]
    return decls


def cache_decls(cfg, batch: int, max_seq: int):
    builder = _family(cfg)[2]
    return builder(batch, max_seq)


def paged_supported(cfg) -> bool:
    """Families with a registered ``CacheLayout`` — every attention
    cache pages now (dense/moe GQA, gemma3 local/global, MLA latent,
    int8 KV with scale pages).  Recurrent state (ssm/hybrid) is
    O(1)/slot and stays slot-dense: there is nothing to page."""
    from .cache_layouts import get_layout
    return get_layout(cfg, cfg.kv_page_size or 16) is not None


def paged_cache_decls(cfg, n_pages, page_size: int):
    """Per-group, per-layer shared page pools, stacked for
    scan-over-layers — e.g. (n_layers, n_pages, hkv, page_size, head_dim)
    per k/v leaf for the flat GQA layout.  ``n_pages``: int (same pool
    size for every page group) or {group_name: int}.  The returned tree
    is keyed by page group ("kv", or "local"/"global" for gemma3, or
    "latent" for MLA) — see ``models.cache_layouts``."""
    from .cache_layouts import get_layout
    layout = get_layout(cfg, page_size)
    if layout is None:
        raise NotImplementedError(
            f"paged KV unsupported for {cfg.name} ({cfg.family}); "
            "use dense slot caches")
    if not isinstance(n_pages, dict):
        n_pages = {g.name: int(n_pages) for g in layout.groups}
    return layout.pool_decls(n_pages)


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _embed_input(cfg, params, batch) -> jnp.ndarray:
    dtype = jnp.dtype(cfg.dtype)
    if cfg.family == "audio":
        tok = batch["tokens"]                       # (b, s, K)
        emb = params["embed"]                       # (K, Vp, d)
        x = sum(emb[c][tok[..., c]] for c in range(cfg.n_codebooks))
        return x.astype(dtype)
    tok = batch["tokens"]                           # (b, s)
    x = params["embed"][tok]
    if cfg.local_global_pattern or cfg.family == "vlm":
        x = x * np.float32(np.sqrt(cfg.d_model))    # gemma scaling
    if cfg.family == "vlm" and "patches" in batch:
        patches = batch["patches"].astype(dtype)    # (b, P, vis_dim)
        pre = patches @ params["vis_proj"]
        x = jnp.concatenate([pre, x], axis=1)
    return x.astype(dtype)


def _scan_blocks(cfg, apply, blocks_p, x, cache, pos, mode, cache_len,
                 last_pos=None, block_tab=None, cache_offset=None):
    n = jax.tree.leaves(blocks_p)[0].shape[0]
    if block_tab is not None and mode in SERVING_MODES:
        # The stacked pools ride in the carry, whole, and each layer
        # writes and reads its own pool inside the stack at its index,
        # in place.  Scanning over them would slice each layer's pool
        # out, write it back into a fresh stack and copy the donated
        # stack once a call: traffic in proportion to the pool.
        def paged_body(carry, xs):
            x, pools = carry
            p_i, l = xs
            x, nc = apply(cfg, p_i, x, pools, pos, mode, cache_len=cache_len,
                          last_pos=last_pos, block_tab=block_tab,
                          cache_offset=cache_offset, layer=(l,))
            # a MoE layer hands back its experts beside the pools
            # (``_with_routes``): they stack over the layers.
            pools, routes = nc if isinstance(nc, tuple) else (nc, None)
            return (x, pools), routes

        (x, pools), routes = lax.scan(_remat(cfg, paged_body), (x, cache),
                                      (blocks_p, jnp.arange(n)))
        return x, pools if routes is None else (pools, routes)

    def body(carry, xs):
        x = carry
        p_i, c_i = xs
        x, nc = apply(cfg, p_i, x, c_i, pos, mode, cache_len=cache_len,
                      last_pos=last_pos, block_tab=block_tab,
                      cache_offset=cache_offset)
        return x, nc

    body = _remat(cfg, body)
    caches = cache if (cache is not None
                       and mode in ("decode", "chunk", "verify")) \
        else jnp.zeros((n, 1))
    x, new_cache = lax.scan(body, x, (blocks_p, caches))
    if mode == "train":
        new_cache = None
    return x, new_cache


def forward(cfg, params, batch, mode: str = "train",
            cache: Optional[Any] = None, pos: Optional[jnp.ndarray] = None,
            cache_len: Optional[int] = None,
            last_pos: Optional[jnp.ndarray] = None,
            cache_offset: Optional[jnp.ndarray] = None,
            with_routes: bool = False):
    """train -> logits (b, s, Vp); prefill -> (last logits, cache);
    decode/chunk -> (logits, new cache).

    ``with_routes`` (serving modes): also return each token's experts,
    (n_moe_layers, b, s, top_k) int32, stacked over the MoE layers (None
    for a model without experts).

    ``last_pos`` (prefill/chunk): (b,) int32 per-sequence index of the
    true last token.  Bucketed serving right-pads prompts to a
    power-of-two length and chunked prefill right-pads the final chunk;
    the returned logits are gathered at ``last_pos`` instead of the
    (padded) final position.  Causality guarantees the padding cannot
    influence positions <= last_pos.  In prefill, ``last_pos`` also
    drives the mask-aware ring emission for sliding-window layers.

    Paged serving: pass ``cache={"pages": pools, "block_tab": bt}`` with
    per-layer page pools (leading n_layers axis) and a (b, n_blocks)
    int32 block table; ``pos`` is then a (b,) per-row position vector.
    ``mode="chunk"`` runs a multi-token prefill chunk against the paged
    cache (x at positions pos..pos+s-1), enabling chunked prefill
    interleaved with decode.  Returns the updated pools as the new cache.

    ``mode="verify"`` (speculative decode): like a batched k-token
    decode step — tokens (b, k) at positions pos..pos+k-1 against the
    paged cache, returning FULL (b, k, Vp) logits (no last-position
    gather) so the caller can greedily score every span position in one
    call.  Own-K/V reads are pool-rounded (each position reads exactly
    what sequential decode would have), keeping accepted speculative
    tokens bit-identical to non-speculative greedy decode.

    ``cache_offset`` (chunk mode, prefix cache): (b,) int32 — the cache
    is *read-only below this position*.  A prefix-cache hit starts its
    catch-up prefill at the divergence point with the matched prefix
    already resident in shared pages; suppressing writes below the
    offset keeps those pages bit-stable for every sequence aliasing
    them.  ``None`` (or 0) preserves the plain chunked-prefill behavior.
    """
    dtype = jnp.dtype(cfg.dtype)
    params = jax.tree.map(
        lambda p: p.astype(dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
    x = _embed_input(cfg, params, batch)
    x = constrain(x, "batch", None, "embed")

    rewrap_kv = False
    block_tab = None
    if cache is not None and isinstance(cache, dict) and "block_tab" in cache:
        block_tab = cache["block_tab"]
        cache = cache["pages"]
        # Canonical paged form: pools and tables are dicts keyed by page
        # group (see models.cache_layouts).  Single-"kv"-group layouts
        # (dense/moe GQA, int8) unwrap to the bare tree/array the block
        # builders consume; gemma3 keeps its {"local","global"} dicts and
        # MLA its "latent" group, unwrapped in their branches below.
        if isinstance(block_tab, dict) and set(block_tab) == {"kv"}:
            block_tab = block_tab["kv"]
        if isinstance(cache, dict) and set(cache) == {"kv"}:
            cache = cache["kv"]
            rewrap_kv = True
    if mode in ("chunk", "verify") and block_tab is None:
        raise NotImplementedError(f"{mode} mode requires a paged cache")

    fam = _family(cfg)
    blocks_p = params["blocks"]
    routes = None
    cond = batch.get("cond")
    if cond is not None:
        cond = cond.astype(dtype)

    if cfg.family == "moe" and cfg.mla:
        apply_first, apply_rest = fam[1]
        bt = None
        if block_tab is not None:
            bt = (block_tab["latent"] if isinstance(block_tab, dict)
                  else block_tab)
            pool = cache["latent"] if "latent" in cache else cache
            cf, cr = pool["first"], pool["rest"]
        else:
            cf = cache["first"] if (cache is not None and mode == "decode") \
                else None
            cr = cache["rest"] if (cache is not None and mode == "decode") \
                else None
        x, c_first = _scan_blocks(cfg, apply_first, blocks_p["first"], x,
                                  cf, pos, mode, cache_len,
                                  last_pos=last_pos, block_tab=bt,
                                  cache_offset=cache_offset)
        x, c_rest = _scan_blocks(cfg, apply_rest, blocks_p["rest"], x,
                                 cr, pos, mode, cache_len,
                                 last_pos=last_pos, block_tab=bt,
                                 cache_offset=cache_offset)
        c_rest, routes = _split_routes(c_rest, mode)
        new_cache = None if mode == "train" else {"first": c_first,
                                                  "rest": c_rest}
        if bt is not None:
            new_cache = {"latent": new_cache}
    elif cfg.family == "hybrid":
        apply_group = fam[1]
        G, k, tail = fam[3]
        shared = {"attn": blocks_p["shared_attn"],
                  "mlp": blocks_p["shared_mlp"]}
        groups_p = jax.tree.map(
            lambda a: a, blocks_p["ssm_groups"])     # (G, k, ...)

        def body(carry, xs):
            x = carry
            p_g, c_g = xs
            x, nc = apply_group(cfg, p_g, shared, x, c_g, pos, mode,
                                cache_len=cache_len)
            return x, nc

        body = _remat(cfg, body)
        c_groups = (cache["groups"] if cache is not None and mode == "decode"
                    else jnp.zeros((G, 1)))
        x, groups_cache = lax.scan(body, x, (groups_p, c_groups))
        tail_cache = None
        if tail:
            def tbody(carry, xs):
                x = carry
                p_i, c_i = xs
                x, nc = _mamba_block(cfg, p_i, x, cache=c_i, pos=pos,
                                     mode=mode)
                return x, nc
            tbody = _remat(cfg, tbody)
            c_tail = (cache["tail"] if cache is not None and mode == "decode"
                      else jnp.zeros((tail, 1)))
            x, tail_cache = lax.scan(tbody, x, (blocks_p["ssm_tail"], c_tail))
        new_cache = None
        if mode != "train":
            new_cache = {"groups": groups_cache}
            if tail:
                new_cache["tail"] = tail_cache
    elif cfg.family == "audio":
        apply = fam[1]

        def apply2(cfg, p, x, c, pos, mode, cache_len=None, last_pos=None,
                   block_tab=None, cache_offset=None):
            return apply(cfg, p, x, cond, c, pos, mode, cache_len,
                         last_pos=last_pos, block_tab=block_tab)

        x, new_cache = _scan_blocks(cfg, apply2, blocks_p, x, cache, pos,
                                    mode, cache_len, last_pos=last_pos)
    else:
        apply = fam[1]
        x, new_cache = _scan_blocks(cfg, apply, blocks_p, x, cache, pos,
                                    mode, cache_len, last_pos=last_pos,
                                    block_tab=block_tab,
                                    cache_offset=cache_offset)
        if cfg.family == "moe":
            new_cache, routes = _split_routes(new_cache, mode)

    x = L.rmsnorm(x, params["final_norm"])
    if mode in ("prefill", "chunk"):
        if last_pos is not None:
            idx = last_pos.astype(jnp.int32)[:, None, None]
            x = jnp.take_along_axis(x, idx, axis=1)
        else:
            x = x[:, -1:]
    with jax.named_scope("logits"):
        if cfg.family == "audio":
            logits = jnp.einsum("bsd,kdv->bskv", x, params["unembed"])
        else:
            logits = x @ params["unembed"]
        if logits.shape[-1] != cfg.padded_vocab:
            # shard_map TP: unembed is vocab-column-sharded (a bit-exact
            # per-shard matmul — the contraction dim is unsharded), so the
            # greedy argmax needs the full row back.
            logits = gather_parts(logits, axis=-1)
        logits = constrain(logits, "batch", None, "vocab")
    if mode == "train":
        return logits
    if rewrap_kv:
        new_cache = {"kv": new_cache}    # mirror the paged input structure
    if with_routes:
        return logits, new_cache, routes
    return logits, new_cache
