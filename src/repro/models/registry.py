"""Model zoo: one entry point per assigned architecture.

``input_specs`` follows the assignment contract: modality frontends are
STUBS — the VLM receives precomputed SigLIP patch embeddings, the audio
model precomputed EnCodec codebook tokens + a text-conditioning tensor.
Everything returns ShapeDtypeStructs for the dry-run (no allocation) and
concrete arrays via ``make_batch`` for smoke tests/examples.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, ShapeCfg
from . import params as P
from . import transformer as T


def decls(cfg: ModelConfig):
    return T.model_decls(cfg)


def init(cfg: ModelConfig, seed: int = 0, *, dtype=None, shardings=None):
    """Parameters from ``seed``: float32 masters by default; see
    ``params.init_params`` for ``dtype`` and ``shardings``."""
    return P.init_params(decls(cfg), seed, dtype=dtype, shardings=shardings)


def abstract(cfg: ModelConfig):
    return P.abstract_params(decls(cfg))


def specs(cfg: ModelConfig, mesh=None):
    return P.param_specs(decls(cfg), mesh)


def num_params(cfg: ModelConfig) -> int:
    return P.param_count(decls(cfg))


def num_active_params(cfg: ModelConfig) -> int:
    """Active N for MoE (routed experts count only top_k/E of expert
    params) — the 6·N_active·D roofline convention."""
    if not cfg.n_experts:
        return num_params(cfg)
    d = T.model_decls(cfg)
    total = P.param_count(d)
    moe_keys = ("w_gate", "w_up", "w_down")

    def expert_params(tree):
        n = 0
        for k, v in tree.items():
            if isinstance(v, dict):
                n += expert_params(v)
            elif k in moe_keys and len(v.shape) >= 3 \
                    and v.shape[-3] == cfg.n_experts:
                n += int(np.prod(v.shape))
        return n

    e = expert_params(d)
    active = total - e + int(e * cfg.top_k / cfg.n_experts)
    return active


forward = T.forward
cache_decls = T.cache_decls
paged_cache_decls = T.paged_cache_decls
paged_supported = T.paged_supported


# --- input specs (ShapeDtypeStruct stand-ins; assignment requirement) -----------------


def input_specs(cfg: ModelConfig, shape: ShapeCfg) -> Dict[str, Any]:
    B, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def tok_spec(b, s):
        if cfg.family == "audio":
            return jax.ShapeDtypeStruct((b, s, cfg.n_codebooks), i32)
        return jax.ShapeDtypeStruct((b, s), i32)

    extras: Dict[str, Any] = {}
    if cfg.family == "audio":
        extras["cond"] = jax.ShapeDtypeStruct(
            (B, cfg.cond_len, cfg.d_model), jnp.bfloat16)

    if shape.kind == "train":
        s_text = s - cfg.vision_patches if cfg.family == "vlm" else s
        batch = {"tokens": tok_spec(B, s_text),
                 "labels": tok_spec(B, s_text), **extras}
        if cfg.family == "vlm":
            batch["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_patches, cfg.vision_dim), jnp.bfloat16)
        return batch
    if shape.kind == "prefill":
        s_text = s - cfg.vision_patches if cfg.family == "vlm" else s
        batch = {"tokens": tok_spec(B, s_text), **extras}
        if cfg.family == "vlm":
            batch["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_patches, cfg.vision_dim), jnp.bfloat16)
        return batch
    # decode: one new token against a seq_len cache.
    return {"tokens": tok_spec(B, 1), **extras}


def make_batch(cfg: ModelConfig, shape_kind: str, batch: int, seq: int,
               seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Concrete synthetic batch (smoke tests, examples)."""
    rng = np.random.default_rng(seed)
    V = cfg.vocab_size

    def toks(b, s):
        if cfg.family == "audio":
            return jnp.asarray(
                rng.integers(0, V, (b, s, cfg.n_codebooks)), jnp.int32)
        return jnp.asarray(rng.integers(0, V, (b, s)), jnp.int32)

    out: Dict[str, jnp.ndarray] = {}
    s_text = seq - cfg.vision_patches if cfg.family == "vlm" else seq
    if shape_kind == "decode":
        out["tokens"] = toks(batch, 1)
    else:
        out["tokens"] = toks(batch, s_text)
    if shape_kind == "train":
        out["labels"] = toks(batch, s_text)
    if cfg.family == "vlm" and shape_kind != "decode":
        out["patches"] = jnp.asarray(
            rng.standard_normal((batch, cfg.vision_patches, cfg.vision_dim)),
            jnp.float32).astype(jnp.bfloat16)
    if cfg.family == "audio":
        out["cond"] = jnp.asarray(
            rng.standard_normal((batch, cfg.cond_len, cfg.d_model)),
            jnp.float32).astype(jnp.bfloat16)
    return out
