"""Logical-axis sharding rules (the F1 "configuration over source edits"
principle applied to distribution).

Model code names *logical* axes ("batch", "heads", "ff", ...); the
launcher installs a rule table mapping logical axes to mesh axes.  The
same model definition then runs on a single CPU device (no mesh — all
constraints become no-ops), a 16×16 pod, or a 2×16×16 multi-pod, without
touching model source — hlslib's portability story for distribution.

Serving integration (see docs/serving.md "Mesh-sharded serving"): the
paged decode/prefill/verify steps in ``serve.serve_loop`` run their
bodies under ``jax.shard_map`` on the mesh named by
``cfg.mesh_shape``/``cfg.tp_axis``.  Inside a shard_map body every mesh
axis is ``Manual``, so every ``constrain()`` in the model code is a
no-op there; instead the body enters ``manual_axis(cfg.tp_axis)`` and
the model inserts explicit collectives through ``psum_parts`` /
``gather_parts`` at the attention / FF output projections (partial-sum
reduce) and at the MLA latent read + logits (tile gather).  Which tensor
dims shard is still driven by this module's rule table:
``serve.serve_loop`` computes parameter and KV-pool PartitionSpecs from
the same ``Decl`` logical axes via ``params.param_specs`` under
``use_rules`` overrides, and ``validate_shardable`` rejects configs
whose head/latent/ff extents don't divide the model axis before
anything reaches jit.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Union[None, str, Tuple[str, ...]]]

# batch over all data-parallel axes; model-parallel dims over "model".
DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "seq": None,           # sequence replicated by default ...
    "seq_sharded": ("data",),  # ... except SP mode (long-context)
    "embed": None,
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "experts": ("model",),
    "expert_cap": None,
    "moe_groups": ("pod", "data"),
    "kv_seq": ("model",),
    "d_inner": ("model",),
    "ssm_heads": ("model",),
    "state": None,
    "layers": None,
    "stack": None,
    "conv": None,
    "lora": None,
    "cond": None,
    "patches": None,
    "codebooks": None,
}

_rules_var: contextvars.ContextVar[Rules] = contextvars.ContextVar(
    "axis_rules", default=DEFAULT_RULES)


def axis_rules() -> Rules:
    return _rules_var.get()


@contextlib.contextmanager
def use_rules(overrides: Optional[Rules] = None, **kw):
    rules = dict(_rules_var.get())
    rules.update(overrides or {})
    rules.update(kw)
    token = _rules_var.set(rules)
    try:
        yield rules
    finally:
        _rules_var.reset(token)


def current_mesh() -> Optional[Mesh]:
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    return m


def spec_for(axes: Sequence[Optional[str]],
             mesh: Optional[Mesh] = None,
             dims: Optional[Sequence[int]] = None) -> P:
    """Logical axes -> PartitionSpec, filtered to axes the mesh has.

    With ``dims`` (the tensor shape), a mesh axis that does not divide
    its dimension is skipped *without being consumed*, so a later
    logical axis can claim it (e.g. 40 kv heads can't take 'model', so
    the kv_seq dim gets it instead)."""
    mesh = mesh if mesh is not None else current_mesh()
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    rules = axis_rules()
    parts = []
    used = set()
    for i, ax in enumerate(axes):
        if ax is None:
            parts.append(None)
            continue
        target = rules.get(ax, None)
        if target is None:
            parts.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        avail = []
        for t in target:
            if t not in mesh_axes or t in used:
                continue
            if dims is not None and mesh is not None:
                prod = mesh.shape[t]
                for a in avail:
                    prod *= mesh.shape[a]
                if dims[i] % prod != 0:
                    continue
            avail.append(t)
        used.update(avail)
        avail = tuple(avail)
        parts.append(avail if len(avail) > 1 else (avail[0] if avail else None))
    return P(*parts)


def constrain(x, *axes: Optional[str]):
    """with_sharding_constraint by logical axes; no-op without a mesh and
    inside a shard_map body (its mesh axes are ``Manual``, which a
    sharding constraint may not name)."""
    mesh = current_mesh()
    if mesh is None or mesh.manual_axes:
        return x
    return jax.lax.with_sharding_constraint(x, spec_for(axes, mesh))


# -- manual (shard_map) collectives ------------------------------------------------
#
# shard_map bodies trace with per-shard shapes on a mesh whose axes are
# all Manual (`constrain` is a no-op there), so `constrain` can't express
# the cross-shard reductions tensor parallelism needs.  The serving
# steps instead enter `manual_axis(tp_axis)` around the model call and
# the model inserts explicit collectives via the helpers below — all of
# which degrade to identity when no manual axis is active, so the same
# model code keeps running unchanged on one device.

_manual_axis_var: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("manual_axis", default=None)


@contextlib.contextmanager
def manual_axis(name: Optional[str]):
    """Mark a mesh axis as manually sharded for the enclosed trace (the
    serving shard_map bodies).  ``psum_parts``/``gather_parts`` become
    real collectives over it; ``None`` (or no context) keeps them
    identity."""
    token = _manual_axis_var.set(name)
    try:
        yield name
    finally:
        _manual_axis_var.reset(token)


def active_manual_axis() -> Optional[str]:
    return _manual_axis_var.get()


def psum_parts(x):
    """Sum per-shard partial results over the manual axis (the reduce at
    a row-sharded output projection); identity when inactive."""
    ax = _manual_axis_var.get()
    if ax is None:
        return x
    return jax.lax.psum(x, ax)


def gather_parts(x, axis: int = -1):
    """Concatenate per-shard tiles along ``axis`` in axis-index order
    (the all_gather at a column-sharded boundary — MLA latent reads,
    logits); identity when inactive.  Bit-exact: no arithmetic, just a
    deterministic concat."""
    ax = _manual_axis_var.get()
    if ax is None:
        return x
    return jax.lax.all_gather(x, ax, axis=axis % x.ndim, tiled=True)


def part_index() -> int:
    """This shard's index on the manual axis (0 when inactive) — the
    offset for slicing a full-width tensor down to the local tile."""
    ax = _manual_axis_var.get()
    if ax is None:
        return 0
    return jax.lax.axis_index(ax)


def part_count() -> int:
    """Shard count of the manual axis (1 when inactive)."""
    ax = _manual_axis_var.get()
    if ax is None:
        return 1
    return jax.lax.axis_size(ax)


def validate_shardable(cfg, tp: int) -> None:
    """Reject configs the serving tensor-parallel path cannot shard,
    BEFORE anything reaches jit — each error names the offending model
    dim and the knob that fixes it.  ``tp`` is the model-axis extent
    (``cfg.mesh_shape[-1]``)."""
    if tp <= 1:
        return

    def _req(value: int, what: str, knob: str):
        if value % tp != 0:
            raise ValueError(
                f"{cfg.name}: {what} = {value} does not divide the "
                f"model axis ({knob} must be a multiple of "
                f"mesh_shape[-1] = {tp}); pick a smaller model axis or "
                f"adjust {knob}")

    _req(cfg.n_heads, "n_heads (query heads)", "n_heads")
    if cfg.mla:
        _req(cfg.kv_lora_rank, "kv_lora_rank (MLA latent dim)",
             "kv_lora_rank")
    else:
        # No MQA replication fallback: the KV pools shard over kv_heads.
        _req(cfg.n_kv_heads, "n_kv_heads (KV head groups)", "n_kv_heads")
    _req(cfg.d_ff, "d_ff (MLP hidden dim)", "d_ff")
    if cfg.moe_d_ff:
        _req(cfg.moe_d_ff, "moe_d_ff (expert hidden dim)", "moe_d_ff")
    if cfg.fuse_qkv:
        raise ValueError(
            f"{cfg.name}: fuse_qkv is incompatible with tensor-parallel "
            f"serving (sharding the concatenated qkv output dim would "
            f"split across the q|k|v boundary); set fuse_qkv=False or "
            f"mesh_shape[-1] = 1")


def zero_shard_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh,
                    axis: str = "data") -> P:
    """ZeRO-1: additionally shard the first large, still-replicated dim of
    an optimizer-state tensor over the data axis (if divisible)."""
    if axis not in mesh.axis_names:
        return spec
    n = mesh.shape[axis]
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, d) in enumerate(zip(parts, shape)):
        if p is None and d % n == 0 and d >= n:
            parts[i] = axis
            return P(*parts)
    return spec
