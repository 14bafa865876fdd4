"""HLO walker validation: known-FLOP programs, trip-count handling,
collective counting, and agreement with analytic model FLOPs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import check
from repro.roofline import hlo_walk as W
from repro.roofline import analysis as RA


def _walk(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return W.walk(hlo)


def test_matmul_flops_exact():
    M, K, N = 128, 256, 64
    a = jnp.zeros((M, K), jnp.float32)
    b = jnp.zeros((K, N), jnp.float32)
    c = _walk(lambda a, b: a @ b, a, b)
    assert c.flops == pytest.approx(2 * M * K * N, rel=1e-6)


def test_scan_multiplies_by_trip_count():
    """The reason the walker exists: cost_analysis counts a scan body
    once; an L-step scan of a matmul must count L x."""
    L, M = 7, 64
    a = jnp.zeros((M, M), jnp.float32)

    def f(a):
        def body(x, _):
            return x @ x, None
        out, _ = jax.lax.scan(body, a, None, length=L)
        return out

    c = _walk(f, a)
    assert c.flops == pytest.approx(L * 2 * M * M * M, rel=1e-6)


def test_nested_scan_trip_products():
    M, L1, L2 = 32, 3, 5
    a = jnp.zeros((M, M), jnp.float32)

    def f(a):
        def outer(x, _):
            def inner(y, _):
                return y @ y, None
            y, _ = jax.lax.scan(inner, x, None, length=L2)
            return y, None
        out, _ = jax.lax.scan(outer, a, None, length=L1)
        return out

    c = _walk(f, a)
    assert c.flops == pytest.approx(L1 * L2 * 2 * M ** 3, rel=1e-6)


def test_bytes_dominated_by_real_traffic():
    """A big matmul's bytes must be ~(A + B + C) and not polluted by
    elementwise wrappers (the TPU-fusion byte model)."""
    M = 512
    a = jnp.zeros((M, M), jnp.float32)
    c = _walk(lambda a, b: jnp.tanh(a @ b) * 2.0 + 1.0, a, a)
    expect = 3 * M * M * 4
    assert c.bytes <= 4 * expect      # some slack for copies/converts
    assert c.bytes >= expect


def test_collective_bytes_counted():
    out = check("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.roofline import hlo_walk as W
mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.zeros((8, 128, 128), jnp.float32)
with jax.sharding.set_mesh(mesh):
    f = jax.jit(lambda v: v.sum(0),
                in_shardings=NamedSharding(mesh, P("x")),
                out_shardings=NamedSharding(mesh, P()))
    hlo = f.lower(x).compile().as_text()
c = W.walk(hlo)
total = c.coll.get("total", 0)
# sum over sharded axis then replicate: at least one all-reduce of a
# (128,128) f32 = 65536 bytes
assert total >= 128 * 128 * 4, c.coll
print("OK", c.coll)
""")
    assert "OK" in out


def test_walker_matches_analytic_dense_flops():
    """Training-step FLOPs for a small dense LM must land within 40% of
    the analytic 6·N·D + attention estimate (remat adds recompute; the
    walker must not be off by integer factors)."""
    from repro import configs
    from repro.configs.base import smoke_variant
    from repro.models import registry
    from repro.train import train_loop as TL, optimizer as OPT
    import dataclasses
    cfg = dataclasses.replace(smoke_variant(configs.get("minitron-4b")),
                              remat="none", vocab_size=512)
    params = registry.init(cfg, 0)
    b, s = 2, 64
    batch = registry.make_batch(cfg, "train", b, s)
    fn, _, _ = TL.make_train_step(cfg, TL.TrainCfg(compress_grads=False),
                                  mesh=None, donate=False)
    hlo = fn.lower(params, OPT.init(params), batch).compile().as_text()
    c = W.walk(hlo)
    # analytic: 6*N*D for matmul params (exclude embed gather; include
    # unembed) + attention 12*b*s^2*h*hd (fwd+bwd, full blocks)
    d, ff, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    N_mat = L * (d * hq * hd + 2 * d * hkv * hd + hq * hd * d
                 + 3 * d * ff) + d * cfg.padded_vocab
    D = b * s
    analytic = 6 * N_mat * D + 12 * b * s * s * hq * hd * L
    assert 0.5 * analytic <= c.flops <= 1.8 * analytic, \
        (c.flops, analytic)


def test_roofline_terms_and_bottleneck():
    r = RA.Roofline(
        arch="x", shape="train_4k", mesh="16x16",
        flops_per_device=197e12, bytes_per_device=819e9,
        collective_bytes_per_device=100e9,
        collectives={"total": int(100e9)},
        model_flops_global=197e12 * 256, n_active_params=1,
        device_kind="TPU v5 lite")
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.t_collective == pytest.approx(2.0)
    assert r.bottleneck == "collective"
    assert r.mfu_bound(256) == pytest.approx(0.5)


def test_unknown_device_kind_has_no_peaks():
    r = RA.Roofline(
        arch="x", shape="train_4k", mesh="1", flops_per_device=1.0,
        bytes_per_device=1.0, collective_bytes_per_device=0.0,
        collectives={"total": 0}, model_flops_global=1.0,
        n_active_params=1, device_kind="cpu")
    with pytest.raises(KeyError, match="no published peaks"):
        r.t_compute


def test_collective_parse_kinds():
    hlo = """
HloModule m, is_scheduled=true

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %ar = f32[256]{0} all-reduce(%p0), to_apply=%add
  %ag = f32[512]{0} all-gather(%ar), dimensions={0}
  %cp = f32[256]{0} collective-permute(%ar), source_target_pairs={{0,1}}
  ROOT %out = f32[256]{0} copy(%cp)
}
"""
    c = W.walk(hlo)
    assert c.coll["all-reduce"] == 1024
    assert c.coll["all-gather"] == 2048     # result bytes
    assert c.coll["collective-permute"] == 1024
