"""Mesh-sharded serving: tensor-parallel paged KV + decode under
shard_map.

The contract under test is BIT-IDENTITY and MEMORY: on a simulated
host mesh the decoded token streams of a model-parallel batcher must
match the 1-device batcher token for token — across every cache-layout
family (flat GQA, MoE, gemma3 local/global, MLA latent, int8+scales),
through the speculative verify step, a prefix-cache rehit, and a
preempt/resume cycle — while each device holds only its 1/tp slice of
the KV page pools.

Multi-device tests re-exec in a subprocess (XLA locks the host device
count at first init; see tests/_subproc.py).  Launch-time shardability
validation and the shard-local config arithmetic are cheap and run
in-process.
"""

import dataclasses

import pytest

from _subproc import check_mesh
from repro import configs
from repro.configs.base import smoke_variant
from repro.distributed.sharding import validate_shardable
from repro.serve.serve_loop import shard_local_cfg


# --- launch-time shardability validation (in-process) ---------------------------------


def test_validate_shardable_names_dim_and_knob():
    cfg = smoke_variant(configs.get("minitron-4b"))      # 4 q / 4 kv heads
    validate_shardable(cfg, 1)                           # tp=1: anything goes
    validate_shardable(cfg, 2)
    with pytest.raises(ValueError, match=r"n_heads.*mesh_shape\[-1\] = 3"):
        validate_shardable(cfg, 3)
    with pytest.raises(ValueError, match="n_kv_heads"):
        validate_shardable(dataclasses.replace(cfg, n_kv_heads=1), 2)
    with pytest.raises(ValueError, match="d_ff"):
        validate_shardable(dataclasses.replace(cfg, d_ff=255), 2)
    with pytest.raises(ValueError, match="fuse_qkv"):
        validate_shardable(dataclasses.replace(cfg, fuse_qkv=True), 2)


def test_validate_shardable_mla_and_moe_dims():
    mla = smoke_variant(configs.get("deepseek-v2-lite-16b"))
    validate_shardable(mla, 2)
    # MLA pools page over the latent dim — that is the dim that must
    # divide, and the error must say so (not n_kv_heads).
    with pytest.raises(ValueError, match="kv_lora_rank"):
        validate_shardable(dataclasses.replace(mla, kv_lora_rank=33), 2)
    moe = smoke_variant(configs.get("phi3p5-moe-42b"))
    validate_shardable(moe, 4)
    with pytest.raises(ValueError, match="moe_d_ff"):
        validate_shardable(dataclasses.replace(moe, moe_d_ff=66), 4)


def test_shard_local_cfg_divides_ranked_dims_only():
    cfg = dataclasses.replace(smoke_variant(configs.get("minitron-4b")),
                              mesh_shape=(1, 2))
    loc = shard_local_cfg(cfg)
    assert loc.n_heads == cfg.n_heads // 2
    assert loc.n_kv_heads == cfg.n_kv_heads // 2
    assert loc.d_ff == cfg.d_ff // 2
    assert loc.mesh_shape == ()          # the body must not re-shard
    assert loc.vocab_size == cfg.vocab_size  # logits tile gathers instead
    mla = dataclasses.replace(
        smoke_variant(configs.get("deepseek-v2-lite-16b")),
        mesh_shape=(1, 2))
    ml = shard_local_cfg(mla)
    # MLA keeps the FULL latent rank in the forward (w_dkv replicated);
    # only the latent page POOL shards, sliced at the cache write.
    assert ml.kv_lora_rank == mla.kv_lora_rank
    assert ml.n_heads == mla.n_heads // 2


def test_serving_mesh_rejects_undersized_host_and_bad_axis():
    from repro.launch.mesh import serving_mesh
    # the parent test process deliberately has ONE device (conftest):
    # the error must point at the XLA flag that fixes it.
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        serving_mesh((1, 2))
    with pytest.raises(ValueError, match="tp_axis"):
        serving_mesh((1,), tp_axis="ff")
    with pytest.raises(ValueError, match="rank"):
        serving_mesh((1, 1, 1, 1))


def test_launch_cli_rejects_bad_mesh_before_jit(capsys):
    from repro.launch.serve import main
    with pytest.raises(SystemExit):
        main(["--arch", "minitron-4b", "--smoke", "--page-size", "8",
              "--mesh", "3"])
    assert "n_heads" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        main(["--arch", "minitron-4b", "--smoke", "--mesh", "2"])
    assert "--page-size" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        main(["--arch", "minitron-4b", "--smoke", "--page-size", "8",
              "--mesh", "2x"])
    assert "INTxINT" in capsys.readouterr().err


# --- sharded == unsharded token streams (subprocess meshes) ---------------------------

_PRE = r'''
import dataclasses
import numpy as np
import repro
from repro.configs import get, smoke_variant
from repro.models import registry
from repro.serve.batching import ContinuousBatcher, Request, drain


def smoke(arch, **kw):
    return dataclasses.replace(smoke_variant(get(arch)), kv_page_size=8,
                               prefill_chunk=8, **kw)


def serve(cfg, prompts, max_news, n_slots=2, max_seq=48, **bkw):
    params = registry.init(cfg, seed=0)
    b = ContinuousBatcher(cfg, params, n_slots=n_slots, max_seq=max_seq,
                          **bkw)
    reqs = []
    for i, (p, mn) in enumerate(zip(prompts, max_news)):
        r = Request(rid=i, prompt=np.asarray(p, np.int32), max_new=mn)
        b.requests.Push(r)
        reqs.append(r)
    b.requests.close()
    b.run(len(reqs))
    return [drain(r) for r in reqs], b


PROMPTS = [list(range(5, 13)), list(range(40, 52)), [7, 9, 11]]
NEWS = [8, 8, 8]
'''


@pytest.mark.multidevice
@pytest.mark.parametrize("arch,kw,exact_half", [
    ("minitron-4b", {}, True),                          # flat GQA
    ("phi3p5-moe-42b", {}, True),                       # MoE experts
    ("gemma3-12b", {}, True),                           # local ring + global
    ("deepseek-v2-lite-16b", {}, False),                # MLA latent pages
    ("minitron-4b", {"kv_cache_dtype": "int8"}, True),  # int8 + scale pages
])
def test_sharded_identity_across_families(arch, kw, exact_half):
    """Acceptance: 2-way model-parallel token streams == 1-device, and
    per-device KV pool bytes drop 2x (except MLA, whose small shared
    rope pages stay replicated — still a strict drop)."""
    code = _PRE + f'''
cfg = smoke({arch!r}, **{kw!r})
u, _ = serve(cfg, PROMPTS, NEWS)
s, b = serve(dataclasses.replace(cfg, mesh_shape=(1, 2)), PROMPTS, NEWS)
assert s == u, (u, s)
m = b.stats()["mesh"]
assert m["shape"] == (1, 2) and m["tp"] == 2
per, tot = m["pool_bytes_per_shard"], m["pool_bytes_total"]
assert per < tot, (per, tot)
if {exact_half!r}:
    assert 2 * per == tot, (per, tot)
print("STREAMS-MATCH")
'''
    assert "STREAMS-MATCH" in check_mesh(code, (1, 2))


@pytest.mark.multidevice
def test_sharded_identity_wider_meshes():
    """The same config across tp=4, a (2, 2) data x model mesh, and a
    rank-1 pure-TP mesh — all must reproduce the 1-device stream."""
    code = _PRE + '''
cfg = smoke("minitron-4b")
u, _ = serve(cfg, PROMPTS, NEWS)
for shape in [(1, 4), (2, 2), (4,)]:
    s, b = serve(dataclasses.replace(cfg, mesh_shape=shape), PROMPTS, NEWS)
    assert s == u, (shape, u, s)
    print("STREAMS-MATCH", shape)
'''
    assert check_mesh(code, (4,)).count("STREAMS-MATCH") == 3


@pytest.mark.multidevice
def test_sharded_speculation_and_decode_flash():
    """The verify step (speculative decode) and the block-table flash
    decode kernel both run inside the shard_map body; both must stay
    bit-identical, with the drafter actually firing."""
    code = _PRE + '''
motif = np.asarray([7, 3, 11, 5], np.int32)
reps = [list(np.tile(motif, 3)[:9]), list(np.tile(motif, 4)[:14])]
base = smoke("minitron-4b")
u, _ = serve(base, reps, [16, 16])
scfg = dataclasses.replace(base, speculate_k=4, speculate_ngram=1,
                           mesh_shape=(1, 2))
s, b = serve(scfg, reps, [16, 16])
assert s == u, (u, s)
sp = b.stats()["speculation"]
assert sp["tokens_drafted"] > 0 and sp["verify_rounds"] > 0, sp
f_u, _ = serve(base, reps, [10, 10])
fcfg = dataclasses.replace(base, decode_flash=True, mesh_shape=(1, 2))
f_s, _ = serve(fcfg, reps, [10, 10])
assert f_s == f_u
print("STREAMS-MATCH")
'''
    assert "STREAMS-MATCH" in check_mesh(code, (1, 2))


@pytest.mark.multidevice
def test_sharded_prefix_rehit_and_preempt_resume():
    """Host-side page movement under sharded pools: a prefix-cache
    rehit (shared pages attached into a sharded pool) and a full
    preempt/spill/resume cycle (host payloads are full-width, so
    snapshots stay mesh-portable) both reproduce the 1-device stream."""
    code = _PRE + '''
import threading

def serve_seq(bat, prompts, max_news):
    outs = []
    for i, (p, mn) in enumerate(zip(prompts, max_news)):
        r = Request(rid=i, prompt=np.asarray(p, np.int32), max_new=mn)
        t = threading.Thread(target=lambda r=r: bat.submit(r))
        t.start()
        bat.run(bat.retired + 1)
        t.join()
        outs.append(drain(r))
    return outs

base = smoke("minitron-4b")
rng = np.random.default_rng(7)
P = rng.integers(0, base.vocab_size, 24).astype(np.int32)
pcfg = dataclasses.replace(base, prefix_cache=True)
ubat = ContinuousBatcher(pcfg, registry.init(pcfg, seed=0), n_slots=2,
                         max_seq=64)
u = serve_seq(ubat, [P, P], [5, 5])
mcfg = dataclasses.replace(pcfg, mesh_shape=(1, 2))
mbat = ContinuousBatcher(mcfg, registry.init(mcfg, seed=0), n_slots=2,
                         max_seq=64)
s = serve_seq(mbat, [P, P], [5, 5])
assert s == u and mbat.prefix_hits == 1

pre = [list(range(20, 28)), list(range(60, 68))]
u2, _ = serve(base, pre, [8, 8], max_seq=32)
ppcfg = dataclasses.replace(base, kv_page_size=4, mesh_shape=(1, 2))
s2, b2 = serve(ppcfg, pre, [8, 8], max_seq=32, n_pages=5)
assert s2 == u2
assert b2.preemptions > 0 and b2.resumes > 0
assert b2.total_used_pages() == 0
print("STREAMS-MATCH")
'''
    assert "STREAMS-MATCH" in check_mesh(code, (1, 2))


@pytest.mark.multidevice
def test_serving_weights_and_pools_built_sharded_in_serving_dtype():
    """build_batcher on a mesh: every weight and pool leaf is created
    with its PartitionSpec (each device holds its shard only), weights
    in cfg.dtype, and the next-step logits on the mesh agree with one
    device's on the same weights."""
    code = _PRE + '''
import jax, jax.numpy as jnp
from repro.launch.serve import build_batcher
from repro.serve.serve_loop import make_paged_logits_step, paged_sharding_specs

def logits(b, prompts):
    for i, p in enumerate(prompts):
        b.submit(Request(rid=i, prompt=np.asarray(p, np.int32), max_new=4))
    b.admit()
    while b._admitting:
        b._prefill_step()
    return np.asarray(make_paged_logits_step(b.cfg, b.page_size)(
        b.params, b.pools, b.block_tab, b.last_tok, b.pos, b.active)[1])

mcfg = dataclasses.replace(smoke("minitron-4b"), dtype="bfloat16",
                           mesh_shape=(1, 2))
b = build_batcher(mcfg, seed=0, n_slots=2, max_seq=48)
p_specs, pool_specs = paged_sharding_specs(mcfg, 8, b.mesh)
for tree, specs in ((b.params, p_specs), (b.pools, pool_specs)):
    for a, spec in zip(jax.tree.leaves(tree), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))):
        want = jax.sharding.NamedSharding(b.mesh, spec)
        assert a.sharding.is_equivalent_to(want, a.ndim), (a.shape, spec)
assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(b.params))
wq = b.params["blocks"]["attn"]["wq"]
assert {s.data.shape[-1] for s in wq.addressable_shards} == {wq.shape[-1] // 2}
sharded = logits(b, PROMPTS[:2])
whole = jax.device_put(b.params, jax.devices()[0])
one = build_batcher(dataclasses.replace(mcfg, mesh_shape=()), params=whole,
                    n_slots=2, max_seq=48)
ref = logits(one, PROMPTS[:2])
err = np.linalg.norm(sharded - ref) / np.linalg.norm(ref)
assert err < 5e-2, err
print("BUILT-SHARDED", err)
'''
    assert "BUILT-SHARDED" in check_mesh(code, (1, 2))


@pytest.mark.multidevice
def test_launch_cli_mesh_banner():
    """--mesh end to end through the CLI: the banner surfaces the mesh
    shape and the per-shard pool bytes (no collective counts: the static
    ones derived from the model shape are gone)."""
    code = '''
from repro.launch.serve import main
main(["--arch", "minitron-4b", "--smoke", "--page-size", "8",
      "--requests", "2", "--slots", "2", "--prompt-len", "6",
      "--max-new", "4", "--mesh", "1x2"])
'''
    out = check_mesh(code, (1, 2))
    assert "mesh: 1x2" in out and "tp=2" in out
    assert "B/shard" in out and "B total" in out and "psum" not in out
