"""The ``deepseek-v2-lite`` configuration at small widths on the CPU: the
float32 reference (``reference_mla_moe``) against the program's forward
and its serving path, the published routing kept dropless whatever the
batch holds, YaRN against the published numbers, the work count, and
the ``doc-qa`` cell's own check (the program passes, the fp8 control
and a broken decode fail)."""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small_mla_moe as small  # noqa: E402

from bench import cell, reference_mla_moe, run, weights  # noqa: E402
from bench.families import mla_moe  # noqa: E402
from test_bench_check import _broken_decode  # noqa: E402

WORKLOAD = "deepseek-v2-lite.doc-qa"
PAGE = 8


def _model(conf=None, **fields):
    """(dims, float32 program config, float32 weights) of a small copy."""
    conf = conf or small.config()
    m = cell.model_dims(conf)
    cfg = dataclasses.replace(cell.model_config(conf, "small"),
                              dtype="float32", **fields)
    return m, cfg, weights.make(m, 2 ** 31 + 5, "float32")


def _tokens(m, b, s, seed=0):
    return np.random.default_rng(seed).integers(0, m["vocab"], (b, s))


@pytest.mark.parametrize("published", [
    {},                                                   # as published
    {"norm_topk_prob": True},                             # renormalised top-k
    {"rope_scaling": None},                               # plain RoPE
], ids=["published", "renormalised", "no-yarn"])
def test_reference_matches_program(published):
    """The program's full forward (its training path, with capacity for
    every token) against the reference: MLA, YaRN, the published
    routing and the dense layer 0."""
    import jax.numpy as jnp
    from repro.models import registry
    conf = dict(small.config(), **published)
    m, cfg, params = _model(conf)
    cfg = dataclasses.replace(cfg, capacity_factor=m["n_experts"] / m["top_k"])
    toks = _tokens(m, 2, 40)
    want = np.asarray(registry.forward(cfg, params,
                                       {"tokens": jnp.asarray(toks)}))
    got = np.asarray(reference_mla_moe.logits(m, params, toks))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _served(cfg, params, seqs, plens, n_slots=4):
    """Each sequence's logits at positions plen - 1 .. len - 1 through
    the serving modes: its prompt in one chunk against the paged latent
    cache, then teacher-forced decode calls of all ``n_slots`` rows at
    once (rows past the sequences masked to no pages, as the decode
    program masks idle slots)."""
    import jax.numpy as jnp
    from repro.models import params as PP
    from repro.models import registry
    max_seq = 64
    nb = max_seq // PAGE
    n_pages = n_slots * nb
    pools = PP.init_params(registry.paged_cache_decls(cfg, n_pages, PAGE))
    tab = np.arange(n_pages, dtype=np.int32).reshape(n_slots, nb)
    out = [[] for _ in seqs]
    for i, (seq, n) in enumerate(zip(seqs, plens)):
        logits, pools = registry.forward(
            cfg, params, {"tokens": jnp.asarray(seq[None, :n])}, mode="chunk",
            cache={"pages": pools, "block_tab": {"latent": tab[i:i + 1]}},
            pos=jnp.asarray([0]), last_pos=jnp.asarray([n - 1]),
            cache_offset=jnp.asarray([0]))
        out[i].append(np.asarray(logits[0, -1]))
    for p in range(min(plens), max(len(s) for s in seqs)):
        live = np.array([i < len(seqs) and plens[i] <= p < len(seqs[i])
                         for i in range(n_slots)])
        tok = np.array([seqs[i][p] if live[i] else 0
                        for i in range(n_slots)], np.int32)
        bt = np.where(live[:, None], tab, n_pages)
        logits, pools = registry.forward(
            cfg, params, {"tokens": jnp.asarray(tok[:, None])}, mode="decode",
            cache={"pages": pools, "block_tab": {"latent": jnp.asarray(bt)}},
            pos=jnp.asarray(np.full(n_slots, p, np.int32)))
        for i in np.flatnonzero(live):
            out[i].append(np.asarray(logits[i, -1]))
    return [np.stack(o) for o in out]


def test_served_logits_match_reference_with_one_route():
    """A zero router sends every token to the same experts (0..k-1, the
    first of equal scores).  The capacity-bounded training dispatch then
    drops most of them; the serving path drops none, and its logits
    match the reference's: the prompt's chunk, which reads its own rows
    unrounded, to float32 rounding; the decode calls, which read the
    latent rows as the bf16 pages hold them (rounded to 2^-9), within
    0.06 (they read up to 0.035 here; the dropping dispatch 0.31)."""
    import jax.numpy as jnp
    from repro.models import params as PP
    from repro.models import registry
    m, cfg, params = _model()
    rest = params["blocks"]["rest"]["moe"]
    rest["router"] = jnp.zeros_like(rest["router"])
    seqs = list(_tokens(m, 2, 24, seed=1))
    plens = [10, 17]
    routes = registry.forward(
        cfg, params, {"tokens": jnp.asarray(np.stack(seqs))}, mode="chunk",
        cache={"pages": PP.init_params(registry.paged_cache_decls(
            cfg, 8, PAGE)), "block_tab": {"latent": jnp.arange(8).reshape(
                2, 4)}},
        pos=jnp.zeros(2, jnp.int32), last_pos=jnp.full(2, 23),
        cache_offset=jnp.zeros(2, jnp.int32), with_routes=True)[2]
    assert (np.asarray(routes) == np.arange(m["top_k"])).all()
    got = _served(cfg, params, seqs, plens)
    for seq, n, g in zip(seqs, plens, got):
        want = np.asarray(reference_mla_moe.logits(m, params, seq[None]))[0]
        np.testing.assert_allclose(g[0], want[n - 1], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(g[1:], want[n:], rtol=0, atol=0.06)
    dropped = np.asarray(registry.forward(
        cfg, params, {"tokens": jnp.asarray(np.stack(seqs))}))
    want = np.asarray(reference_mla_moe.logits(m, params, np.stack(seqs)))
    assert np.abs(dropped - want).max() > 0.2


def test_whole_prompt_prefill_runs_in_blocks():
    """The one-shot prefill of a prompt longer than a dropless block
    (``MOE_BLOCK`` tokens) runs its experts block by block and still
    drops nothing: with every token routed to the same experts its last
    logits match the reference's."""
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import registry
    m, cfg, params = _model()
    rest = params["blocks"]["rest"]["moe"]
    rest["router"] = jnp.zeros_like(rest["router"])
    toks = _tokens(m, 1, L.MOE_BLOCK + 44, seed=3)
    got, _ = registry.forward(cfg, params, {"tokens": jnp.asarray(toks)},
                              mode="prefill")
    want = np.asarray(reference_mla_moe.logits(m, params, toks))[0, -1]
    np.testing.assert_allclose(np.asarray(got)[0, -1], want, rtol=2e-4,
                               atol=2e-4)


def test_one_request_alone_and_in_a_full_batch():
    """A request's served logits do not depend on its batchmates: alone
    (every other slot idle) and beside three others, bit for bit."""
    m, cfg, params = _model()
    seqs = list(_tokens(m, 4, 20, seed=2))
    plens = [6, 9, 4, 11]
    alone = _served(cfg, params, seqs[:1], plens[:1])[0]
    full = _served(cfg, params, seqs, plens)[0]
    np.testing.assert_array_equal(alone, full)


def test_yarn_frequencies_and_scale():
    """DeepSeek-V2-Lite's YaRN (factor 40, original 4,096 positions,
    beta_fast 32, beta_slow 1, rope head 64): theta's frequency for
    pairs 0-10, theta's / 40 from pair 23, linear between; cos and sin
    unscaled (mscale = mscale_all_dim); softmax scale 192^-1/2 (0.1 *
    0.707 * ln 40 + 1)^2 = 0.11472, in the program and the reference."""
    from repro import configs
    from repro.models import layers as L
    cfg = configs.get("deepseek-v2-lite-16b")
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    got = L.yarn_freqs(64, 1e4, 40, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[10] == pytest.approx(extra[10], rel=1e-6)
    assert got[23] == pytest.approx(extra[23] / 40, rel=1e-6)
    scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert scale == pytest.approx(0.11472, abs=5e-6)
    assert L.mla_scale(cfg) == pytest.approx(scale, rel=1e-12)
    m = cell.model_dims(cell.config("deepseek-v2-lite"))
    freqs, cs, ref_scale = reference_mla_moe.rope_tables(m)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert cs == 1.0 and ref_scale == pytest.approx(scale, rel=1e-12)


def test_family_shapes_are_the_programs():
    """At published widths the benchmark's weight tree is the program's,
    leaf for leaf, and counts 5.18B parameters (10.36 GB in bf16)."""
    import jax
    from repro.models import registry
    conf = cell.config("deepseek-v2-lite")
    m = cell.model_dims(conf)
    cfg = cell.model_config(conf, "deepseek-v2-lite")
    want = jax.tree.map(lambda a: tuple(a.shape), registry.abstract(cfg))
    got = weights.nest({k: v[0] for k, v in mla_moe.shapes(m).items()})
    assert got == want
    n = sum(math.prod(s) for s, _ in mla_moe.shapes(m).values())
    assert 5.17e9 < n < 5.19e9


def test_token_flops_by_hand():
    m = cell.model_dims(cell.config("deepseek-v2-lite"))
    d, h, E = 2048, 16, 64
    proj = 2 * (d * h * 192 + d * 576 + h * 128 * 512 + h * 512 * 128
                + h * 128 * d)
    dense = proj + 6 * d * 10944
    moe = proj + 2 * d * E + 8 * 6 * d * 1408          # 6 routed + 2 shared
    key = 2 * h * 576 + 2 * h * 512
    assert mla_moe.attn_key_flops(m) == key
    assert mla_moe.token_flops(m, 0, False) == dense + 8 * moe + 9 * key
    grown = mla_moe.token_flops(m, 999, True) - mla_moe.token_flops(m, 0,
                                                                    False)
    assert grown == 9 * 999 * key + 2 * d * 102400
    for start, end, last in ((0, 16, False), (6144, 6171, True)):
        want = sum(mla_moe.token_flops(m, p, last and p == end - 1)
                   for p in range(start, end))
        assert mla_moe.span_flops(m, start, end, last) == pytest.approx(
            want, rel=1e-12)


def _run_small(seed, **kw):
    return run.run_cell(WORKLOAD, seed, 3.0, False, config=small.config(),
                        mix=small.mix(), **kw)


def test_program_passes_and_control_fails():
    """The run's own verdict passes the program and, with the fp8
    control's tokens in the program's place, fails the control."""
    r = _run_small(2 ** 31 + 77, control=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    c = r["control"]
    assert not c["correct"], c["checks"]
    assert c["checks"]["max_logit_gap"]["value"] > \
        c["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["token", "stale"])
def test_broken_decode_is_not_correct(monkeypatch, fault):
    _broken_decode(monkeypatch, fault)
    r = _run_small(5)
    assert r["attempted"] > 0
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]


def test_expert_load_counts():
    """``stats()["moe"]`` while telemetry is on: every live token of a
    decode call and every prompt token of a chunk makes top_k
    assignments in each MoE layer, and a call hits between top_k and E
    experts a layer."""
    import threading
    from repro.launch.serve import build_batcher
    from repro.serve.batching import Request, drain
    from repro.serve.telemetry import ServeTelemetry
    m, cfg, params = _model()
    conf = small.config()
    cfg = dataclasses.replace(cfg, **cell.serving(conf)[0])
    bat = build_batcher(cfg, params=params, n_slots=4, max_seq=256,
                        telemetry=ServeTelemetry())
    plens, news = [7, 30, 45], [5, 3, 9]
    reqs = [Request(rid=i, prompt=_tokens(m, 1, n, seed=i)[0].astype(np.int32),
                    max_new=k) for i, (n, k) in enumerate(zip(plens, news))]
    t = threading.Thread(target=lambda: [bat.submit(r) for r in reqs])
    t.start()
    bat.run(len(reqs))
    t.join()
    [drain(r) for r in reqs]
    s = bat.stats()["moe"]
    k, E, layers = m["top_k"], m["n_experts"], m["n_layers"] - 1
    prefill = np.asarray(s["prefill_tokens"])
    decode = np.asarray(s["decode_tokens"])
    assert prefill.shape == decode.shape == (layers, E)
    assert (prefill.sum(1) == k * sum(plens)).all()
    assert (decode.sum(1) == k * sum(n - 1 for n in news)).all()
    calls = s["decode_calls"]
    assert calls == bat.steps > 0
    assert all(k * calls <= h <= E * calls for h in s["decode_experts_hit"])
    assert "moe" not in build_batcher(cfg, params=params, n_slots=4,
                                      max_seq=256).stats()
