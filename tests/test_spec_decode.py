"""Speculative multi-token decode on the paged KV cache: draft /
verify-as-chunk / commit-or-rollback by block-table swap.

The contract under test is BIT-IDENTITY: greedy verification accepts
exactly the prefix of drafted tokens that plain greedy decode would
have produced, and rejected tails roll back by swapping scratch pages
out of the block table — so for every cache layout family (flat GQA,
gemma3 local/global ring, MLA latent, int8+scale pages) the spec and
non-spec token streams must match token for token, including across
forced rejections, pool-pressure preemption, prefix-cache sharing, and
injected verify-site faults.
"""

import dataclasses
import threading

import numpy as np
import pytest

from repro import configs
from repro.configs.base import smoke_variant
from repro.models import registry
from repro.serve.batching import ContinuousBatcher, Request, drain
from repro.serve.resilience import (BatcherFault, RequestErrored,
                                    RequestExpired, ServeSupervisor)

PAGE = 8
MAX_SEQ = 32


@pytest.fixture(scope="module")
def model():
    cfg = smoke_variant(configs.get("minitron-4b"))
    return cfg, registry.init(cfg, 0)


def _spec_cfg(cfg, k=4, **kw):
    # speculate_ngram=1: the permissive single-token drafter, so short
    # smoke runs draft early and often — this suite exercises the
    # commit/rollback machinery, not drafter selectivity (the default
    # full-ngram requirement is covered by the probe-schedule test and
    # the bench's adversarial gate).
    base = dict(kv_page_size=PAGE, prefill_chunk=PAGE, speculate_k=k,
                speculate_ngram=1)
    base.update(kw)
    return dataclasses.replace(cfg, **base)


def _plain_cfg(cfg, **kw):
    return _spec_cfg(cfg, k=0, **kw)


def _repetitive_prompts(plens):
    """Motif-cycled prompts: tiny smoke models decode these into short
    cycles, so the n-gram drafter actually fires."""
    motif = np.asarray([7, 3, 11, 5], np.int32)
    return [np.tile(motif, L // 4 + 1)[:L].astype(np.int32) for L in plens]


def _oracle_drafter(bat, plain):
    """Drafter stub that proposes the next tokens of each request's plain
    greedy stream: verification accepts every draft by construction, so
    verify rounds run whatever the random weights decode to."""
    def draft(slot):
        r = bat._slot_req[slot]
        done = len(bat._history[slot]) - len(r.prompt)
        cap = min(bat.speculate_k, bat._host_remaining[slot] - 1)
        return [int(t) for t in plain[r.rid][done:done + max(cap, 0)]]
    return draft


def _random_prompts(cfg, plens):
    return [np.asarray(registry.make_batch(cfg, "prefill", 1, L,
                                           seed=L)["tokens"][0])
            for L in plens]


def _run(cfg, params, prompts, max_news, *, n_slots=2, max_seq=MAX_SEQ,
         **kw):
    bat = ContinuousBatcher(cfg, params, n_slots=n_slots, max_seq=max_seq,
                            **kw)
    reqs = [Request(rid=i, prompt=p, max_new=mn)
            for i, (p, mn) in enumerate(zip(prompts, max_news))]
    prod = threading.Thread(target=lambda: [bat.submit(r) for r in reqs])
    prod.start()
    bat.run(len(reqs))
    prod.join()
    return [drain(r) for r in reqs], bat


def _check_allocators(bat):
    for alloc in bat._alloc.values():
        alloc.check_consistency()


# --- bit-identity across every cache layout family -------------------------------------


@pytest.mark.parametrize("arch,extra", [
    ("minitron-4b", {}),                          # flat GQA
    ("minitron-4b", {"kv_cache_dtype": "int8"}),  # int8 + scale pages
    ("minitron-4b", {"decode_flash": True}),      # block-table flash kernel
    ("gemma3-12b", {}),                           # local ring + global flat
    ("deepseek-v2-lite-16b", {}),                 # MLA latent pages
])
def test_spec_bit_identical_across_layouts(arch, extra):
    cfg = smoke_variant(configs.get(arch))
    cfg = dataclasses.replace(cfg, **extra)
    params = registry.init(cfg, 0)
    prompts = _repetitive_prompts([9, 14, 6, 12])
    # long enough that every family's continuation develops the repeats
    # the full-span drafter needs (short drafts are never proposed).
    max_news = [16, 16, 16, 16]
    plain, _ = _run(_plain_cfg(cfg), params, prompts, max_news,
                    max_seq=48)
    spec, bat = _run(_spec_cfg(cfg), params, prompts, max_news,
                     max_seq=48)
    assert spec == plain
    st = bat.stats()["speculation"]
    assert st["tokens_drafted"] > 0, "repetitive workload must actually draft"
    assert st["tokens_drafted"] == (st["tokens_accepted"]
                                    + st["tokens_rolled_back"])
    assert bat.total_used_pages() == 0
    _check_allocators(bat)


def test_spec_bit_identical_random_workload(model):
    """Novel (random) prompts rarely draft — and when they do, every
    rejection must roll back cleanly to the plain-decode stream."""
    cfg, params = model
    prompts = _random_prompts(cfg, [9, 14, 6, 12])
    max_news = [10, 14, 12, 8]
    plain, _ = _run(_plain_cfg(cfg), params, prompts, max_news)
    spec, bat = _run(_spec_cfg(cfg), params, prompts, max_news)
    assert spec == plain
    _check_allocators(bat)


# --- forced rejection + self-disable ----------------------------------------------------


def test_forced_rejection_rolls_back_and_self_disables(model):
    """A drafter that always proposes garbage: every draft must be
    rejected (rolled back by block-table swap) without perturbing the
    output stream, and the per-slot acceptance EWMA must stop the
    bleeding — drafting self-disables after a few bad rounds instead of
    paying a verify step forever."""
    cfg, params = model
    prompts = _repetitive_prompts([9, 12])
    max_news = [16, 16]
    plain, _ = _run(_plain_cfg(cfg), params, prompts, max_news)

    scfg = _spec_cfg(cfg)
    bat = ContinuousBatcher(scfg, params, n_slots=2, max_seq=MAX_SEQ)
    bat._draft = lambda slot: (
        [] if bat._accept_ewma[slot] < bat.speculate_min_accept
        else [1, 2, 3])
    reqs = [Request(rid=i, prompt=p, max_new=mn)
            for i, (p, mn) in enumerate(zip(prompts, max_news))]
    prod = threading.Thread(target=lambda: [bat.submit(r) for r in reqs])
    prod.start()
    bat.run(len(reqs))
    prod.join()
    assert [drain(r) for r in reqs] == plain
    st = bat.stats()["speculation"]
    assert st["tokens_rolled_back"] > 0
    # EWMA 1.0 -> 0.5 -> 0.25 < 0.3: at most 3 drafting rounds per slot
    # before self-disable (garbage may accidentally match argmax once,
    # allow slack), so rollbacks are bounded, not O(steps).
    assert st["verify_rounds"] <= 10
    assert bat.steps > st["verify_rounds"], "plain path must take over"
    _check_allocators(bat)


def test_probe_schedule_gates_drafting(model):
    """A self-disabled slot re-probes only on the global step grid at or
    after its (backed-off) ``_probe_at``; enabled slots draft freely."""
    cfg, params = model
    bat = ContinuousBatcher(_spec_cfg(cfg, speculate_ngram=3), params,
                            n_slots=2, max_seq=MAX_SEQ)
    bat._history[0] = [7, 3, 11, 5] * 6        # periodic: full-span draft
    bat._host_remaining[0] = 10
    assert bat._draft(0), "enabled slot must draft"
    bat._accept_ewma[0] = 0.0                  # self-disabled
    bat._probe_at[0] = 8
    bat.steps = 7
    assert not bat._draft(0), "before probe_at: no probe"
    bat.steps = 9
    assert not bat._draft(0), "off the probe grid: no probe"
    bat.steps = 2 * bat.speculate_probe
    assert bat._draft(0), "grid tick past probe_at: probe drafts"
    bat._probe_at[0] = bat.steps + 1
    assert not bat._draft(0), "backed off past this tick: no probe"
    # a probe whose history scan finds no full-ngram match consumes the
    # probe and backs off exponentially — it answered "not draftable"
    # for free, so the next stray match can't fire a full-priced round.
    bat._history[0] = list(range(30))          # novel: no repeated 3-gram
    bat._probe_gap[0] = 4
    bat._probe_at[0] = bat.steps
    assert not bat._draft(0), "novel history: probe scan finds nothing"
    assert bat._probe_gap[0] == 8, "no-match probe doubles the gap"
    assert bat._probe_at[0] == bat.steps + 8


# --- preemption + deadline expiry under speculation -------------------------------------


def test_spec_survives_pool_pressure_preemption(model):
    """A pool too small for all slots: speculation must never preempt
    on its own (dry scratch allocation just drops the draft), and the
    ordinary spill/resume preemption around it must keep the output
    stream bit-identical to the uncontended non-spec run."""
    cfg, params = model
    prompts = _repetitive_prompts([9, 12, 7, 10])
    max_news = [12, 12, 10, 10]
    plain, _ = _run(_plain_cfg(cfg), params, prompts, max_news,
                    n_slots=4, max_seq=MAX_SEQ)
    spec, bat = _run(_spec_cfg(cfg), params, prompts, max_news,
                     n_slots=4, max_seq=MAX_SEQ, n_pages=9)
    assert spec == plain
    assert bat.preemptions > 0, "pool must actually be contended"
    assert bat.total_used_pages() == 0
    _check_allocators(bat)


def test_spec_deadline_expiry_frees_everything(model):
    """A request expiring mid-decode while its neighbour speculates:
    the expiry path must free every page (no scratch can leak — scratch
    lives strictly inside one step call) and the survivor's stream must
    stay bit-identical."""
    cfg, params = model
    prompts = _repetitive_prompts([9, 12])
    plain, _ = _run(_plain_cfg(cfg), params, prompts, [16, 16])

    fake = [100.0]   # NB: submitted_at == 0.0 is the unstamped sentinel
    scfg = _spec_cfg(cfg)
    bat = ContinuousBatcher(scfg, params, n_slots=2, max_seq=MAX_SEQ,
                            clock=lambda: fake[0])
    bat._draft = _oracle_drafter(bat, plain)
    live = Request(rid=0, prompt=prompts[0], max_new=16)
    dying = Request(rid=1, prompt=prompts[1], max_new=16,
                    deadline_ms=500.0)
    bat.submit(live)
    bat.submit(dying)
    bat.admit()
    while bat._admitting:
        bat._prefill_step()
    for _ in range(3):
        bat.step()                         # speculative rounds, both alive
    fake[0] += 10.0                        # 10 000 ms pass: dying expires
    bat.run(2)                             # retires dying, finishes live
    assert drain(live) == plain[0]
    with pytest.raises(RequestExpired) as ei:
        drain(dying)
    assert len(ei.value.tokens) >= 1       # partial prefix delivered
    assert bat.stats()["expired"] == 1
    assert bat.stats()["speculation"]["tokens_accepted"] > 0
    assert bat.total_used_pages() == 0
    _check_allocators(bat)


# --- prefix cache x speculation ---------------------------------------------------------


def test_prefix_rehit_unaffected_by_speculating_sharer(model):
    """Speculative KV writes land in private scratch pages, never in
    shared/refcounted ones: a request speculating over a cached prefix
    must leave the cached pages bit-stable, so a later rehit of the
    same prompt streams the exact same tokens (and still hits)."""
    cfg, params = model
    prompt = _repetitive_prompts([12])[0]

    def serve(scfg):
        bat = ContinuousBatcher(scfg, params, n_slots=2, max_seq=MAX_SEQ)
        outs = []
        for rid in range(3):               # cold, rehit, rehit-after-spec
            r = Request(rid=rid, prompt=prompt.copy(), max_new=12)
            bat.submit(r)
            bat.run(rid + 1)
            outs.append(drain(r))
        return outs, bat

    plain, _ = serve(_plain_cfg(cfg, prefix_cache=True))
    spec, bat = serve(_spec_cfg(cfg, prefix_cache=True))
    assert spec == plain
    assert spec[1] == spec[0] and spec[2] == spec[0]
    st = bat.stats()
    assert st["prefix_hits"] >= 2
    assert st["speculation"]["tokens_drafted"] > 0
    _check_allocators(bat)


# --- chaos: injected faults at the verify site ------------------------------------------


def test_verify_fault_unwinds_scratch_before_dying(model):
    """An injected fault at the verify site (after scratch setup) is
    fatal — but the unwind must free the scratch pages and restore the
    block-table entries first, leaving the allocator consistent for
    fail_inflight."""
    cfg, params = model
    prompts = _repetitive_prompts([9, 12])
    plain, _ = _run(_plain_cfg(cfg), params, prompts, [12, 12])
    scfg = _spec_cfg(cfg)
    bat = ContinuousBatcher(scfg, params, n_slots=2, max_seq=MAX_SEQ,
                            faults="verify:2")
    bat._draft = _oracle_drafter(bat, plain)
    reqs = [Request(rid=i, prompt=p, max_new=12)
            for i, p in enumerate(prompts)]
    for r in reqs:
        bat.submit(r)
    with pytest.raises(BatcherFault):
        bat.run(2)
    for r in reqs:
        with pytest.raises(RequestErrored):
            drain(r, timeout=2.0)
    assert bat.stats()["speculation"]["tokens_accepted"] > 0
    _check_allocators(bat)


def test_supervised_recovery_from_verify_fault_is_bit_identical(model):
    """Under a ServeSupervisor the verify-site crash is journaled and
    replayed: every surviving request's stream must be bit-identical to
    the fault-free non-spec run (greedy replay + greedy verification
    are both deterministic)."""
    cfg, params = model
    prompts = _repetitive_prompts([9, 12])
    plain, _ = _run(_plain_cfg(cfg), params, prompts, [12, 12])
    scfg = _spec_cfg(cfg)
    bat = ContinuousBatcher(scfg, params, n_slots=2, max_seq=MAX_SEQ,
                            faults="verify:2")
    bat._draft = _oracle_drafter(bat, plain)
    sup = ServeSupervisor(bat)
    reqs = [Request(rid=i, prompt=p, max_new=12)
            for i, p in enumerate(prompts)]
    for r in reqs:
        bat.submit(r)
    sup.run(len(reqs))
    assert [drain(r) for r in reqs] == plain
    assert sup.report.restarts >= 1
    assert bat.stats()["speculation"]["tokens_accepted"] > 0
    _check_allocators(bat)
