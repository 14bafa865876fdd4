"""Paged KV cache + chunked prefill: allocator invariants, admission
backpressure, block-table reuse correctness, paged-vs-dense token
equivalence across every CacheLayout family (flat GQA, int8 scale
pages, gemma3 local/global ring-of-pages, MLA latent pages), stall-free
chunked admission, the mask-aware ring prefill for windowed buckets,
the block-table-aware decode flash kernel, and the lazy-decode-growth /
slot-preemption invariants (token-identical resume, allocator
consistency across spill/restore, dense-equivalent page budget).
"""

import dataclasses
import functools
import math
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.configs.base import smoke_variant
from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention_decode_paged,
                                           pages_per_block)
from repro.kernels.platform import paged_decode_kernel
from repro.models import registry
from repro.serve.batching import (ContinuousBatcher, PageAllocator, Request,
                                  drain)
from repro.serve.serve_loop import greedy_generate, make_paged_logits_step
from repro.serve.telemetry import ServeTelemetry


@pytest.fixture(scope="module")
def model():
    cfg = smoke_variant(configs.get("minitron-4b"))
    return cfg, registry.init(cfg, 0)


def _run_batcher(cfg, params, prompts, max_news, *, n_slots=2, max_seq=32,
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


def _prompts(cfg, plens):
    return [np.asarray(registry.make_batch(cfg, "prefill", 1, L,
                                           seed=L)["tokens"][0])
            for L in plens]


# --- page allocator -------------------------------------------------------------------


def test_allocator_alloc_free_reuse_invariants():
    a = PageAllocator(8)
    p1 = a.alloc(3)
    p2 = a.alloc(4)
    assert len(p1) == 3 and len(p2) == 4
    assert len(set(p1) | set(p2)) == 7          # no page handed out twice
    assert a.free_pages == 1 and a.used_pages == 7
    # insufficient: returns None and allocates NOTHING (no partial grab).
    assert a.alloc(2) is None
    assert a.free_pages == 1 and a.used_pages == 7
    a.free(p1)
    assert a.free_pages == 4
    with pytest.raises(ValueError):
        a.free(p1)                               # double free rejected
    p3 = a.alloc(4)                              # freed pages are reusable
    assert p3 is not None and set(p3) & set(p1)
    a.free(p2)
    a.free(p3)
    assert a.free_pages == 8 and a.used_pages == 0


def test_allocator_exhaustion_and_full_cycle():
    a = PageAllocator(4)
    p = a.alloc(4)
    assert a.alloc(1) is None
    a.free(p)
    assert a.alloc(4) is not None


# --- paged batcher: correctness + backpressure ----------------------------------------


def test_paged_matches_dense_token_for_token(model):
    """Acceptance: paged batcher output == dense batcher output for every
    request, including under page-pool backpressure (pool smaller than
    the dense-equivalent capacity)."""
    cfg, params = model
    plens = [8, 5, 11, 3, 9, 6]
    max_news = [4, 7, 2, 5, 3, 6]
    prompts = _prompts(cfg, plens)
    gold, _ = _run_batcher(cfg, params, prompts, max_news)
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    got, bat = _run_batcher(paged_cfg, params, prompts, max_news, n_pages=6)
    assert bat.paged
    assert got == gold
    assert bat.total_used_pages() == 0           # all pages returned


@pytest.mark.parametrize("arch,window", [("minitron-4b", None),
                                         ("minitron-4b", 16),
                                         ("phi3p5-moe-42b", None)])
def test_paged_matches_dense_across_families(arch, window):
    """Dense GQA, sliding-window, and MoE configs all produce identical
    tokens through the paged and dense batchers."""
    cfg = smoke_variant(configs.get(arch))
    if window is not None:
        cfg = dataclasses.replace(cfg, sliding_window=window)
    params = registry.init(cfg, 0)
    plens = [5, 12, 21]
    max_news = [4, 3, 4]
    prompts = _prompts(cfg, plens)
    gold, _ = _run_batcher(cfg, params, prompts, max_news, max_seq=48)
    got, bat = _run_batcher(dataclasses.replace(cfg, kv_page_size=8),
                            params, prompts, max_news, max_seq=48)
    assert bat.paged
    assert got == gold


@pytest.mark.parametrize("arch,kw", [
    ("gemma3-12b", {}),                              # local/global tree
    ("deepseek-v2-lite-16b", {}),                    # MLA latent pages
    ("minitron-4b", {"kv_cache_dtype": "int8"}),     # int8 + scale pages
])
def test_structured_layouts_paged_match_dense(arch, kw):
    """Acceptance: every CacheLayout family — gemma3's window-aware
    local/global split, MLA's compressed latent cache, int8 KV with
    per-position scale pages — is paged-supported and produces the dense
    batcher's tokens exactly.  Prompts fit one prefill chunk so both
    paths see identical rounding."""
    cfg = dataclasses.replace(smoke_variant(configs.get(arch)), **kw)
    params = registry.init(cfg, 0)
    plens = [5, 12, 21]
    max_news = [4, 3, 4]
    prompts = _prompts(cfg, plens)
    gold, _ = _run_batcher(cfg, params, prompts, max_news, max_seq=48)
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    assert registry.paged_supported(paged_cfg)
    got, bat = _run_batcher(paged_cfg, params, prompts, max_news,
                            max_seq=48, prefill_chunk=32)
    assert bat.paged
    assert got == gold
    assert bat.total_used_pages() == 0


def test_gemma3_local_pages_window_bounded():
    """The gemma3 local page group is a ring: its table width (and so
    every slot's local page count) is O(window/page) regardless of
    max_seq, while the global group grows with the sequence."""
    cfg = dataclasses.replace(smoke_variant(configs.get("gemma3-12b")),
                              kv_page_size=8)
    params = registry.init(cfg, 0)
    bat = ContinuousBatcher(cfg, params, n_slots=2, max_seq=64)
    w, page = cfg.sliding_window, 8
    assert bat.n_blocks["local"] == w // page + 1     # ring, not 64/8
    assert bat.n_blocks["global"] == 64 // page
    prompts = _prompts(cfg, [40])
    gold = list(np.asarray(greedy_generate(
        cfg, params, {"tokens": jnp.asarray(prompts[0])[None]}, steps=6,
        max_seq=64)[0]))
    got, bat = _run_batcher(cfg, params, prompts, [6], max_seq=64,
                            prefill_chunk=64)
    assert got == [gold]
    # a 40-token prompt + decode held at most ring-width local pages.
    assert bat.peak_pages <= (w // page + 1) + -(-64 // page)


def test_paged_falls_back_to_dense_for_recurrent_families():
    """ssm keeps O(1)/slot recurrent state: kv_page_size must be ignored
    (dense fallback), and outputs still match the greedy path."""
    cfg = dataclasses.replace(smoke_variant(configs.get("mamba2-1p3b")),
                              kv_page_size=8)
    params = registry.init(cfg, 0)
    prompts = _prompts(cfg, [6, 9])
    golds = [list(np.asarray(greedy_generate(
        cfg, params, {"tokens": jnp.asarray(p)[None]}, steps=3,
        max_seq=24)[0])) for p in prompts]
    got, bat = _run_batcher(cfg, params, prompts, [3, 3], max_seq=24)
    assert not bat.paged
    assert got == golds


def test_out_of_pages_admission_backpressure(model):
    """A request that cannot get pages WAITS in the FIFO (no error) and
    admits once a retire frees its pages."""
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    # pool of 3 pages; each request needs ceil((8+8)/8) = 2 pages -> only
    # one request can be in flight at a time.
    plens = [8, 8, 8]
    prompts = _prompts(cfg, plens)
    gold, _ = _run_batcher(cfg, params, prompts, [8, 8, 8])
    got, bat = _run_batcher(paged_cfg, params, prompts, [8, 8, 8],
                            n_pages=3)
    assert got == gold
    assert bat.retired == 3
    assert bat.total_used_pages() == 0


def test_unservable_request_rejected_not_deadlocked(model):
    """A request needing more pages than the WHOLE pool can never be
    served: its consumer gets a typed ``RequestRejected`` (no tokens,
    no livelock, no drain timeout) and other requests still serve."""
    from repro.serve.resilience import RequestRejected
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    prompts = _prompts(cfg, [20, 6])
    bat = ContinuousBatcher(paged_cfg, params, n_slots=2, max_seq=32,
                            n_pages=2)
    reqs = [Request(rid=i, prompt=p, max_new=mn)
            for i, (p, mn) in enumerate(zip(prompts, [8, 4]))]
    prod = threading.Thread(target=lambda: [bat.submit(r) for r in reqs])
    prod.start()
    bat.run(len(reqs))
    prod.join()
    with pytest.raises(RequestRejected, match="unservable") as ei:
        drain(reqs[0])
    assert ei.value.tokens == []                 # rejected, no output
    assert len(drain(reqs[1])) == 4              # small one still served
    assert bat.stats()["rejections"] == {"unservable": 1}


def test_block_table_correct_after_retire_then_reuse(model):
    """Slot/page reuse cannot leak state: many requests cycling through
    one slot (pages freed and immediately reallocated) all reproduce
    their per-request greedy outputs."""
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    plens = [9, 4, 12, 7, 10]
    prompts = _prompts(cfg, plens)
    golds = [list(np.asarray(greedy_generate(
        cfg, params, {"tokens": jnp.asarray(p)[None]}, steps=4,
        max_seq=32)[0])) for p in prompts]
    got, bat = _run_batcher(paged_cfg, params, prompts, [4] * 5,
                            n_slots=1, n_pages=4)
    assert got == golds
    assert bat.total_used_pages() == 0
    # retired slots' block-table rows are invalidated on device.
    for name, tab in bat.block_tab.items():
        assert int(jnp.min(tab)) == bat.n_pages[name]


# --- lazy decode growth + slot preemption ---------------------------------------------


def test_lazy_growth_preempt_resume_token_identical(model):
    """The preemption acceptance triple:

    * a pool too small for both decodes forces preemption mid-decode,
      and every request still produces EXACTLY its uncontended tokens
      (pages are spilled/restored bit-identically);
    * the allocator free list is consistent across spill/restore — all
      pages return, no leaks, tables invalidated;
    * the batcher actually preempted and resumed (the path ran).
    """
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=4)
    plens = [8, 8]
    max_news = [8, 8]
    prompts = _prompts(cfg, plens)
    gold, _ = _run_batcher(cfg, params, prompts, max_news)
    # full need = ceil(16/4) = 4 pages/request; prompts need 2 each.
    # pool of 5: both admit lazily (4 used), growth runs dry -> preempt.
    got, bat = _run_batcher(paged_cfg, params, prompts, max_news, n_pages=5)
    assert bat.paged
    assert bat.preemptions > 0 and bat.resumes > 0
    assert got == gold
    assert bat.total_used_pages() == 0
    for name, alloc in bat._alloc.items():
        assert alloc.free_pages == bat.n_pages[name]
    for name, tab in bat.block_tab.items():
        assert int(jnp.min(tab)) == bat.n_pages[name]


def test_priority_picks_preemption_victim(model):
    """The lowest-priority slot is preempted first: under page pressure
    the high-priority request keeps decoding while the low-priority one
    is parked — and both still finish with uncontended tokens."""
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=4)
    prompts = _prompts(cfg, [8, 8])
    golds = [list(np.asarray(greedy_generate(
        cfg, params, {"tokens": jnp.asarray(p)[None]}, steps=8,
        max_seq=32)[0])) for p in prompts]
    bat = ContinuousBatcher(paged_cfg, params, n_slots=2, max_seq=32,
                            n_pages=5)
    lo = Request(rid=0, prompt=prompts[0], max_new=8, priority=0)
    hi = Request(rid=1, prompt=prompts[1], max_new=8, priority=1)
    import threading
    prod = threading.Thread(target=lambda: [bat.submit(lo), bat.submit(hi)])
    prod.start()
    bat.run(2)
    prod.join()
    assert [drain(lo), drain(hi)] == golds
    assert bat.preemptions > 0
    # every preemption hit the low-priority request.
    assert set(bat.preempted_rids) == {0}


def test_lazy_growth_stays_within_dense_budget(model):
    """Lazy growth must never allocate beyond the dense-equivalent page
    budget (n_slots * blocks(max_seq) per group): pages are proportional
    to tokens actually materialized, so the peak is strictly below the
    reserve-everything bound for short requests."""
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    plens = [8, 5, 11, 3, 9, 6]
    max_news = [4, 7, 2, 5, 3, 6]
    prompts = _prompts(cfg, plens)
    got, bat = _run_batcher(paged_cfg, params, prompts, max_news,
                            n_slots=2, max_seq=32)
    dense_budget = sum(bat.n_slots * nb for nb in bat.n_blocks.values())
    assert 0 < bat.peak_pages <= dense_budget
    # short requests never materialize max_seq tokens: strictly below.
    assert bat.peak_pages < dense_budget
    assert bat.total_used_pages() == 0


def test_lazy_admits_more_than_reserve_at_equal_pool(model):
    """The bursty-admission claim: at equal pool size, reserving only
    prompt pages admits strictly more concurrent slots than reserving
    plen + max_new up front."""
    cfg, params = model

    def fill(reserve):
        paged_cfg = dataclasses.replace(cfg, kv_page_size=8,
                                        kv_reserve_decode=reserve)
        bat = ContinuousBatcher(paged_cfg, params, n_slots=8, max_seq=64,
                                n_pages=8)
        reqs = [Request(rid=i, prompt=_prompts(cfg, [4])[0], max_new=28)
                for i in range(8)]
        for r in reqs:
            bat.submit(r)
        progress = True
        while progress:
            progress = bat.admit() > 0
            while bat._admitting:
                bat._prefill_step()
                progress = True
        inflight = sum(r is not None for r in bat._slot_req)
        bat.run(len(reqs))
        for r in reqs:
            drain(r)
        return inflight, bat

    lazy_inflight, lazy_bat = fill(reserve=False)
    reserve_inflight, _ = fill(reserve=True)
    # 8 pages, 1-page prompts, 4-page worst case: 8 lazy vs 2 reserved.
    assert lazy_inflight > reserve_inflight
    assert lazy_bat.total_used_pages() == 0


def test_submit_rejects_degenerate_requests(model):
    """Admission edge case: requests that would admit into an
    immediately non-alive slot are rejected at submit() with a clear
    error instead of burning a slot and pages."""
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, n_slots=2, max_seq=32)
    with pytest.raises(ValueError, match="max_new"):
        bat.submit(Request(rid=0, prompt=_prompts(cfg, [4])[0], max_new=1))
    with pytest.raises(ValueError, match="max_new"):
        bat.submit(Request(rid=1, prompt=_prompts(cfg, [4])[0], max_new=0))
    with pytest.raises(ValueError, match="prompt length"):
        bat.submit(Request(rid=2, prompt=_prompts(cfg, [31])[0], max_new=4))
    with pytest.raises(ValueError, match="prompt length"):
        bat.submit(Request(rid=3, prompt=_prompts(cfg, [40])[0], max_new=4))


# --- chunked prefill ------------------------------------------------------------------


def test_chunked_prefill_long_prompt_equivalence(model):
    """A prompt spanning several chunks produces exactly the greedy
    tokens, and the chunk counter reflects ceil(plen/chunk)."""
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    prompts = _prompts(cfg, [40])
    gold = list(np.asarray(greedy_generate(
        cfg, params, {"tokens": jnp.asarray(prompts[0])[None]}, steps=5,
        max_seq=64)[0]))
    got, bat = _run_batcher(paged_cfg, params, prompts, [5], max_seq=64,
                            prefill_chunk=16)
    assert got == [gold]
    assert bat.prefill_chunks == math.ceil(40 / 16)


def test_chunked_admission_interleaves_with_decode(model):
    """Stall-free admission: while a long prompt is chunk-prefilling, the
    already-active slot keeps emitting tokens between chunks."""
    cfg, params = model
    paged_cfg = dataclasses.replace(cfg, kv_page_size=8)
    bat = ContinuousBatcher(paged_cfg, params, n_slots=2, max_seq=64,
                            prefill_chunk=8, prefill_interleave=1)
    short = Request(rid=0, prompt=_prompts(cfg, [4])[0], max_new=10)
    long_r = Request(rid=1, prompt=_prompts(cfg, [40])[0], max_new=2)
    bat.submit(short)
    bat.admit()
    bat._prefill_step()                          # short fully admitted
    assert bat._slot_req[0] is short             # admit() picked slot 0
    bat.submit(long_r)
    bat.admit()
    assert len(bat._admitting) == 1
    # drive the run-loop policy by hand: decode between chunks.
    tokens_between_chunks = []
    while bat._admitting:
        before = bat.steps
        bat.step()                               # interleaved decode
        bat._prefill_step()                      # one chunk
        tokens_between_chunks.append(bat.steps - before)
    # every chunk boundary saw >= 1 decode step -> the active slot was
    # never frozen for the whole 5-chunk admission.
    assert len(tokens_between_chunks) == 5
    assert all(n >= 1 for n in tokens_between_chunks)
    bat.run(2)                                   # retire both
    assert len(drain(short)) == 10 and len(drain(long_r)) == 2


# --- mask-aware ring prefill (windowed buckets) ---------------------------------------


def test_windowed_bucketed_prefill_matches_greedy(model):
    """Buckets larger than the sliding window no longer fall back to
    exact-length compiles: padded positions are masked out of the ring,
    so every length reproduces the greedy output."""
    cfg, params = model
    cfgw = dataclasses.replace(cfg, sliding_window=16)
    params_w = params                            # same weights, new mask
    max_seq = 64
    for plen in (5, 16, 21, 40):                 # straddle the window
        prompt = registry.make_batch(cfgw, "prefill", 1, plen, seed=plen)
        gold = list(np.asarray(greedy_generate(
            cfgw, params_w, prompt, steps=4, max_seq=max_seq)[0]))
        got, _ = _run_batcher(cfgw, params_w,
                              [np.asarray(prompt["tokens"][0])], [4],
                              max_seq=max_seq)
        assert got == [gold], f"plen={plen}"


def test_windowed_prefill_compiles_log_bounded(model):
    """The pow2 bound holds for windowed configs too (the ROADMAP item):
    arbitrary lengths cost <= log2(max_seq) prefill compiles."""
    cfg, params = model
    cfgw = dataclasses.replace(cfg, sliding_window=16)
    max_seq = 64
    lengths = [3, 7, 9, 15, 17, 21, 30, 33, 40, 47]
    prompts = _prompts(cfgw, lengths)
    got, bat = _run_batcher(cfgw, params, prompts, [2] * len(lengths),
                            max_seq=max_seq)
    assert all(len(o) == 2 for o in got)
    assert bat.prefill_compiles <= int(math.log2(max_seq))


# --- decode_flash in the batcher step path --------------------------------------------


def test_decode_flash_batcher_equivalence_gqa_window_ring(model):
    """cfg.decode_flash routes the batcher's vmapped decode through the
    Pallas kernel (interpret mode on CPU) and must match the XLA step
    token-for-token across GQA, sliding-window (ring), and paged
    layouts."""
    cfg, params = model
    plens = [8, 5, 11]
    max_news = [4, 6, 3]
    for variant in ({}, {"sliding_window": 16}):
        base = dataclasses.replace(cfg, **variant)
        prompts = _prompts(base, plens)
        gold, _ = _run_batcher(base, params, prompts, max_news)
        flash, _ = _run_batcher(
            dataclasses.replace(base, decode_flash=True), params, prompts,
            max_news)
        assert flash == gold, f"dense decode_flash mismatch ({variant})"
        paged, bat = _run_batcher(
            dataclasses.replace(base, decode_flash=True, kv_page_size=8),
            params, prompts, max_news)
        assert bat.paged
        assert paged == gold, f"paged decode_flash mismatch ({variant})"


def test_paged_logits_step_matches_decode_step_and_kernel(model):
    """The logits step reads a batcher's prefilled state without
    advancing it: its argmax is the token the decode step then emits,
    and the Pallas paged kernel gives the gather path's logits."""
    cfg, params = model
    pcfg = dataclasses.replace(cfg, kv_page_size=8)
    bat = ContinuousBatcher(pcfg, params, n_slots=2, max_seq=48)
    for i, p in enumerate(_prompts(pcfg, [9, 14])):
        bat.submit(Request(rid=i, prompt=p, max_new=4))
    bat.admit()
    while bat._admitting:
        bat._prefill_step()
    state = (bat.params, bat.pools, bat.block_tab, bat.last_tok, bat.pos,
             bat.active)
    _, gath = make_paged_logits_step(pcfg, 8)(*state)
    _, kern = make_paged_logits_step(
        dataclasses.replace(pcfg, decode_flash=True), 8)(*state)
    assert gath.shape == (2, cfg.padded_vocab) and gath.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(kern), np.asarray(gath),
                               rtol=1e-5, atol=1e-5)
    bat.step()
    assert bat._host_last_tok == np.argmax(np.asarray(gath), -1).tolist()


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("flag", [None, True, False])
def test_paged_decode_read_chosen_by_platform(monkeypatch, backend, flag):
    """``decode_flash`` forces the read when set; unset, the kernel runs
    on a TPU and the gather elsewhere, and heads narrower than a lane
    row keep the gather."""
    cfg = dataclasses.replace(smoke_variant(configs.get("minitron-4b")),
                              head_dim=128, decode_flash=flag)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert paged_decode_kernel(cfg) == (
        flag if flag is not None else backend == "tpu")
    narrow = dataclasses.replace(cfg, head_dim=32)
    assert paged_decode_kernel(narrow) == bool(flag)


@pytest.mark.parametrize("flag", [None, True])
def test_decode_read_counts_live_and_table_pages(model, flag):
    """``stats()["decode_read"]`` names the decode program's read path
    and, while telemetry is on, sums over decode calls the table entries
    holding the live slots' positions against the table's width: both
    grow with every call, the live ones never past the table."""
    cfg, params = model
    pcfg = dataclasses.replace(cfg, kv_page_size=8, decode_flash=flag)
    bat = ContinuousBatcher(pcfg, params, n_slots=2, max_seq=64,
                            telemetry=ServeTelemetry(trace=False))
    for i, p in enumerate(_prompts(pcfg, [9, 14])):
        bat.submit(Request(rid=i, prompt=p, max_new=6))
    bat.admit()
    while bat._admitting:
        bat._prefill_step()
    read = bat.stats()["decode_read"]
    assert read == {"path": "kernel" if flag else "gather",
                    "live_pages": 0, "table_pages": 0}
    for _ in range(4):
        bat.step()
        now = bat.stats()["decode_read"]
        assert now["live_pages"] > read["live_pages"]
        assert now["table_pages"] - read["table_pages"] == 2 * 8
        assert now["live_pages"] <= now["table_pages"]
        read = now
    # the slots hold 9 and 14 prompt positions plus 4 decoded: pages of
    # 8 positions up to each decode position, summed over 4 calls.
    assert read["live_pages"] == sum(
        -(-(9 + t + 1) // 8) + -(-(14 + t + 1) // 8) for t in range(4))


def test_gqa_paged_matches_dense():
    """True GQA (hkv < hq) through the paged batcher."""
    cfg = dataclasses.replace(smoke_variant(configs.get("minitron-4b")),
                              n_kv_heads=2)
    params = registry.init(cfg, 0)
    prompts = _prompts(cfg, [6, 13])
    gold, _ = _run_batcher(cfg, params, prompts, [4, 4])
    got, bat = _run_batcher(dataclasses.replace(cfg, kv_page_size=8),
                            params, prompts, [4, 4])
    assert bat.paged and got == gold


# --- paged decode kernel vs reference -------------------------------------------------


def _paged_case(rng, b_pos, hq, hkv, d, page, n_blocks, n_pages, sq=1,
                ungrown=(), dtype=jnp.float32):
    """Random pools, q and block tables: each slot holds distinct pages
    for its positions pos..pos+sq-1; the entries past them are
    unallocated (>= n_pages).  A position of None is an idle slot: its
    whole row is unallocated (and its position 0).  Slots in
    ``ungrown`` leave the page of their last position unallocated, as
    a table not yet grown for the step leaves it."""
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, page, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, page, d)), dtype)
    q = jnp.asarray(rng.standard_normal((len(b_pos), hq, sq, d)), dtype)
    free = list(rng.permutation(n_pages))
    bt = np.full((len(b_pos), n_blocks), n_pages + 7, np.int32)
    for i, p in enumerate(b_pos):
        n = 0 if p is None else (p + sq - 1) // page + 1 - (i in ungrown)
        bt[i, :n] = [free.pop() for _ in range(n)]
    pos = [p or 0 for p in b_pos]
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(pos, jnp.int32)


def _kernel_tol(out, vp):
    """What the kernel may differ from the float32 reference by.  In
    float32, 1e-3.  With bfloat16 operands, as on the chip, the kernel
    rounds the probabilities to bfloat16 before the PV matmul and its
    output to bfloat16, each within half an ulp (2**-9 relative), and
    both errors are at most that share of the largest value (after
    scaling) it averages: 2**-8 of max |v|, besides float32 rounding."""
    if out.dtype == jnp.float32:
        return 1e-3
    return 2.0 ** -8 * float(jnp.abs(vp.astype(jnp.float32)).max()) + 1e-5


# (hq, hkv, d, page, n_blocks, n_pages, positions of the first query,
# slots left ungrown, dtype): GQA group 3 with contexts ending mid-page,
# at a page's end and at 0, on 2 KV heads and on 8; an idle slot (no
# page allocated) between live ones; a slot at a page boundary whose new
# page is not allocated yet (the table not grown); a full table beside a
# short one; MQA group 48; a 20-entry table that the pages per block
# (8 pages of 16 KiB) do not divide; and slots walking one and two
# blocks of 8 pages of 64 positions, with idle slots first, between and
# last (the prefetch chain skips them), in float32 and in bfloat16, the
# chip's operand type.
_KERNEL_CASES = {
    "gqa3": (6, 2, 32, 16, 4, 10, [35, 15, 0], (), jnp.float32),
    "gqa3_8_heads": (24, 8, 32, 16, 4, 10, [35, 15, 0], (), jnp.float32),
    "idle_slot": (6, 2, 32, 16, 4, 10, [35, None, 17], (), jnp.float32),
    "ungrown_page": (6, 2, 32, 16, 4, 10, [32, 15], (0,), jnp.float32),
    "full_table": (6, 2, 32, 16, 4, 10, [63, 20], (), jnp.float32),
    "mqa48": (48, 1, 32, 16, 4, 10, [35, 0], (), jnp.float32),
    "blocks_not_dividing": (4, 2, 128, 16, 20, 48, [300, 17, 0], (),
                            jnp.float32),
    "gqa3_blocks": (6, 2, 32, 64, 24, 40, [None, 830, 700, None, 100, 5,
                                           None], (), jnp.float32),
    "mqa48_blocks": (48, 1, 64, 64, 24, 40, [830, 700, 100, 5], (),
                     jnp.float32),
    "gqa3_blocks_bf16": (6, 2, 64, 64, 24, 40, [830, 700, None, 100], (),
                         jnp.bfloat16),
    "mqa48_blocks_bf16": (48, 1, 128, 64, 24, 40, [None, 830, 700, 100, 5],
                          (), jnp.bfloat16),
}


# Pools stacked over layers, as the layer loop carries them, read at a
# layer with other layers' pools on both sides: (case above, stack,
# layer), one stack axis and two (gemma3's (group, layer)).
_STACKED_CASES = {
    "gqa3_layer_1": ("gqa3", (3,), (1,)),
    "mqa48_blocks_bf16_layer_1_2": ("mqa48_blocks_bf16", (2, 4), (1, 2)),
}


def _stacked(pool, stack, layer, rng):
    """``pool`` at ``layer`` of a ``stack`` of random pools."""
    full = jnp.asarray(rng.standard_normal(stack + pool.shape), pool.dtype)
    return full.at[layer].set(pool)


@pytest.mark.parametrize("sq", [1, 3])
@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("case", sorted(_KERNEL_CASES)
                         + sorted(_STACKED_CASES))
def test_paged_flash_kernel_matches_ref(case, window, sq):
    base, stack, layer = _STACKED_CASES.get(case, (case, (), ()))
    (hq, hkv, d, page, n_blocks, n_pages, pos, ungrown,
     dtype) = _KERNEL_CASES[base]
    ppb = pages_per_block(hkv * page * d * jnp.dtype(dtype).itemsize, page,
                          n_blocks)
    if base == "blocks_not_dividing":
        assert n_blocks % ppb and pos[0] // page >= ppb
    if base.endswith(("_blocks", "_blocks_bf16")):
        assert max(-(-(p + sq) // page) for p in pos if p is not None) > ppb
    last = n_blocks * page - sq                  # the span stays in the table
    live = [p is not None for p in pos]
    pos = [p if p is None else min(p, last) for p in pos]
    rng = np.random.default_rng(5)
    q, kp, vp, bt, pos = _paged_case(rng, pos, hq, hkv, d, page, n_blocks,
                                     n_pages, sq, ungrown, dtype)
    gold = ref.paged_attention_ref(q, kp, vp, bt, pos, window=window)
    kp, vp = (_stacked(p, stack, layer, rng) for p in (kp, vp))
    out = flash_attention_decode_paged(q, kp, vp, bt, pos, window=window,
                                       layer=layer)
    live = np.asarray(live)
    assert out.dtype == dtype
    err = jnp.abs(out.astype(jnp.float32) - gold)[live].max()
    assert float(err) <= _kernel_tol(out, vp)
    assert not np.asarray(out)[~live].any()     # an idle slot reads nothing


@pytest.mark.parametrize("sq", [1, 3])
@pytest.mark.parametrize("window", [16, 24])
def test_paged_flash_kernel_ring_page_base_matches_ref(window, sq):
    """Ring-of-pages window groups: the kernel's per-entry logical base
    (scalar-prefetched ``page_base``) must reproduce the reference's
    reconstructed-position masking, including negative (never-written)
    slots."""
    rng = np.random.default_rng(7)
    b, hq, hkv, d = 2, 4, 2, 32
    n_pages, page, nbl = 8, 8, 4
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, page, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, page, d)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
    bt = jnp.asarray([[3, 1, 7, 0], [2, 5, 99, 99]], jnp.int32)
    pos = jnp.asarray([43, 9], jnp.int32)
    # entry j holds logical page l = cur - ((cur - j) % nbl).
    cur = pos[:, None] // page
    jj = jnp.arange(nbl)[None, :]
    base = ((cur - ((cur - jj) % nbl)) * page).astype(jnp.int32)
    out = flash_attention_decode_paged(q, kp, vp, bt, pos, window=window,
                                       page_base=base)
    gold = ref.paged_attention_ref(q, kp, vp, bt, pos, window=window,
                                   page_base=base)
    assert float(jnp.abs(out - gold).max()) <= 1e-3


@pytest.mark.parametrize("layer", [(), (2,)], ids=["one", "stacked"])
@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 24])
def test_paged_flash_kernel_int8_scales_match_ref(window, qdtype, layer):
    """int8 pools with per-position scale pages: the kernel dequantizes
    in VMEM and must match the dense dequantize-then-attend oracle, with
    the pages entering the matmuls in the query's type (bfloat16 on
    the chip), also for pools and scales read at a layer of a stack."""
    rng = np.random.default_rng(9)
    b, hq, hkv, d = 2, 4, 2, 32
    n_pages, page, n_blocks = 6, 16, 3
    kp = jnp.asarray(rng.integers(-127, 128, (n_pages, hkv, page, d)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (n_pages, hkv, page, d)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, hkv, page, 1)),
                     jnp.bfloat16)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, hkv, page, 1)),
                     jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, hq, 1, d)), qdtype)
    bt = jnp.asarray([[0, 2, 4], [5, 1, 99]], jnp.int32)
    pos = jnp.asarray([40, 20], jnp.int32)
    gold = ref.paged_attention_ref(q, kp, vp, bt, pos, window=window,
                                   k_scale_pages=ks, v_scale_pages=vs)
    err_scale = vp.astype(jnp.float32) * vs
    if layer:                     # other layers' pages and scales around
        kp, vp, ks, vs = (jnp.stack([a[::-1]] * 3).at[layer].set(a)
                          for a in (kp, vp, ks, vs))
    out = flash_attention_decode_paged(q, kp, vp, bt, pos, window=window,
                                       k_scale_pages=ks, v_scale_pages=vs,
                                       layer=layer)
    err = float(jnp.abs(out.astype(jnp.float32) - gold).max())
    assert err <= _kernel_tol(out, err_scale)


@pytest.mark.parametrize("window", [None, 24])
def test_ops_paged_decode_dispatch(window):
    """The public ops wrapper: the Pallas branch and the XLA reference
    branch must agree (guards the wrapper against signature drift)."""
    from repro.kernels.ops import paged_decode_attention
    rng = np.random.default_rng(11)
    b, hq, hkv, d = 2, 4, 2, 32
    n_pages, page = 6, 16
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, page, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, page, d)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, hq, 1, d)), jnp.float32)
    bt = jnp.asarray([[0, 2, 4], [5, 1, 99]], jnp.int32)
    pos = jnp.asarray([40, 20], jnp.int32)
    fast = paged_decode_attention(q, kp, vp, bt, pos, window=window,
                                  use_pallas=True)
    gold = paged_decode_attention(q, kp, vp, bt, pos, window=window,
                                  use_pallas=False)
    assert float(jnp.abs(fast - gold).max()) <= 1e-3


def test_paged_pool_memory_smaller_than_dense(model):
    """The headline: at equal slot count, the paged pool for short
    requests is a fraction of the dense n_slots x max_seq reservation."""
    cfg, params = model
    n_slots, max_seq, page = 4, 64, 8
    dense = ContinuousBatcher(cfg, params, n_slots=n_slots, max_seq=max_seq)
    paged = ContinuousBatcher(
        dataclasses.replace(cfg, kv_page_size=page), params,
        n_slots=n_slots, max_seq=max_seq, n_pages=n_slots * 2)
    dense_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(dense.cache))
    paged_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(paged.pools))
    assert paged_bytes * 3 < dense_bytes


# --- the layer loop writes the stacked pools in place ---------------------------------


def _per_layer_scan(cfg, apply, blocks_p, x, cache, pos, mode, cache_len,
                    last_pos=None, block_tab=None, cache_offset=None):
    """The reference layer loop: each layer's pools sliced out of the
    stack, the layer applied to them alone (``layer=()``), and the
    result written back into the stack."""
    routes = []
    for l in range(jax.tree.leaves(blocks_p)[0].shape[0]):
        x, nc = apply(cfg, _at(blocks_p, l), x, _at(cache, l), pos, mode,
                      cache_len=cache_len, last_pos=last_pos,
                      block_tab=block_tab, cache_offset=cache_offset)
        if isinstance(nc, tuple):                 # a MoE layer's experts
            nc, r = nc
            routes.append(r)
        cache = jax.tree.map(lambda a, u, l=l: a.at[l].set(u), cache, nc)
    return x, (cache, jnp.stack(routes)) if routes else cache


def _at(tree, idx):
    return jax.tree.map(lambda a: a[idx], tree)


def _random_pools(cfg, page, n_pages, seed):
    """Every pool leaf filled with random values (int8 pages with random
    codes, scale pages positive), so a read of the wrong layer or of a
    stale entry shows."""
    rng = np.random.default_rng(seed)
    decls = registry.paged_cache_decls(cfg, n_pages, page)
    from repro.models.params import abstract_params

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        v = rng.standard_normal(a.shape)
        return jnp.asarray(np.abs(v) * 0.01 if a.shape[-1] == 1 else v,
                           a.dtype)
    return jax.tree.map(fill, abstract_params(decls))


def _tables(layout, rows, max_seq, n_pages, rng):
    """Block tables per page group: each row's live entries hold distinct
    pages, the rest are unallocated (n_pages); a row of None is idle."""
    out = {}
    for g in layout.groups:
        nb = layout.n_blocks(g.name, max_seq)
        free = list(rng.permutation(n_pages))
        bt = np.full((len(rows), nb), n_pages, np.int32)
        for i, last in enumerate(rows):
            if last is not None:
                n = min(last // layout.page + 1, nb)
                bt[i, :n] = [free.pop() for _ in range(n)]
        out[g.name] = jnp.asarray(bt)
    return out


# (architecture, ModelConfig changes, mode of the first call): flat bf16
# pools, read by the gather and by the block-table kernel (interpreted);
# int8 pools with scale pages; gemma3's ring local and flat global
# groups indexed (group, layer); MLA's latent pages in two stacks; a
# speculative verify span; a chunk whose first positions sit below the
# prefix cache's read-only offset.  Each case also runs a prefill chunk.
_IN_PLACE_CASES = {
    "flat_bf16": ("minitron-4b", {"n_layers": 3}, "decode"),
    "flat_kernel": ("minitron-4b", {"n_layers": 3, "decode_flash": True},
                    "decode"),
    "int8_scales": ("minitron-4b", {"n_layers": 3,
                                    "kv_cache_dtype": "int8"}, "decode"),
    "gemma3_ring": ("gemma3-12b", {}, "decode"),
    "mla_latent": ("deepseek-v2-lite-16b", {"n_layers": 4}, "decode"),
    "verify_span": ("minitron-4b", {"n_layers": 3}, "verify"),
    "chunk_cache_offset": ("minitron-4b", {"n_layers": 3}, "decode"),
}


def _layer_stack(cfg, params, pools, bt):
    """One attention layer's function, the pool stack it writes, the
    index of a layer past the first and that layer's parameters."""
    from repro.models import layers as L
    blocks = params["blocks"]
    if cfg.mla:
        layer = (cfg.n_layers - cfg.first_dense_layers - 1,)
        fn = functools.partial(L.mla_apply_paged, cfg,
                               block_tab=bt["latent"])
        return fn, pools["latent"]["rest"], layer, _at(
            blocks["rest"]["attn"], layer)
    if cfg.local_global_pattern:
        layer = (1, cfg.local_global_pattern - 1)
        fn = functools.partial(L.attention_apply_paged, cfg,
                               window=cfg.sliding_window, ring=True,
                               block_tab=bt["local"])
        return fn, pools["local"], layer, _at(blocks["attn"], layer)
    layer = (1,)
    fn = functools.partial(L.attention_apply_paged, cfg,
                           window=cfg.sliding_window, block_tab=bt["kv"])
    return fn, pools["kv"], layer, _at(blocks["attn"], layer)


@pytest.mark.parametrize("case", sorted(_IN_PLACE_CASES))
def test_layer_loop_updates_pools_in_place(case, monkeypatch):
    """The layer loop carries the stacked pools and each layer writes and
    reads its own pool inside the stack.  A decode (or verify) call and
    a chunk give the logits and pools of the per-layer loop bit for bit,
    and one layer's write changes only its own index of the stack."""
    from repro.models import transformer
    from repro.models.cache_layouts import get_layout
    arch, change, first = _IN_PLACE_CASES[case]
    page, max_seq, n_pages = 8, 64, 48
    cfg = dataclasses.replace(smoke_variant(configs.get(arch)),
                              kv_page_size=page, **change)
    params = registry.init(cfg, 0)
    layout = get_layout(cfg, page)
    rng = np.random.default_rng(3)
    pools = _random_pools(cfg, page, n_pages, 4)
    k = 3 if first == "verify" else 1
    # (rows' last positions, first positions, tokens per row) per call:
    # a batch of slots (one idle) then one slot's 8-token chunk holding
    # 6 prompt tokens.
    calls = [(first, [12 + k, None, 37 + k, 5 + k], [12, 0, 37, 5], k),
             ("chunk", [29], [22], 8)]
    for mode, rows, pos, s in calls:
        bt = _tables(layout, rows, max_seq, n_pages, rng)
        pos = jnp.asarray(pos, jnp.int32)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (len(rows), s)),
                           jnp.int32)
        kw = {}
        if mode == "chunk":
            kw["last_pos"] = jnp.asarray([5], jnp.int32)
            if case == "chunk_cache_offset":
                kw["cache_offset"] = pos + 3

        def run(pools, bt=bt, pos=pos, toks=toks, mode=mode, kw=kw):
            return registry.forward(cfg, params, {"tokens": toks},
                                    mode=mode, pos=pos,
                                    cache={"pages": pools, "block_tab": bt},
                                    **kw)

        logits, new = jax.jit(run)(pools)
        with monkeypatch.context() as m:
            m.setattr(transformer, "_scan_blocks", _per_layer_scan)
            ref_logits, ref_new = jax.jit(run)(pools)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        jax.tree.map(np.testing.assert_array_equal, new, ref_new)

        fn, stack, layer, p = _layer_stack(cfg, params, pools, bt)
        x = jnp.asarray(rng.standard_normal((len(rows), s, cfg.d_model)),
                        jnp.float32)
        lkw = {"verify": True} if mode == "verify" else {}
        if mode == "chunk":
            lkw = {"last_idx": kw["last_pos"],
                   "cache_offset": kw.get("cache_offset")}
        y, out = jax.jit(lambda st: fn(p, x, pages=st, pos=pos, layer=layer,
                                       **lkw))(stack)
        y1, own = jax.jit(lambda st: fn(p, x, pages=st, pos=pos,
                                        **lkw))(_at(stack, layer))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y1))
        jax.tree.map(lambda o, w: np.testing.assert_array_equal(
            np.asarray(o[layer]), np.asarray(w)), out, own)
        # every other layer's pool is untouched, and this one was written
        jax.tree.map(lambda o, a, w: np.testing.assert_array_equal(
            np.asarray(o.at[layer].set(a[layer])), np.asarray(a)),
            out, stack, own)
        assert any(not np.array_equal(np.asarray(o[layer]),
                                      np.asarray(a[layer]))
                   for o, a in zip(jax.tree.leaves(out),
                                   jax.tree.leaves(stack)))
        pools = new
