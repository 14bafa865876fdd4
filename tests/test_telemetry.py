"""Serving telemetry (serve.telemetry): histograms/quantiles, the
Prometheus pull surface, request-lifecycle tracing, and the batcher
integration.

The hlslib thesis applied to observability: introspection is part of
the library contract, not an external profiler.  The contracts under
test here:

* histogram bucket/quantile math is exact and numpy-compatible;
* the text exposition round-trips through its own validator and a live
  ``http.server`` scrape;
* a single served request exercising prefix hit, preemption + restore,
  AND speculative decode yields a JSONL trace from which TTFT,
  per-chunk prefill times, inter-token gaps, and speculation acceptance
  can be reconstructed EXACTLY (fake clock: every stamp deterministic);
* traces stitch across supervised crash recovery — the replayed
  request carries the same rid, and token events mirror exactly the
  tokens a consumer drains (replay-suppressed pushes emit nothing);
* instrumentation never perturbs decode: telemetry-on and telemetry-off
  batchers stream bit-identical tokens.
"""

import dataclasses
import json
import math
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro import configs
from repro.configs.base import smoke_variant
from repro.core.health import Heartbeat
from repro.models import registry
from repro.serve.batching import ContinuousBatcher, Request, drain
from repro.serve.resilience import ServeSupervisor
from repro.serve.telemetry import (ENGINE_RID, Histogram, MetricsRegistry,
                                   MetricsServer, ServeTelemetry, Tracer,
                                   parse_exposition, percentile,
                                   percentiles, validate_exposition)

PAGE = 8


@pytest.fixture(scope="module")
def model():
    cfg = smoke_variant(configs.get("minitron-4b"))
    return cfg, registry.init(cfg, 0)


def _motif_prompt(n):
    """Motif-cycled prompt (the spec-decode suite's idiom): tiny smoke
    models decode these into short cycles, so the n-gram drafter fires."""
    motif = np.asarray([7, 3, 11, 5], np.int32)
    return np.tile(motif, n // 4 + 1)[:n].astype(np.int32)


def _repeat_drafter(bat):
    """Drafter stub: proposes the slot's last token repeated, so verify
    rounds run whatever the random weights decode the prompt into."""
    def draft(slot):
        cap = min(bat.speculate_k, bat._host_remaining[slot] - 1)
        return [bat._history[slot][-1]] * max(cap, 0)
    return draft


def _tick_clock(start=100.0, dt=1e-3):
    """Deterministic auto-advancing clock: every read moves time forward
    by ``dt``, so spans always have nonzero width and every stamp is
    exactly reconstructible.  Starts away from the 0.0 unstamped-
    submitted_at sentinel."""
    t = [start]

    def clk():
        t[0] += dt
        return t[0]

    return clk


# --- percentile helpers (shared with benchmarks/run.py) --------------------------------


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 5, 100):
        xs = rng.exponential(1.0, n).tolist()
        for q in (0, 10, 50, 90, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12)
    assert percentiles([1, 2, 3, 4, 5], (50, 90)) == (3.0, pytest.approx(4.6))
    with pytest.raises(ValueError):
        percentile([], 50)


# --- histogram bucket/quantile math ----------------------------------------------------


def test_histogram_buckets_and_quantiles():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(5.0)
    assert h.counts == [1, 1, 1]           # (..1], (1..2], (2..4]
    # bucket-derived median: linear interpolation inside the crossing
    # bucket (the histogram_quantile convention).
    assert h.quantile(0.5) == pytest.approx(1.5)
    assert h.quantile(0.0) <= h.quantile(0.99)
    s = h.summary()
    assert s["count"] == 3 and set(s) >= {"p50", "p90", "p99", "sum"}
    # +Inf-bucket observations clamp to the last finite bound.
    h.observe(100.0)
    assert h.count == 4 and sum(h.counts) == 3
    assert h.quantile(0.999) == pytest.approx(4.0)


def test_histogram_empty_and_validation():
    h = Histogram("t", buckets=(1.0, 2.0))
    assert math.isnan(h.quantile(0.5))
    assert h.summary()["count"] == 0
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))  # not strictly ascending


def test_registry_kind_conflict_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("serve_x_total", "x")
    assert reg.counter("serve_x_total") is c          # get-or-create
    with pytest.raises(ValueError):
        reg.histogram("serve_x_total")                # kind conflict
    a = reg.counter("serve_y_total", labels={"reason": "a"})
    b = reg.counter("serve_y_total", labels={"reason": "b"})
    assert a is not b
    a.inc(2)
    b.inc(3)
    text = reg.render_prometheus()
    samples = validate_exposition(text)
    assert samples['serve_y_total{reason="a"}'] == 2
    assert samples['serve_y_total{reason="b"}'] == 3


# --- Prometheus exposition round-trip --------------------------------------------------


def test_exposition_round_trip_and_invariants():
    reg = MetricsRegistry()
    reg.counter("serve_a_total", "a").inc(7)
    reg.gauge("serve_depth", "queue").set(3.5)
    h = reg.histogram("serve_lat_seconds", "lat",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    samples = validate_exposition(text)
    assert parse_exposition(text) == samples
    assert samples["serve_a_total"] == 7
    assert samples["serve_depth"] == 3.5
    assert samples['serve_lat_seconds_bucket{le="+Inf"}'] == 4
    assert samples["serve_lat_seconds_count"] == 4
    assert samples["serve_lat_seconds_sum"] == pytest.approx(5.555)
    # the validator actually rejects broken expositions.
    with pytest.raises(ValueError):
        validate_exposition("no_type_decl 1\n")
    with pytest.raises(ValueError):
        validate_exposition("# TYPE h histogram\n"
                            'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
                            "h_count 3\n")            # non-cumulative


def test_metrics_server_scrape_and_404():
    reg = MetricsRegistry()
    reg.counter("serve_scrapeme_total").inc(11)
    srv = MetricsServer(reg, port=0).start()
    try:
        with urllib.request.urlopen(srv.url, timeout=10) as rsp:
            assert rsp.status == 200
            assert rsp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            samples = validate_exposition(rsp.read().decode())
        assert samples["serve_scrapeme_total"] == 11
        base = srv.url.rsplit("/", 1)[0]
        with urllib.request.urlopen(base + "/healthz", timeout=10) as rsp:
            assert rsp.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


# --- Tracer unit -----------------------------------------------------------------------


def test_tracer_cap_and_chrome_export():
    clk = _tick_clock()
    tr = Tracer(clock=clk, max_events=3)
    tr.event(0, "a")
    tr.span(1, "b", 1.0, 1.5, slot=0)
    tr.event(ENGINE_RID, "c")
    tr.event(0, "over")                    # over the cap: dropped
    tr.event(0, "over2")
    assert len(tr) == 3 and tr.dropped == 2
    jl = [json.loads(line) for line in tr.to_jsonl().splitlines()]
    assert [e["name"] for e in jl] == ["a", "b", "c"]
    ch = tr.to_chrome()["traceEvents"]
    # per-request tids (rid+1); engine events on tid 0; ts in us.
    assert [e["tid"] for e in ch] == [1, 2, 0]
    assert ch[1]["ph"] == "X" and ch[1]["dur"] == pytest.approx(0.5e6)
    assert ch[1]["ts"] == pytest.approx(1.0e6)
    assert ch[0]["ph"] == "i" and ch[0]["s"] == "t"
    assert all(e["args"]["rid"] == jl[i]["rid"] for i, e in enumerate(ch))
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


# --- the acceptance trace: prefix hit + preempt/restore + speculation ------------------


def _lifecycle_cfg(cfg):
    return dataclasses.replace(
        cfg, kv_page_size=PAGE, prefill_chunk=PAGE, prefix_cache=True,
        kv_host_tier_bytes=1 << 20, tier_restore_min_tokens=0,
        speculate_k=4, speculate_ngram=1)


def test_full_lifecycle_trace_reconstruction(model):
    """THE acceptance criterion: serve a request that prefix-hits,
    speculates, is preempted (staged spill) and restored — then rebuild
    TTFT, per-chunk prefill times, inter-token gaps, and speculation
    acceptance from the JSONL trace alone and cross-check every one
    against the histograms and the batcher's own counters, exactly."""
    cfg, params = model
    lcfg = _lifecycle_cfg(cfg)
    clk = _tick_clock()
    tel = ServeTelemetry(clock=clk)
    bat = ContinuousBatcher(lcfg, params, n_slots=2, max_seq=64,
                            queue_depth=8, clock=clk, telemetry=tel)
    bat._draft = _repeat_drafter(bat)
    assert tel.clock is bat._clock          # bind_clock adopted it

    # phase 1: warm the prefix index (rid 0, served alone).
    warm = Request(rid=0, prompt=_motif_prompt(16), max_new=4)
    bat.submit(warm)
    bat.run(1)
    toks0 = drain(warm)
    assert len(toks0) == 4

    # phase 2: rid 1 re-uses the motif prompt (prefix HIT), decodes far
    # enough to speculate, and is forcibly preempted mid-decode through
    # the staged spill path, then restored by the run loop.
    req = Request(rid=1, prompt=_motif_prompt(16), max_new=12)
    bat.submit(req)
    bat.admit()
    while bat._admitting:
        bat._prefill_step()                 # catch-up chunks + 1st token
    for _ in range(3):
        bat.step()                          # speculative decode rounds
    slot = next(i for i, r in enumerate(bat._slot_req)
                if r is not None and r.rid == 1)
    bat._preempt(slot)                      # staged spill (tier engine)
    bat.run(2)                              # restore + finish
    toks1 = drain(req)
    assert len(toks1) == 12
    assert bat.preemptions >= 1 and bat.resumes >= 1
    st = bat.stats()
    assert st["prefix_hits"] >= 1
    assert st["speculation"]["tokens_drafted"] > 0

    # --- reconstruct everything from the JSONL export, nothing else.
    evs = [json.loads(line) for line in tel.tracer.to_jsonl().splitlines()]
    r1 = [e for e in evs if e["rid"] == 1]
    names = [e["name"] for e in r1]
    for needed in ("submit", "admit", "prefill_chunk", "first_token",
                   "token", "spec_verify", "preempt", "spill", "restore",
                   "resume", "retire", "request"):
        assert needed in names, f"rid 1 trace missing {needed!r}"
    by = {}
    for e in r1:
        by.setdefault(e["name"], []).append(e)

    # lifecycle ordering: list order is the batcher's causal order.
    order = [names.index(n) for n in
             ("submit", "admit", "first_token", "preempt", "resume",
              "retire")]
    assert order == sorted(order)
    assert names.index("spill") < names.index("restore")
    # the spill precedes its preempt instant (span stamped at start).
    assert by["spill"][0]["ts"] < by["preempt"][0]["ts"]

    # prefix hit + CoW detail on the admit event; catch-up start > 0.
    admit = by["admit"][0]["args"]
    assert admit["prefix_hit_tokens"] >= PAGE
    # catch-up prefill starts inside the hit region (the final chunk is
    # recomputed to produce the next-token logits).
    assert 0 < admit["start"] <= admit["prefix_hit_tokens"]
    assert admit["queue_s"] > 0
    assert by["preempt"][0]["args"]["mode"] == "spill"
    assert by["resume"][0]["args"]["mode"] == "restore"

    # TTFT: first_token.ts - submit.ts, exactly (fake clock).
    ttft = by["first_token"][0]["ts"] - by["submit"][0]["ts"]
    assert ttft == by["first_token"][0]["args"]["ttft_s"]
    assert ttft > 0

    # per-chunk prefill times: the catch-up admission needs fewer chunks
    # than the 16-token prompt would cold (prefix pages skipped).
    chunks = by["prefill_chunk"]
    assert 1 <= len(chunks) <= admit["n_chunks"]
    assert all(c["dur"] > 0 for c in chunks)
    assert [c["args"]["chunk"] for c in chunks] == list(range(len(chunks)))
    assert chunks[-1]["args"]["final"] is True

    # inter-token gaps: every streamed token is an event; gaps positive
    # and monotone stamps.
    toks = by["token"]
    assert len(toks) == len(toks1)
    stamps = [e["ts"] for e in toks]
    assert stamps == sorted(stamps)
    gaps1 = [b - a for a, b in zip(stamps, stamps[1:])]

    # speculation acceptance per verify round.
    drafted = sum(e["args"]["drafted"] for e in by["spec_verify"])
    accepted = sum(e["args"]["accepted"] for e in by["spec_verify"])
    assert drafted > 0 and 0 <= accepted <= drafted

    # the whole-request span closes the lifecycle.
    span = by["request"][0]
    assert span["ph"] == "X" and span["args"]["outcome"] == "retired"
    assert span["ts"] == by["submit"][0]["ts"]
    assert span["ts"] + span["dur"] == by["retire"][0]["ts"]

    # --- cross-check trace reconstruction vs histograms vs counters.
    lat = st["latency"]
    # TTFT histogram holds BOTH requests; reconstruct rid 0's the same
    # way and the sums must match to the float.
    r0 = {}
    for e in evs:
        if e["rid"] == 0:
            r0.setdefault(e["name"], []).append(e)
    ttft0 = r0["first_token"][0]["ts"] - r0["submit"][0]["ts"]
    assert lat["ttft"]["count"] == 2
    assert tel.h_ttft.sum == ttft0 + ttft
    gap_stamps0 = [e["ts"] for e in r0["token"]]
    gaps0 = [b - a for a, b in zip(gap_stamps0, gap_stamps0[1:])]
    assert tel.h_gap.count == len(gaps0) + len(gaps1)
    assert tel.h_gap.sum == pytest.approx(sum(gaps0) + sum(gaps1),
                                          rel=1e-12)
    all_chunks = [e for e in evs if e["name"] == "prefill_chunk"]
    assert tel.h_chunk.count == len(all_chunks) == bat.prefill_chunks
    assert tel.h_chunk.sum == pytest.approx(
        sum(c["dur"] for c in all_chunks), rel=1e-12)
    assert tel.h_spill.count == bat.preemptions == 1
    assert tel.h_restore.count == bat.resumes == 1
    assert tel.h_spill.sum == by["spill"][0]["dur"]
    assert tel.h_restore.sum == by["restore"][0]["dur"]
    # speculation counters cover BOTH requests (the warm rid 0 drafts
    # too): the trace's spec_verify events sum to the batcher totals.
    all_spec = [e for e in evs if e["name"] == "spec_verify"]
    assert (sum(e["args"]["drafted"] for e in all_spec)
            == st["speculation"]["tokens_drafted"])
    assert (sum(e["args"]["accepted"] for e in all_spec)
            == st["speculation"]["tokens_accepted"])
    # decode/verify engine spans live on ENGINE_RID and fill their
    # histograms 1:1.
    eng = [e for e in evs if e["rid"] == ENGINE_RID]
    assert tel.h_step.count == sum(e["name"] == "decode_step" for e in eng)
    assert tel.h_verify.count == sum(e["name"] == "verify_round"
                                     for e in eng)
    assert tel.h_verify.count == st["speculation"]["verify_rounds"]

    # the Prometheus surface agrees with the batcher counters.
    samples = validate_exposition(tel.render_prometheus())
    assert samples["serve_requests_submitted_total"] == 2
    assert samples["serve_retired_total"] == 2
    assert samples["serve_preemptions_total"] == bat.preemptions
    assert samples["serve_resumes_total"] == bat.resumes
    assert samples["serve_prefix_hits_total"] == st["prefix_hits"]
    assert (samples["serve_spec_tokens_drafted_total"]
            == st["speculation"]["tokens_drafted"])
    assert samples["serve_ttft_seconds_count"] == 2

    # Chrome export mirrors the same events with per-request tids.
    ch = tel.tracer.to_chrome()["traceEvents"]
    assert len(ch) == len(evs)
    assert {e["tid"] for e in ch} == {0, 1, 2}
    # cached prefix pages stay resident (refcounted by the index); the
    # allocator free lists must still be consistent.
    for alloc in bat._alloc.values():
        alloc.check_consistency()


# --- trace continuity across supervised crash recovery ---------------------------------


def test_trace_stitches_across_crash_recovery(model):
    """faults="step:2" under ServeSupervisor: the trace must record the
    supervisor_fault + supervisor_restart engine events and a
    recover_journal event per replayed rid — and because replay
    suppresses already-delivered pushes, each rid's token events must
    equal EXACTLY the tokens its consumer drains (no duplicates from
    the replayed prefix)."""
    cfg, params = model
    pcfg = dataclasses.replace(cfg, kv_page_size=PAGE, prefill_chunk=PAGE)
    tel = ServeTelemetry()
    bat = ContinuousBatcher(pcfg, params, n_slots=2, max_seq=64,
                            queue_depth=64, faults="step:2",
                            telemetry=tel)
    sup = ServeSupervisor(bat, max_restarts=2)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, 12).astype(np.int32), max_new=6)
            for i in range(4)]
    for r in reqs:
        bat.submit(r)
    report = sup.run(len(reqs))
    assert report.restarts == 1
    outs = {r.rid: drain(r, timeout=10.0) for r in reqs}
    assert all(len(t) == 6 for t in outs.values())

    evs = tel.tracer.events()
    eng = [e for e in evs if e["rid"] == ENGINE_RID]
    faults = [e for e in eng if e["name"] == "supervisor_fault"]
    restarts = [e for e in eng if e["name"] == "supervisor_restart"]
    assert len(faults) == 1 and len(restarts) == 1
    assert "InjectedFault" in faults[0]["args"]["cause"]
    # recovered = mid-flight journal replays + not-yet-started requeues.
    journaled = [e for e in evs if e["name"] == "recover_journal"]
    requeued = [e for e in evs if e["name"] == "recover_requeue"]
    assert len(journaled) >= 1
    assert len(journaled) + len(requeued) == report.recovered_requests
    for e in journaled:
        rid = e["rid"]
        # the SAME rid has trace events on both sides of the fault:
        idx = evs.index(e)
        assert any(x["rid"] == rid for x in evs[:idx])
        assert any(x["rid"] == rid and x["name"] == "retire"
                   for x in evs[idx:])
    # token events mirror the drained streams exactly, per rid.
    for r in reqs:
        n_tok = sum(1 for e in evs
                    if e["rid"] == r.rid and e["name"] == "token")
        assert n_tok == len(outs[r.rid]), f"rid {r.rid} double-traced"
    # one terminal request-span per rid, all retired.
    spans = [e for e in evs if e["name"] == "request"]
    assert sorted(e["rid"] for e in spans) == [0, 1, 2, 3]
    assert all(e["args"]["outcome"] == "retired" for e in spans)


# --- counter-name unification: aliases & registry agreement ----------------------------


def test_stats_alias_keys(model):
    cfg, params = model
    scfg = dataclasses.replace(
        cfg, kv_page_size=PAGE, prefill_chunk=PAGE,
        kv_host_tier_bytes=1 << 20, tier_restore_min_tokens=0,
        speculate_k=4, speculate_ngram=1)
    tel = ServeTelemetry()
    bat = ContinuousBatcher(scfg, params, n_slots=1, max_seq=48,
                            queue_depth=8, telemetry=tel)
    req = Request(rid=0, prompt=_motif_prompt(12), max_new=10)
    bat.submit(req)
    bat.admit()
    while bat._admitting:
        bat._prefill_step()
    bat.step()
    bat._preempt(0)                        # force one staged spill
    bat.run(1)
    assert len(drain(req)) == 10
    st = bat.stats()
    sp = st["speculation"]
    # the aliases kept for one release are gone; only canonical names.
    for alias in ("drafted", "accepted", "rolled_back", "verify_steps"):
        assert alias not in sp, alias
    assert sp["tokens_drafted"] == (sp["tokens_accepted"]
                                    + sp["tokens_rolled_back"])
    assert sp["verify_rounds"] >= 1
    tr = st["transfers"]
    assert "staged_gathers" not in tr and "staged_scatters" not in tr
    assert tr["gathers"] >= 1 and tr["scatters"] >= 1
    assert tr["gather_seconds"] >= 0 and tr["scatter_seconds"] >= 0
    # the registry's series agree with the stats() dicts.
    samples = validate_exposition(tel.render_prometheus())
    assert samples["serve_transfer_gathers_total"] == tr["gathers"]
    assert (samples["serve_spec_verify_rounds_total"]
            == sp["verify_rounds"])


# --- injectable clocks (satellite: kv_tiers engine + supervisor heartbeat) -------------


def test_transfer_engine_fake_clock_timing(model):
    """The staged engine's gather/scatter seconds come from the
    injected clock — under a tick clock the totals are exact."""
    cfg, params = model
    tcfg = dataclasses.replace(
        cfg, kv_page_size=PAGE, prefill_chunk=PAGE,
        kv_host_tier_bytes=1 << 20, tier_restore_min_tokens=0)
    dt = 1e-3
    bat = ContinuousBatcher(tcfg, params, n_slots=1, max_seq=48,
                            queue_depth=8, clock=_tick_clock(dt=dt))
    assert bat._xfer._clock is bat._clock
    req = Request(rid=0, prompt=_motif_prompt(12), max_new=8)
    bat.submit(req)
    bat.admit()
    while bat._admitting:
        bat._prefill_step()
    bat._preempt(0)
    bat.run(1)
    assert len(drain(req)) == 8
    tr = bat.stats()["transfers"]
    # each timed op brackets the work with two consecutive tick-clock
    # reads -> exactly one dt of "elapsed" time per op.
    assert tr["gather_seconds"] == pytest.approx(tr["gathers"] * dt,
                                                 rel=1e-6)
    assert tr["scatter_seconds"] == pytest.approx(tr["scatters"] * dt,
                                                  rel=1e-6)


def test_heartbeat_injectable_clock():
    fake = [0.0]
    hb = Heartbeat(["w0", "w1"], timeout=5.0, clock=lambda: fake[0])
    assert hb.dead() == []
    fake[0] = 4.0
    hb.beat("w1")
    fake[0] = 6.0
    assert hb.dead() == ["w0"]             # silent past the timeout
    assert hb.alive() == ["w1"]


# --- program spans: Tracer events and profiler annotations -----------------------------


def _host_annotations(log_dir):
    """(name, stats) of every ``serve.*`` host event in a recorded
    profile."""
    import glob
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for p in ProfileData.from_file(path).planes:
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith("serve."):
                    out.append((e.name, dict(e.stats)))
    return out


def _profiled(log_dir, fn):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_annotations(log_dir)


def test_span_tracer_event_and_annotation(tmp_path):
    """``span`` records the Tracer "X" event with its args and the counts
    set at its end; with ``profile`` on it also writes a profiler
    annotation of the same name carrying the Tracer's entry stamp."""
    tel = ServeTelemetry(clock=_tick_clock(), profile=True)

    def work():
        with tel.span(3, "serve.unit", step=7, n_live=2) as sp:
            sp.set(tokens=5)

    anns = _profiled(tmp_path / "on", work)
    (ev,) = tel.tracer.events()
    assert ev["name"] == "serve.unit" and ev["ph"] == "X"
    assert ev["rid"] == 3 and ev["dur"] > 0
    assert ev["args"] == {"n_live": 2, "tokens": 5}
    ((name, st),) = anns
    assert name == "serve.unit"
    assert st["t"] == pytest.approx(ev["ts"], abs=1e-9)
    assert st["n_live"] == 2 and st["tokens"] == 5 and st["step_num"] == 7

    # profile off: the Tracer event only; trace off too: nothing at all.
    quiet = ServeTelemetry(clock=_tick_clock())
    silent = ServeTelemetry(clock=_tick_clock(), trace=False)

    def off():
        with quiet.span(3, "serve.unit"):
            pass
        with silent.span(3, "serve.unit") as sp:
            sp.set(tokens=1)

    assert _profiled(tmp_path / "off", off) == []
    assert [e["name"] for e in quiet.tracer.events()] == ["serve.unit"]
    assert silent.tracer is None


def test_telemetry_off_writes_no_span(model, tmp_path):
    """A batcher without telemetry serves without writing a single
    program annotation into a running profile."""
    cfg, params = model
    pcfg = dataclasses.replace(cfg, kv_page_size=PAGE, prefill_chunk=PAGE,
                               prefix_cache=True)
    bat = ContinuousBatcher(pcfg, params, n_slots=2, max_seq=48,
                            queue_depth=8)
    req = Request(rid=0, prompt=_motif_prompt(11), max_new=4)

    def serve():
        bat.submit(req)
        bat.run(1)

    assert _profiled(tmp_path, serve) == []
    assert len(drain(req)) == 4


def test_profiling_stats_do_not_keep_the_batcher(model):
    """``stats()["programs"]`` (profiling) compiles the step programs'
    op map when read, and a kept snapshot does not hold the batcher, so
    its device state is freed with it."""
    import gc
    import weakref
    cfg, params = model
    pcfg = dataclasses.replace(cfg, kv_page_size=PAGE, prefill_chunk=PAGE)
    bat = ContinuousBatcher(pcfg, params, n_slots=2, max_seq=48,
                            queue_depth=8,
                            telemetry=ServeTelemetry(profile=True))
    req = Request(rid=0, prompt=_motif_prompt(11), max_new=4)
    bat.submit(req)
    bat.run(1)
    assert len(drain(req)) == 4
    before, after = bat.stats(), bat.stats()
    ops = after["programs"]["jit_step_fn"]
    assert any("/attn_read/" in p for p in ops.values())
    gone = weakref.ref(bat)
    del bat
    gc.collect()
    assert gone() is None
    assert dict(before["programs"]) == {}


def test_step_spans_end_after_readback(model):
    """The decode_step and final prefill_chunk spans (and the histograms
    they feed) end after their readback, and the tokens they produced are
    stamped at that end: they time the step as the host sees it, not
    its dispatch."""
    cfg, params = model
    pcfg = dataclasses.replace(cfg, kv_page_size=PAGE, prefill_chunk=PAGE)
    clk = _tick_clock()
    tel = ServeTelemetry(clock=clk)
    bat = ContinuousBatcher(pcfg, params, n_slots=2, max_seq=48,
                            queue_depth=8, clock=clk, telemetry=tel)
    reqs = [Request(rid=i, prompt=_motif_prompt(12 + i), max_new=5)
            for i in range(2)]
    for r in reqs:
        bat.submit(r)
    bat.run(len(reqs))
    assert [len(drain(r)) for r in reqs] == [5, 5]
    evs = tel.tracer.events()
    end = lambda e: e["ts"] + e["dur"]            # noqa: E731
    readback = None
    steps = chunks = 0
    for e in evs:
        if e["name"] == "serve.readback":
            readback = e
        elif e["name"] in ("decode_step", "prefill_chunk"):
            if e["name"] == "prefill_chunk" and not e["args"]["final"]:
                continue
            assert readback is not None
            assert e["ts"] < readback["ts"]
            assert end(readback) <= end(e) + 1e-9
            made = [t for t in evs if t["name"] in ("token", "first_token")
                    and t["ts"] == pytest.approx(end(e), abs=1e-9)]
            assert made, e
            steps += e["name"] == "decode_step"
            chunks += e["name"] == "prefill_chunk"
            readback = None
    assert steps == tel.h_step.count and chunks == 2
    assert tel.h_step.sum == pytest.approx(
        sum(e["dur"] for e in evs if e["name"] == "decode_step"))


# --- zero-perturbation: telemetry must not change decode -------------------------------


def test_telemetry_off_and_on_bit_identical(model):
    cfg, params = model
    pcfg = dataclasses.replace(cfg, kv_page_size=PAGE, prefill_chunk=PAGE,
                               prefix_cache=True, speculate_k=4,
                               speculate_ngram=1)
    rng = np.random.default_rng(7)
    prompts = [_motif_prompt(11),
               rng.integers(0, cfg.vocab_size, 9).astype(np.int32)]

    def serve(telemetry):
        bat = ContinuousBatcher(pcfg, params, n_slots=2, max_seq=48,
                                queue_depth=8, telemetry=telemetry)
        reqs = [Request(rid=i, prompt=p.copy(), max_new=8)
                for i, p in enumerate(prompts)]
        prod = threading.Thread(target=lambda: [bat.submit(r)
                                                for r in reqs])
        prod.start()
        bat.run(len(reqs))
        prod.join()
        return [drain(r) for r in reqs], bat

    off, bat_off = serve(None)
    tel = ServeTelemetry()
    on, bat_on = serve(tel)
    assert on == off
    assert bat_off._telemetry is None      # guard actually off
    # the off batcher's stats() has no latency block; on's does.
    assert "latency" not in bat_off.stats()
    assert bat_on.stats()["latency"]["ttft"]["count"] == 2
    assert len(tel.tracer.events()) > 0
