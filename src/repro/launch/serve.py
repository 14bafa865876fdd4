"""Batched serving driver: continuous batching over tpulib Streams.

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
        --smoke --requests 8 --slots 4 --prompt-len 8 --max-new 16
"""

import argparse
import time

import numpy as np

from ..configs import get as get_arch
from ..configs.base import smoke_variant
from ..core.dataflow import DataflowContext
from ..serve.batching import ContinuousBatcher, Request, drain
from ..serve.resilience import RequestFailed, ServeSupervisor
from ..serve.serve_loop import serving_params
from ..serve.telemetry import MetricsServer, ServeTelemetry
from . import compile_cache


def build_batcher(cfg, *, seed: int = 0, params=None, n_slots: int,
                  max_seq: int, n_pages=None, schedule=None, overload=None,
                  queue_depth=None, faults=None, telemetry=None
                  ) -> ContinuousBatcher:
    """The serving batcher as ``main`` builds it: random weights from
    ``seed`` held in ``cfg.dtype`` on the devices that use them
    (``serve_loop.serving_params``; pass ``params`` to reuse weights
    already built), then a ``ContinuousBatcher`` over them."""
    if params is None:
        params = serving_params(cfg, seed)
    return ContinuousBatcher(cfg, params, n_slots=n_slots, max_seq=max_seq,
                             n_pages=n_pages, schedule=schedule,
                             overload=overload, queue_depth=queue_depth,
                             faults=faults, telemetry=telemetry)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size; > 0 enables the paged batcher "
                         "(page pools + chunked prefill)")
    ap.add_argument("--pages", type=int, default=0,
                    help="page pool size (default: dense-equivalent)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical prompt prefixes via the "
                         "refcounted page pool (paged mode only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="first N prompt tokens identical across all "
                         "requests (system-prompt workload; demos "
                         "--prefix-cache hits)")
    ap.add_argument("--prefill-exact", action="store_true",
                    help="recompute prompt K/V at the final chunk so "
                         "chunked prefill is bit-exact vs dense")
    ap.add_argument("--host-tier-bytes", type=int, default=0,
                    help="host-RAM tier (T1) byte budget: evicted "
                         "prefixes demote there and rehits restore "
                         "instead of recomputing (needs --prefix-cache)")
    ap.add_argument("--tier-snapshot", default="",
                    help="on-disk snapshot (T2) path: loaded at start "
                         "if present, saved at exit — cached prompts "
                         "survive restarts (needs --host-tier-bytes)")
    ap.add_argument("--tier-restore-min", type=int, default=-1,
                    help="recompute-vs-restore crossover in tokens "
                         "(default: cfg.tier_restore_min_tokens)")
    ap.add_argument("--schedule", choices=("fifo", "sla"), default=None,
                    help="admission order: arrival (fifo) or SLA class "
                         "rank + deadline (sla)")
    ap.add_argument("--overload", choices=("block", "reject"), default=None,
                    help="full-queue policy: backpressure the producer "
                         "(block) or shed with a typed rejection (reject)")
    ap.add_argument("--queue-depth", type=int, default=0,
                    help="request queue depth (0 = 2*slots)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault-injection spec, e.g. "
                         "'step:3;t1_d2h:1+' (see serve.resilience)")
    ap.add_argument("--supervise", action="store_true",
                    help="run under ServeSupervisor: watchdog heartbeat "
                         "+ journaled crash recovery on fatal step "
                         "faults (paged mode)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="speculative decode: n-gram drafter proposes up "
                         "to K tokens/slot, one batched verify call "
                         "scores them (paged mode only; output stays "
                         "bit-identical to plain greedy decode)")
    ap.add_argument("--speculate", action="store_true",
                    help="shorthand for --speculate-k 4")
    ap.add_argument("--speculate-probe", type=int, default=-1,
                    help="re-probe period for self-disabled drafter "
                         "slots in steps (0 = sticky disable; default: "
                         "config value)")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="per-request deadline in ms (0 = none); "
                         "expired requests are cancelled, pages freed")
    ap.add_argument("--klass", choices=("latency", "standard", "batch"),
                    default="standard",
                    help="SLA class stamped on every generated request")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve Prometheus text exposition on "
                         "http://127.0.0.1:PORT/metrics (0 = ephemeral "
                         "port, printed at startup; -1 = off)")
    ap.add_argument("--trace-out", default="",
                    help="write the request-lifecycle trace here at "
                         "exit: .json => Chrome chrome://tracing "
                         "format, anything else => JSONL events")
    ap.add_argument("--profile", action="store_true",
                    help="write every serve.* program span into a "
                         "running jax.profiler trace as an annotation "
                         "carrying its trace stamp, so device profiles "
                         "line up with the host trace spans")
    ap.add_argument("--mesh", default="",
                    help="device mesh shape, e.g. '2' (2-way tensor "
                         "parallel) or '1x2' (data x model); the last "
                         "axis is the model/TP axis — KV page pools "
                         "shard over heads/latent, decode runs under "
                         "shard_map (see docs/serving.md)")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if args.tier_snapshot and not args.host_tier_bytes:
        ap.error("--tier-snapshot needs the host tier: pass "
                 "--host-tier-bytes as well")
    if args.host_tier_bytes and not args.prefix_cache:
        ap.error("--host-tier-bytes needs --prefix-cache (demotion is "
                 "keyed by the prefix index)")
    speculate_k = args.speculate_k or (4 if args.speculate else 0)
    if (args.page_size or args.prefix_cache or args.prefill_exact
            or args.host_tier_bytes or speculate_k):
        import dataclasses
        page = args.page_size or cfg.kv_page_size
        if args.prefix_cache and not page:
            ap.error("--prefix-cache needs the paged batcher: pass "
                     "--page-size as well")
        if speculate_k and not page:
            ap.error("--speculate/--speculate-k needs the paged batcher "
                     "(rollback swaps block tables): pass --page-size")
        kw = dict(kv_page_size=page, prefix_cache=args.prefix_cache,
                  prefill_exact=args.prefill_exact,
                  kv_host_tier_bytes=args.host_tier_bytes,
                  kv_tier_snapshot=args.tier_snapshot,
                  speculate_k=speculate_k)
        if args.speculate_probe >= 0:
            kw["speculate_probe"] = args.speculate_probe
        if args.tier_restore_min >= 0:
            kw["tier_restore_min_tokens"] = args.tier_restore_min
        cfg = dataclasses.replace(cfg, **kw)
    if args.mesh:
        import dataclasses
        from ..distributed.sharding import validate_shardable
        try:
            shape = tuple(int(d) for d in args.mesh.lower().split("x"))
        except ValueError:
            ap.error(f"--mesh {args.mesh!r}: expected INTxINT... "
                     f"(e.g. '2' or '1x2')")
        if not cfg.kv_page_size:
            ap.error("--mesh needs the paged batcher (page pools shard "
                     "over heads/latent): pass --page-size as well")
        # Validate shardability at LAUNCH time — a config whose heads /
        # latent dim / ff dim does not divide the model axis must fail
        # here with the axis and knob named, not deep inside jit.
        try:
            validate_shardable(cfg, shape[-1])
        except ValueError as e:
            ap.error(str(e))
        cfg = dataclasses.replace(cfg, mesh_shape=shape)
    compile_cache.enable()
    rng = np.random.default_rng(args.seed)

    telemetry = None
    metrics_server = None
    if args.metrics_port >= 0 or args.trace_out or args.profile:
        telemetry = ServeTelemetry(trace=bool(args.trace_out),
                                   profile=args.profile)
    batcher = build_batcher(cfg, seed=args.seed, n_slots=args.slots,
                            max_seq=args.max_seq,
                            n_pages=args.pages or None,
                            schedule=args.schedule,
                            overload=args.overload,
                            queue_depth=args.queue_depth or None,
                            faults=args.faults or None,
                            telemetry=telemetry)
    if args.metrics_port >= 0:
        metrics_server = MetricsServer(telemetry,
                                       port=args.metrics_port).start()
        print(f"metrics: {metrics_server.url}")
    supervisor = ServeSupervisor(batcher) if args.supervise else None
    if batcher.mesh is not None:
        m = batcher.stats()["mesh"]
        print(f"mesh: {'x'.join(map(str, m['shape']))} over axes "
              f"({','.join(m['axes'])}), tp={m['tp']}, kv pool "
              f"{m['pool_bytes_per_shard']}B/shard of "
              f"{m['pool_bytes_total']}B total")
    sysp = rng.integers(0, cfg.vocab_size,
                        min(args.shared_prefix,
                            args.prompt_len)).astype(np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate([sysp, rng.integers(
                        0, cfg.vocab_size,
                        args.prompt_len - len(sysp)).astype(np.int32)]),
                    max_new=args.max_new, klass=args.klass,
                    deadline_ms=args.deadline_ms or None)
            for i in range(args.requests)]

    t0 = time.time()
    # The paper's Read/Compute/Write dataflow: producer PE feeds the
    # request stream, the batcher PE decodes, consumers drain outputs.
    run = supervisor.run if supervisor is not None else batcher.run
    with DataflowContext() as df:
        def producer():
            for r in reqs:
                batcher.submit(r)
        df.function(producer, name="producer")
        df.function(run, len(reqs), name="batcher")
    dt = time.time() - t0

    total_tokens = 0
    failed = 0
    for r in reqs:
        try:
            out = drain(r)
        except RequestFailed as e:
            failed += 1
            print(f"req {r.rid}: {type(e).__name__}: {e.reason} "
                  f"({len(e.tokens)} token(s) streamed)")
            continue
        total_tokens += len(out)
        print(f"req {r.rid}: {out[:12]}{'...' if len(out) > 12 else ''}")
    if batcher.paged:
        st = batcher.stats()
        pool = ",".join(f"{k}:{v}" for k, v in sorted(batcher.n_pages.items()))
        mode = (f"paged(page={batcher.page_size},pool={pool},"
                f"chunks={batcher.prefill_chunks},"
                f"preempt={batcher.preemptions})")
        if batcher.prefix_cache:
            print(f"prefix-cache: hit-rate "
                  f"{st['prefix_hit_rate']:.2f} "
                  f"({st['prefix_hits']}/{st['prefix_lookups']} lookups, "
                  f"{st['prefix_hit_tokens']} tokens skipped), "
                  f"shared pages {st['shared_pages']}, "
                  f"cow copies {st['cow_copies']}, "
                  f"evicted prefixes {st['prefix_evictions']}, "
                  f"cached {st['cached_prefixes']} prefixes / "
                  f"{st['cached_prefix_pages']} pages, "
                  f"pools {st['pools']}")
        else:
            print(f"pages: shared {st['shared_pages']}, "
                  f"cow copies {st['cow_copies']}, "
                  f"pools {st['pools']}")
        sp = st.get("speculation", {})
        if sp.get("k"):
            print(f"speculation: k={sp['k']}, drafted "
                  f"{sp['tokens_drafted']}, accepted "
                  f"{sp['tokens_accepted']} "
                  f"(rate {sp['acceptance_rate']:.2f}), "
                  f"rolled back {sp['tokens_rolled_back']}, "
                  f"verify rounds {sp['verify_rounds']}, "
                  f"decode steps saved {sp['decode_steps_saved']}")
        if "tiers" in st:
            t = st["tiers"]
            print(f"kv tiers: T1 {t['t1_entries']} entries / "
                  f"{t['t1_bytes']}B of {t['t1_budget_bytes']}B "
                  f"({t['t1_evictions']} evicted), "
                  f"demotions {t['demotions']} "
                  f"(+{t['demote_skips']} cached), "
                  f"rehits {t['rehits']} ({t['rehit_tokens']} tokens "
                  f"restored), recomputes {t['recomputes']}, "
                  f"recompute-resumes {t['recompute_resumes']}, "
                  f"transfers {t['gathers']}G/"
                  f"{t['scatters']}S "
                  f"({t['d2h_bytes']}B down, {t['h2d_bytes']}B up)")
        if batcher._tiers is not None and batcher.tier_snapshot:
            n = batcher.save_tier_snapshot()
            print(f"kv tiers: snapshot saved to {n}")
    else:
        mode = "dense"
    st = batcher.stats()
    if (failed or st["rejections"] or st["expired"] or st["errored"]
            or st["cancelled"] or st.get("restarts")
            or st.get("tier_disabled") or args.supervise):
        rej = ",".join(f"{k}:{v}" for k, v in sorted(
            st["rejections"].items())) or "none"
        extra = ""
        if supervisor is not None:
            rep = supervisor.report
            extra = (f", supervisor restarts {rep.restarts} "
                     f"(recovered {rep.recovered_requests} requests, "
                     f"{rep.stalls} stalls)")
        print(f"resilience: rejections [{rej}], expired {st['expired']}, "
              f"errored {st['errored']}, cancelled {st['cancelled']}, "
              f"tier faults {st.get('tier_faults', 0)}"
              f"{' (tier DISABLED)' if st.get('tier_disabled') else ''}"
              f"{extra}")
    print(f"{len(reqs)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s, {batcher.steps} decode steps, "
          f"{mode}, "
          f"slot-util {total_tokens/max(batcher.steps,1)/args.slots:.2f})")
    if telemetry is not None:
        lat = telemetry.latency_summary()
        ttft, gap = lat["ttft"], lat["inter_token"]
        if ttft["count"]:
            print(f"latency: ttft p50 {ttft['p50']*1e3:.1f}ms / "
                  f"p99 {ttft['p99']*1e3:.1f}ms, inter-token p50 "
                  f"{gap['p50']*1e3:.1f}ms / p99 {gap['p99']*1e3:.1f}ms "
                  f"(bucket-derived, n={int(ttft['count'])})")
        if args.trace_out:
            if args.trace_out.endswith(".json"):
                n = telemetry.tracer.write_chrome(args.trace_out)
                kind = "chrome trace"
            else:
                n = telemetry.tracer.write_jsonl(args.trace_out)
                kind = "JSONL trace"
            print(f"trace: {n} events -> {args.trace_out} ({kind}; "
                  f"{telemetry.tracer.dropped} dropped)")
    if metrics_server is not None:
        metrics_server.stop()


if __name__ == "__main__":
    main()
