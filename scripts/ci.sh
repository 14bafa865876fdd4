#!/usr/bin/env bash
# Tier-1 verification + serve-path benchmarks in smoke mode.
# Usage: scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# The suite runs on the CPU backend: Pallas kernels are interpreted
# there (kernels/platform.py), multi-device tests simulate their meshes
# in subprocesses, and tests/test_tpu_compile.py compiles the main-path
# kernels for a described v5e chip.  On a TPU host the program is
# checked by `python chip_smoke.py` (one chip; `--chips 4` for four).
JAX_PLATFORMS=cpu python -m pytest -x -q

# Serving fast-path benches (smoke): writes benchmarks/BENCH_serve_smoke.json
# so every CI run leaves a machine-readable perf snapshot behind without
# clobbering the committed full-run BENCH_serve.json trajectory.  The serve
# set includes the paged-KV rows (paged_capacity, serve_longprompt_*,
# bursty_admission, paged-vs-dense for gemma3/int8) and the prefix-cache
# rows (prefix_hit_ttft, prefix_capacity) and the tiered-KV rows
# (host_tier_rehit, spill_resume_latency); benchmarks.run exits NONZERO —
# failing this script — if paged tokens-in-flight capacity ever regresses
# below dense, if lazy decode growth admits fewer concurrent slots than
# reserve-at-admission at equal pool size, if a prefix-cache-hit TTFT is
# not >= 5x faster than the cold admission, if sharing a system prompt
# does not admit strictly more slots than exclusive pages at equal pool,
# if restoring an evicted prefix from the host tier is not >= 2x faster
# than recomputing it, if the staged spill/restore engine is slower
# than the per-page baseline it replaced, if SLA scheduling does not
# beat FIFO on the latency-class SLO hit-rate at equal throughput
# (deadline_slo), if speculative decode (spec_decode_throughput)
# fails its floors — repetitive-workload speedup, adversarial-workload
# ratio (the self-disabling drafter must keep the overhead bounded),
# or bit-identity of the speculative token streams vs plain decode —
# or if mesh-sharded serving (serve_sharded_throughput) regresses: the
# tp=1 shard_map wrapper must stay within 0.95x of the unsharded
# batcher (paired-median ratio), and the 2-way mesh arm (in-process,
# run when 2 devices are visible) must reproduce the 1-device token
# streams exactly while halving per-shard KV pool bytes — or if
# telemetry stops being near-free (telemetry_overhead): decode
# throughput with lifecycle tracing + the metrics registry enabled must
# stay >= 0.97x (0.85x in smoke) of the bare batcher on paired medians,
# with the trace's token events matching the streamed tokens exactly.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    python -m benchmarks.run --smoke --serve

# Metrics-endpoint smoke (serve.telemetry): serve a couple of requests
# through a fully instrumented paged batcher, scrape the live
# /metrics HTTP endpoint the way Prometheus would, and validate the
# exposition — TYPE lines before samples, cumulative histogram buckets,
# +Inf bucket == _count — plus the presence of the core serving series.
JAX_PLATFORMS=cpu python - <<'PY'
import dataclasses, threading, urllib.request
import numpy as np
from repro import configs
from repro.configs.base import smoke_variant
from repro.models import registry
from repro.serve.batching import ContinuousBatcher, Request, drain
from repro.serve.telemetry import (MetricsServer, ServeTelemetry,
                                   validate_exposition)

cfg = smoke_variant(configs.get("minitron-4b"))
pcfg = dataclasses.replace(cfg, kv_page_size=8, prefill_chunk=8)
tel = ServeTelemetry()
bat = ContinuousBatcher(pcfg, registry.init(cfg, 0), n_slots=2,
                        max_seq=64, telemetry=tel)
rng = np.random.default_rng(3)
reqs = [Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, 10).astype(np.int32), max_new=8)
        for i in range(2)]
prod = threading.Thread(target=lambda: [bat.submit(r) for r in reqs])
prod.start()
bat.run(len(reqs))
prod.join()
assert all(len(drain(r)) == 8 for r in reqs)

srv = MetricsServer(tel, port=0).start()
try:
    with urllib.request.urlopen(srv.url, timeout=10) as rsp:
        assert rsp.status == 200, rsp.status
        ctype = rsp.headers["Content-Type"]
        assert ctype.startswith("text/plain; version=0.0.4"), ctype
        text = rsp.read().decode("utf-8")
finally:
    srv.stop()
validate_exposition(text)
for series in ("serve_ttft_seconds_bucket", "serve_decode_step_seconds",
               "serve_requests_submitted_total", "serve_steps_total",
               "serve_retired_total", "serve_pool_pages",
               "serve_queue_depth"):
    assert series in text, f"missing series: {series}"
print(f"metrics endpoint smoke OK ({len(text.splitlines())} lines)")
PY

# Chaos smoke (serve.resilience): the deterministic fault-injection
# matrix — failed tier transfers, corrupted/truncated snapshots,
# allocator exhaustion, crashes inside the jitted step — replayed under
# a FIXED seed so the @p probability draws are identical on every CI
# run.  Asserts the recovery contract: no consumer ever hangs in
# drain(), only the faulted request errors (original cause chained),
# allocator invariants hold after recovery, and every surviving output
# is bit-identical to the fault-free run.  (The tier-1 pytest above
# already ran this file once with the default seed; this stage pins the
# seeded draws explicitly so the chaos matrix is reproducible even if
# the default ever changes.)
REPRO_FAULT_SEED=0 JAX_PLATFORMS=cpu python -m pytest -x -q \
    tests/test_resilience.py
