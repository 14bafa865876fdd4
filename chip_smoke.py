#!/usr/bin/env python3
"""One smoke run of the serving path on TPU chips, at published widths.

    python chip_smoke.py              # one chip: minitron-4b
    python chip_smoke.py --chips 4    # four chips: gemma3-12b on a (1, 4) mesh

One chip.  minitron-4b at its published config in bfloat16 (paged KV,
page 16, prefix cache on, 4 slots, max_seq 2048) serves 8 requests — a
shared 256-token prefix plus 256-768 unique tokens each, 32 new tokens —
through the batcher ``repro.launch.serve`` builds, reading its KV
through the XLA gather (``decode_flash=False``).  Then the same config
with ``decode_flash=True`` compiles the Pallas paged decode kernel for
the chip, and one decode step's logits through it are compared with the
gather path's on the same prefilled state.

Four chips.  gemma3-12b at its published config (25.5 GB in bfloat16,
so it needs the mesh), built shard by shard on a (1, 4) mesh, serves 4
requests long enough to wrap its 1024-token window ring.  Its first
layer period (6 layers: 5 window + 1 global) is then cut out of the same
weights and run on one chip and on the mesh: the first decode step's
logits must agree.

The lines before the last say what was served; they come from one smoke
run and are not measurements.  The last line is the JSON result.  With
no TPU, or with any phase failing, the script exits non-zero and prints
no result.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAGE = 16
SLOTS = 4
MAX_SEQ = 2048
MAX_NEW = 32
REQUESTS, PREFIX, UNIQUE = 8, 256, (256, 768)   # one chip
PAST_WINDOW = (64, 512)          # four chips: prompt tokens beyond the window

# Logit agreement, as ||a - b|| / ||b|| over all slots and the padded
# vocabulary.  Weights, activations and the KV cache are bfloat16: the
# kernel and the gather path (or the sharded and the one-chip program)
# accumulate attention and partial sums in a different order and round
# each layer's output to bfloat16 (2^-8 relative) at different points,
# and those differences compound over the layers.  A wrong page, mask or
# shard moves the logits by O(1) of their norm.
LOGIT_RTOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def log(msg):
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def prompts_with_prefix(rng, vocab, n, prefix, lo, hi):
    """``n`` prompts: one shared ``prefix``-token head, then lo..hi unique
    tokens each."""
    head = rng.integers(0, vocab, prefix).astype(np.int32)
    return [np.concatenate([head, rng.integers(0, vocab, int(k))
                            .astype(np.int32)])
            for k in rng.integers(lo, hi + 1, n)]


def submit_all(batcher, prompts, max_new, first_rid=0):
    from repro.serve.batching import Request
    reqs = [Request(rid=first_rid + i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        batcher.submit(r)
    return reqs


def serve(batcher, prompts, max_new, inflight=()):
    """Submit every prompt from a producer thread, run the batcher until
    they and the ``inflight`` requests retire, and return every request's
    tokens (``inflight`` first)."""
    from repro.serve.batching import drain
    reqs = []
    producer = threading.Thread(target=lambda: reqs.extend(
        submit_all(batcher, prompts, max_new, first_rid=len(inflight))))
    producer.start()
    batcher.run(len(inflight) + len(prompts))
    producer.join()
    return [drain(r) for r in list(inflight) + reqs]


def check_served(batcher, outs, n, max_new, want_prefix_hits):
    st = batcher.stats()
    check(len(outs) == n and all(len(o) == max_new for o in outs),
          f"expected {n} x {max_new} tokens, got {[len(o) for o in outs]}")
    bad = {k: st[k] for k in ("expired", "errored", "cancelled") if st[k]}
    check(not st["rejections"] and not bad,
          f"requests failed: rejections {st['rejections']}, {bad}")
    if want_prefix_hits:
        check(st["prefix_hits"] > 0, "the shared prefix never hit the cache")
    return st


def prefilled_logits(batcher, cfgs, prompts):
    """Admit ``prompts`` (at most one per slot), run their whole chunked
    prefill, and return (the requests, the next decode step's logits for
    each config in ``cfgs`` over that one state).  Nothing is donated, so
    the batcher can go on serving the requests afterwards."""
    from repro.serve.serve_loop import make_paged_logits_step
    reqs = submit_all(batcher, prompts, MAX_NEW)
    batcher.admit()
    while batcher._admitting:
        batcher._prefill_step()
    state = (batcher.params, batcher.pools, batcher.block_tab,
             batcher.last_tok, batcher.pos, batcher.active)
    return reqs, [np.asarray(make_paged_logits_step(
        c, batcher.page_size)(*state)[1]) for c in cfgs]


def rel_err(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(name, got, ref):
    err = rel_err(got, ref)
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    log(f"{name}: logits rel. L2 error {err:.3e} (limit {LOGIT_RTOL:.0e}), "
        f"max abs {float(np.abs(got - ref).max()):.3e}, argmax agrees on "
        f"{agree}/{len(ref)} slots")
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{name}: non-finite logits")
    check(err <= LOGIT_RTOL, f"{name}: logits disagree ({err:.3e})")


def describe(cfg):
    return (f"{cfg.name} (d_model {cfg.d_model}, {cfg.n_layers} layers, "
            f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype})")


def param_bytes(params):
    import jax
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


def one_chip(cfg, seed, clock):
    """minitron-4b: serve through the batcher, then the Pallas paged
    decode kernel against the gather path."""
    from repro.launch.serve import build_batcher
    rng = np.random.default_rng(seed)
    prompts = prompts_with_prefix(rng, cfg.vocab_size, REQUESTS, PREFIX,
                                  *UNIQUE)

    t0, c0 = time.perf_counter(), clock.seconds
    gcfg = dataclasses.replace(cfg, decode_flash=False)
    batcher = build_batcher(gcfg, seed=seed, n_slots=SLOTS, max_seq=MAX_SEQ)
    params = batcher.params
    log(f"weights: {describe(cfg)}: {param_bytes(params) / 1e9:.2f} GB "
        f"built in {time.perf_counter() - t0:.1f} s")
    outs = serve(batcher, prompts, MAX_NEW)
    st = check_served(batcher, outs, len(prompts), MAX_NEW, True)
    log(f"serve: {len(outs)} requests x {MAX_NEW} tokens "
        f"(prompts {min(map(len, prompts))}-{max(map(len, prompts))} "
        f"tokens), prefix hits {st['prefix_hits']} "
        f"({st['prefix_hit_tokens']} tokens), {st['prefill_chunks']} "
        f"prefill chunks, {st['steps']} decode steps; wall "
        f"{time.perf_counter() - t0:.1f} s, compile "
        f"{clock.seconds - c0:.1f} s")
    del batcher
    gc.collect()

    t0, c0 = time.perf_counter(), clock.seconds
    fcfg = dataclasses.replace(cfg, decode_flash=True)
    fast = build_batcher(fcfg, params=params, n_slots=SLOTS, max_seq=MAX_SEQ)
    held, (kern, gath) = prefilled_logits(fast, (fcfg, gcfg),
                                          prompts[:SLOTS])
    compare("kernel: Pallas paged decode vs gather path", kern, gath)
    outs_f = serve(fast, prompts[SLOTS:], MAX_NEW, inflight=held)
    check_served(fast, outs_f, len(prompts), MAX_NEW, True)
    same = sum(a == b for a, b in zip(outs, outs_f))
    log(f"kernel: decode_flash batcher served {len(outs_f)} requests "
        f"x {MAX_NEW} tokens ({same}/{len(outs_f)} streams equal to the "
        f"gather batcher's); wall "
        f"{time.perf_counter() - t0:.1f} s, compile "
        f"{clock.seconds - c0:.1f} s")


def four_chips(cfg, seed, clock):
    """gemma3-12b: the whole model sharded over a (1, 4) mesh serves 4
    requests; one layer period cut from it agrees on one chip and on the
    mesh."""
    import jax
    from repro.launch.serve import build_batcher
    rng = np.random.default_rng(seed)
    window = cfg.sliding_window
    prompts = prompts_with_prefix(rng, cfg.vocab_size, SLOTS, 0,
                                  window + PAST_WINDOW[0],
                                  window + PAST_WINDOW[1])
    mcfg = dataclasses.replace(cfg, kv_page_size=PAGE, mesh_shape=(1, 4))

    t0, c0 = time.perf_counter(), clock.seconds
    batcher = build_batcher(mcfg, seed=seed, n_slots=SLOTS, max_seq=MAX_SEQ)
    params = batcher.params
    total = param_bytes(params)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    log(f"weights: {describe(cfg)}: {total / 1e9:.2f} GB built sharded "
        f"in {time.perf_counter() - t0:.1f} s; peak GB in use per device "
        f"{[p and round(p / 1e9, 2) for p in peaks]}")
    check(None in peaks or max(peaks) < total,
          "one device held the whole model")
    outs = serve(batcher, prompts, MAX_NEW)
    st = check_served(batcher, outs, len(prompts), MAX_NEW, False)
    m = st["mesh"]
    log(f"mesh serve: {len(outs)} requests x {MAX_NEW} tokens (prompts "
        f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens) on a "
        f"{'x'.join(map(str, m['shape']))} mesh, KV pool "
        f"{m['pool_bytes_per_shard']} of {m['pool_bytes_total']} bytes per "
        f"shard, pools {st['pools']}; wall {time.perf_counter() - t0:.1f} "
        f"s, compile {clock.seconds - c0:.1f} s")

    # the first layer period of the same weights, on the mesh and whole
    # on one chip.
    t0, c0 = time.perf_counter(), clock.seconds
    period = cfg.local_global_pattern + 1
    cut = dict(params, blocks=jax.tree.map(lambda a: a[:1],
                                           params["blocks"]))
    del batcher, params
    gc.collect()
    ccfg = dataclasses.replace(mcfg, n_layers=period)
    scfg = dataclasses.replace(ccfg, mesh_shape=())
    on_mesh = build_batcher(ccfg, params=cut, n_slots=SLOTS,
                            max_seq=MAX_SEQ)
    _, (sharded,) = prefilled_logits(on_mesh, (ccfg,), prompts)
    del on_mesh
    whole = jax.device_put(cut, jax.devices()[0])
    del cut
    gc.collect()
    single = build_batcher(scfg, params=whole, n_slots=SLOTS,
                           max_seq=MAX_SEQ)
    _, (one,) = prefilled_logits(single, (scfg,), prompts)
    compare(f"mesh vs one chip ({period} layers, published widths)",
            sharded, one)
    log(f"mesh vs one chip: wall {time.perf_counter() - t0:.1f} s, "
        f"compile {clock.seconds - c0:.1f} s")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: minitron-4b on one chip (default); 4: "
                         "gemma3-12b on a (1, 4) mesh and its comparison "
                         "with one chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU found: {e}")
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees {len(devices)} "
                 f"{devices[0].platform} device(s)); this runs on the chip "
                 f"only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU chips, JAX sees {len(devices)}")
    log(f"device: {len(devices)} x {devices[0].device_kind}; one smoke "
        f"run, not a measurement")

    from repro import configs
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            cfg = dataclasses.replace(configs.get("minitron-4b"),
                                      kv_page_size=PAGE, prefix_cache=True)
            one_chip(cfg, args.seed, clock)
        else:
            four_chips(configs.get("gemma3-12b"), args.seed, clock)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    log(f"total: wall {time.perf_counter() - t0:.1f} s, compile "
        f"{clock.seconds:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
