#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/``) and a
traffic mix (``traffic/``).  Set-up makes the weights on the device from
the seed, builds the serving batcher as ``repro.launch.serve`` does,
serves the mix's system prompts (so they sit in the prefix cache) and
warms up the cell's programs.  The window then drives
``ContinuousBatcher.run`` for ``--seconds``: a load thread submits each
request when it is due, and every token is timed where it reaches the
request's client stream.  Requests sent in the window are waited on to
the end.  Then the program is freed, a sample of finished requests is
checked against the float32 reference (``reference.py``), and the last
line of standard output is the JSON result.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces a slice of the window with the profiler and the program's own
spans, and reports the per-layer metrics (``metrics/``).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()          # set-up is timed from here

import argparse                        # noqa: E402
import gc                              # noqa: E402
import heapq                           # noqa: E402
import importlib                       # noqa: E402
import json                            # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402
import threading                       # noqa: E402
from pathlib import Path               # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np                     # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import cell as C            # noqa: E402
from repro.core.stream import Stream   # noqa: E402
from bench import traffic, weights     # noqa: E402
from bench.trace import WINDOW         # noqa: E402

# Every request carries a deadline this long past the window's close (a
# client's timeout): the batcher then cancels what still runs, so a run
# ends soon after its window.
GRACE_S = 3.0
SAMPLE = 3                # finished requests checked against the reference
TRACE_SLICE_S = 4.0       # seconds of the window the profiler records
TRACE_DIR = ROOT / ".bench_trace"
DECODE_PROGRAM = "step_fn"     # jitted names in serve/serve_loop.py
CHUNK_PROGRAM = "chunk_fn"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling, from
    its own monitoring events, with the function each was for."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def events(self) -> int:
        return len(self.names)

    def _on(self, event, duration, fun_name="?", **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.names.append(f"{event.rsplit('/', 1)[-1]}:{fun_name}")


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default method)."""
    return float(np.percentile(np.asarray(values, float), q))


# -- the client side ----------------------------------------------------------------


class Client(Stream):
    """The client's end of one request's output stream: every token is
    stamped as it arrives; a terminal event marks the request failed or
    expired; closing calls ``on_end``."""

    def __init__(self, on_end):
        super().__init__(depth=1 << 20, name="client")
        self.on_end = on_end
        self.times: List[float] = []
        self.tokens: List[int] = []
        self.failure: Optional[str] = None
        self.expired = False
        self.end: Optional[float] = None

    def Push(self, value, timeout=None):
        t = time.monotonic()
        if isinstance(value, (int, np.integer)):
            self.tokens.append(int(value))
            self.times.append(t)
        else:
            kind = getattr(value, "kind", "failed")
            self.expired = kind == "expired"
            self.failure = f"{kind}: {getattr(value, 'reason', value)}"

    def close(self):
        if self.end is None:
            self.end = time.monotonic()
            self.on_end(self)
        super().close()


class Record:
    def __init__(self, rid, session, turn, due, prompt, max_new):
        self.rid, self.session, self.turn = rid, session, turn
        self.due, self.prompt, self.max_new = due, prompt, max_new
        self.sent = 0.0
        self.client = None

    @property
    def done(self) -> bool:
        """Finished: every token it asked for arrived."""
        c = self.client
        return (c.end is not None and c.failure is None
                and len(c.tokens) == self.max_new)

    @property
    def failed(self) -> bool:
        """Neither finished nor cut off by its deadline (the window's
        close plus ``GRACE_S``): failed, rejected, ended short, or never
        ended.  A cut request is not failed: the window was over."""
        return not (self.done or self.client.expired)


class Load:
    """Sends the plan's requests when they are due.  Open loop: every
    turn at its fixed time.  Closed loop: a client's first request at
    once, each later one when the one before it has ended.  Nothing due
    at or after ``t_end`` is sent."""

    def __init__(self, plan, batcher, t0: float, t_end: float,
                 t_cut: float, first_rid: int):
        from repro.serve.batching import Request
        self.Request = Request
        self.plan, self.batcher = plan, batcher
        self.t_end, self.t_cut = t_end, t_cut
        self.rid = first_rid
        self.cv = threading.Condition()
        self.heap: list = []
        self.seq = 0
        self.records: List[Record] = []
        self.by_turn: Dict[tuple, Record] = {}
        self.before_prev_ended = 0    # later turns sent while the turn
        for i, s in enumerate(plan.sessions):   # before them still ran
            for k, t in enumerate(s.turns[:1] if plan.closed else s.turns):
                self._push(t0 + t.due_s, i, k)

    def _push(self, due, session, turn):
        heapq.heappush(self.heap, (due, self.seq, session, turn))
        self.seq += 1

    def _ended(self, rec: Record):
        with self.cv:
            if (self.plan.closed and rec.done
                    and rec.turn + 1 < len(self.plan.sessions[
                        rec.session].turns)):
                self._push(rec.client.end, rec.session, rec.turn + 1)
            self.cv.notify()

    def run(self):
        while True:
            with self.cv:
                while True:
                    now = time.monotonic()
                    if self.heap and self.heap[0][0] >= self.t_end:
                        self.heap.clear()
                    if self.heap and self.heap[0][0] <= now:
                        due, _, si, k = heapq.heappop(self.heap)
                        break
                    if now >= self.t_end:
                        return
                    wait = self.t_end - now
                    if self.heap:
                        wait = min(wait, self.heap[0][0] - now)
                    self.cv.wait(max(wait, 0.0))
            turn = self.plan.sessions[si].turns[k]
            rec = Record(self.rid, si, k, due, turn.prompt, turn.max_new)
            self.rid += 1
            rec.client = Client(lambda c, rec=rec: self._ended(rec))
            self.records.append(rec)
            self.by_turn[si, k] = rec
            prev = self.by_turn.get((si, k - 1))
            self.before_prev_ended += prev is not None and prev.client.end is None
            rec.sent = time.monotonic()
            self.batcher.submit(self.Request(
                rid=rec.rid, prompt=turn.prompt, max_new=turn.max_new,
                out=rec.client, deadline_ms=(self.t_cut - rec.sent) * 1e3))


def serve_now(batcher, prompts, max_new: int, first_rid: int) -> int:
    """Serve ``prompts`` to the end (set-up): submit from a thread, run
    the batcher here.  Returns the next free request id."""
    from repro.serve.batching import Request, drain
    reqs = [Request(rid=first_rid + i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    t = threading.Thread(target=lambda: [batcher.submit(r) for r in reqs])
    t.start()
    batcher.run(batcher.retired + len(reqs))
    t.join()
    for r in reqs:
        drain(r)
    return first_rid + len(reqs)


# -- one run ------------------------------------------------------------------------


class Context:
    """What a per-layer metric reader (``metrics/<name>.py``) reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def device_info(devices) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def check_tree(params, cfg) -> None:
    """The benchmark's weight tree must be the program's, leaf for leaf."""
    import jax
    from repro.models import registry
    want = jax.tree.map(lambda a: tuple(a.shape), registry.abstract(cfg))
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if want != got:
        raise SystemExit(f"bench: the weight tree differs from the "
                         f"program's parameters:\n  program {want}\n  "
                         f"bench   {got}")


def sample(records: List[Record], seed: int) -> List[Record]:
    """The longest finished request and up to SAMPLE - 1 others, drawn
    from the seed."""
    done = [r for r in records if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.max_new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.choice(len(rest), min(SAMPLE - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def end_to_end(records, t0, t_end, seconds) -> Dict[str, float]:
    """TTFT from each request's due time over every request of the window
    (one cut off before its first token counts the time to the cut);
    every gap between two tokens of one request that both arrived in the
    window; every token received in the window."""
    ttft = [(r.client.times[0] if r.client.tokens else r.client.end)
            - r.due for r in records if r.client.end is not None]
    inside = [t[(t >= t0) & (t < t_end)] for t in
              (np.asarray(r.client.times) for r in records)]
    gaps = np.concatenate([np.diff(t) for t in inside] + [np.zeros(0)])
    in_window = sum(len(t) for t in inside)
    out = {"output_tok_per_s": in_window / seconds}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
        out["ttft_p90_ms"] = percentile(ttft, 90) * 1e3
    if len(gaps):
        out["itl_p99_ms"] = percentile(gaps, 99) * 1e3
    return out


def per_layer(names: List[str], ctx: Context) -> Dict[str, float]:
    """Each metric read by ``metrics/<name up to its first dot>.py``."""
    out = {}
    for name in names:
        reader = importlib.import_module(
            f"bench.metrics.{name.split('.', 1)[0]}")
        v = reader.read(ctx)
        if v is not None:
            out[name] = float(v)
    return out


def verdict(gap: Optional[float], limit: float, n_failed: int):
    """The numbers compared, each with its limit, and ``correct``: no
    reading of the gap (no finished request, or the server never
    stopped) is not correct."""
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": n_failed, "limit": 0},
    }
    return checks, bool(gap is not None and gap <= limit and n_failed == 0)


def window_summary(records, t0, t_end, seconds, compiled, drain_s):
    """What the window held, beside the cell's metrics (for sweeps and
    the log): requests sent, finished and cut, the tails, and what
    compiled inside it."""
    out = {"sent": len(records), "done": sum(r.done for r in records),
           "cut": sum(r.client.expired for r in records),
           "compile_events": len(compiled), "drain_s": drain_s}
    out.update(end_to_end(records, t0, t_end, seconds))
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             config: Optional[Dict] = None, mix: Optional[Dict] = None,
             devices=None, control: bool = False) -> Dict[str, Any]:
    """One run of cell ``name``; returns the result object.  ``config``
    and ``mix`` replace the cell's files (tests run a small copy).
    ``control`` also reads the fp8 control's widest gap on the same
    sample (``result["control"]``); the benchmark's own runs do not."""
    import jax
    from repro.launch.serve import build_batcher
    from repro.serve.telemetry import ServeTelemetry

    bench = C.benchmark()
    w = C.workload(name, bench)
    conf = config or C.config(w["config"])
    mix = mix or C.traffic(w["traffic"])
    devices = devices or jax.devices()
    cfg = C.model_config(conf, w["config"])
    m = C.model_dims(conf)
    _, serving = C.serving(conf)
    n_slots, max_seq = int(serving["n_slots"]), int(serving["max_seq"])
    plan = traffic.plan(mix, m["vocab"], seed, seconds, max_seq)
    clock = CompileClock()

    params = weights.make(m, seed, cfg.dtype)
    check_tree(params, cfg)
    telemetry = ServeTelemetry(trace=True, profile=True) if trace else None
    batcher = build_batcher(cfg, params=params, telemetry=telemetry,
                            **dict({"queue_depth": 1 << 16}, **serving))

    # set-up: cache the system prompts one by one (each then holds the
    # whole batcher, so no decode step runs between its chunks), then
    # warm up a prefix hit, or a cold prompt, decoding across a page
    # boundary; then a prompt that leaves the cached warm-up prompt in
    # the middle of a page, so that the prefix cache copies that page at
    # admission (copy-on-write: a few small programs, which a window
    # would otherwise compile when a prompt's first tokens happen to
    # match a cached one's).
    rng = np.random.default_rng(int(seed) + 2)
    page = max(cfg.kv_page_size, 16)
    rid = 1 << 30
    for sp in plan.system_prompts:
        rid = serve_now(batcher, [sp], 2, rid)
    head = (plan.system_prompts[0] if plan.system_prompts
            else rng.integers(0, m["vocab"], page).astype(np.int32))
    warm = np.concatenate([head, rng.integers(0, m["vocab"], page + 3)])
    rid = serve_now(batcher, [warm.astype(np.int32)], page + 4, rid)
    if cfg.prefix_cache:
        fork = np.concatenate([warm[:len(head) + page // 2],
                               rng.integers(0, m["vocab"], page)])
        cow0 = batcher.stats().get("cow_copies")
        rid = serve_now(batcher, [fork.astype(np.int32)], 2, rid)
        if batcher.stats().get("cow_copies") == cow0:
            raise SystemExit("bench: the warm-up made no copy-on-write")
    jax.effects_barrier()
    gc.collect()
    compile_setup = clock.seconds
    mem = devices[0].memory_stats() or {}
    log(f"set-up: {time.monotonic() - T_PROCESS:.3f} s, compiling "
        f"{compile_setup:.3f} s; device memory in use "
        f"{mem.get('bytes_in_use')} of {mem.get('bytes_limit')} bytes, "
        f"peak {mem.get('peak_bytes_in_use')}")
    stats0 = batcher.stats()
    events0 = len(telemetry.tracer) if telemetry else 0

    t0 = time.monotonic()
    t_end = t0 + seconds
    setup_s = t0 - T_PROCESS
    compiles0 = clock.events
    t_cut = t_end + GRACE_S
    load = Load(plan, batcher, t0, t_end, t_cut, first_rid=0)
    server = threading.Thread(target=batcher.run, args=(1 << 60,),
                              daemon=True)
    sender = threading.Thread(target=load.run, daemon=True)
    server.start()
    sender.start()

    traced = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        slice_s = min(TRACE_SLICE_S, seconds / 2)
        time.sleep(max(0.0, t0 + (seconds - slice_s) / 2 - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # annotations, not every call
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        with jax.profiler.TraceAnnotation(WINDOW):
            ta = time.monotonic()
            time.sleep(slice_s)
            tb = time.monotonic()
        jax.profiler.stop_trace()
        traced = (ta, tb)
    sender.join(max(0.0, t_end - time.monotonic()) + 5.0)
    compiled_in_window = clock.names[compiles0:]

    # the batcher runs on until the deadlines have cut off what is left.
    records = load.records
    batcher.requests.close()
    server.join(max(0.0, t_cut - time.monotonic()) + 30.0)
    stopped = not server.is_alive()
    drain_s = max([r.client.end for r in records if r.client.end] + [t_end]
                  ) - t_end
    stats1 = batcher.stats()
    device = device_info(devices)

    late = [r.sent - r.due for r in records]
    ttft = end_to_end(records, t0, t_end, seconds)
    failed = [r for r in records if r.failed]
    log(f"window: {len(records)} requests sent in {seconds:g} s "
        f"({len(records) / seconds:.3f}/s), {len(failed)} failed; "
        f"{sum(len(r.client.tokens) for r in records)} tokens served; "
        f"{len(compiled_in_window)} compile events inside the window; the "
        f"last ended {drain_s:.3f} s after it")
    if compiled_in_window:
        log(f"window: compiled {sorted(set(compiled_in_window))}")
    if late:
        log(f"load: sent late by p50 {percentile(late, 50) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms")
    log(f"load: {sum(r.turn > 0 for r in records)} later turns of sessions, "
        f"{load.before_prev_ended} of them sent before the turn before "
        f"them had ended")

    e2e_names = [e["name"] for e in bench["end_to_end"]
                 if name in e.get("workloads", [name])]
    metrics: Dict[str, Dict[str, Any]] = {}
    units = {e["name"]: e["unit"] for e in bench["end_to_end"]}
    units.update({e["name"]: e["unit"] for e in bench["per_layer"]})
    ctx = None
    if trace:
        from bench import trace as TR
        reduced = TR.reduce(_load_trace()) if traced else None
        ctx = Context(
            m=m, n_slots=n_slots, chunk=batcher.chunk, records=records,
            t0=t0, t_end=t_end, traced=traced, reduced=reduced,
            events=telemetry.tracer.events()[events0:],
            stats0=stats0, stats1=stats1, compile_s=compile_setup,
            device_kind=devices[0].device_kind,
            programs=(DECODE_PROGRAM, CHUNK_PROGRAM))
        names = [e["name"] for e in bench["per_layer"]
                 if name in e.get("workloads", [name])]
        vals = per_layer(names, ctx)
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
    else:
        vals = dict(ttft, setup_s=setup_s)
        names = e2e_names
    for k in names:
        if k in vals:
            metrics[k] = {"value": vals[k], "unit": units[k]}

    # the check: free the program, then the reference over a sample.
    picked = sample(records, seed)
    seqs = [(r.prompt, np.asarray(r.client.tokens, np.int32))
            for r in picked]
    load.batcher = None          # the records' clients still reach ``load``
    del batcher, params, load, ctx, telemetry
    gc.collect()
    log(f"check: device memory in use before the reference "
        f"{(devices[0].memory_stats() or {}).get('bytes_in_use')} bytes")
    limit = float(conf["correct"]["max_logit_gap"])
    gap = gap_c = None          # no reading: the check fails
    if seqs and stopped:
        ref_params = weights.make(m, seed, cfg.dtype)
        gaps, gaps_c = C.family(m["family"]).logit_gaps(
            m, conf, ref_params, seqs, max_seq, control=control)
        gap = float(max(g.max() for g in gaps))
        gap_c = float(max(g.max() for g in gaps_c))
        del ref_params
    log(f"check: {sum(len(s) for _, s in seqs)} served tokens of "
        f"{len(seqs)} requests against the reference")
    checks, correct = verdict(gap, limit, len(failed))
    result = {"correct": correct, "attempted": len(records),
              "failed": len(failed), "metrics": metrics, "device": device,
              "window": window_summary(records, t0, t_end, seconds,
                                       compiled_in_window, drain_s)}
    if control:
        # the fp8 control's tokens in the program's place, judged alike
        result["control"] = dict(zip(("checks", "correct"), verdict(
            gap_c, limit, len(failed))))
    if trace and reduced is not None:
        result["breakdown"] = TR.breakdown(reduced)
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return result


def _load_trace():
    from jax.profiler import ProfileData
    from bench import trace as TR
    return ProfileData.from_file(TR.find_xplane(str(TRACE_DIR)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    w = C.workload(args.workload)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"bench: no accelerator: {e}")
        return 2
    if devices[0].platform != "tpu" or len(devices) < int(w["chips"]):
        log(f"bench: {args.workload} needs {w['chips']} TPU chip(s); JAX "
            f"sees {len(devices)} {devices[0].platform} device(s)")
        return 2
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices=devices[:int(w["chips"])])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
