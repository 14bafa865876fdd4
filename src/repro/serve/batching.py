"""Continuous batching built on tpulib Streams (F4) + dataflow (F3).

Requests arrive on a bounded ``Stream`` (the hlslib FIFO); the batcher PE
packs them into fixed slots, decodes all active slots together, and
retires finished sequences into per-request output streams, immediately
reusing the slot — continuous batching.  Producer/batcher/consumer is
exactly the paper's Read/Compute/Write dataflow and runs under
``DataflowContext`` in ``examples/serve_lm.py``.

Device-resident fast path
-------------------------
All per-slot decode state — ``last_tok``, ``pos``, ``remaining``, and the
active mask — lives in device arrays.  One *donated* jitted call advances
every slot per step, samples on device, and returns a single small
``(2, n_slots)`` int32 array (next token + finished flag per slot): the
ONLY per-step device->host transfer is 8 bytes/slot instead of a vocab
row.

Paged KV cache (``cfg.kv_page_size > 0``)
-----------------------------------------
Dense slot caches reserve ``n_slots x max_seq`` KV rows no matter how
short each request is.  In paged mode the KV cache is owned by a
pluggable ``CacheLayout`` (``models.cache_layouts``): per *page group*,
every attention layer owns a shared device page pool, a host-side
``PageAllocator`` (free list) hands pages to requests, and a per-slot
*block table* maps logical page j -> physical page.  Every attention
family pages now — flat bf16 {k, v} pools for dense/moe GQA, int8 pools
with per-position scale pages, gemma3's local/global split (two page
groups: window-bounded ring-of-pages for the local layers, flat growing
pages for the global ones), and MLA's compressed latent pages.  The
batcher only talks to the layout API, so there is no per-family
branching here; recurrent families (ssm/hybrid) have O(1)/slot state —
nothing to page — and keep the dense path.

Lazy decode growth + slot preemption
------------------------------------
Admission reserves only *prompt* pages; each decode step grows a slot's
block table on demand when its next write position crosses into an
unallocated logical page (window-bounded ring groups stop growing at
``ceil(window/page) + 1`` pages and reuse them in place).  When the pool
runs dry mid-decode, the batcher *preempts* the lowest-priority slot
(ties: most recently admitted): its pages are spilled host-side via the
layout, its pages freed, and the request parked.  Once pages free up it
resumes — possibly in a different slot — with the spilled pages restored
bit-identically, so output tokens are exactly those of an uncontended
run.  ``ContinuousBatcher(..., reserve_decode=True)`` (or
``cfg.kv_reserve_decode``) restores the old reserve-at-admission policy
for A/B benchmarking; the ``bursty_admission`` bench shows lazy growth
admitting strictly more concurrent slots at equal pool size.

When the pool cannot even cover a request's *prompt*, admission simply
*waits*: the request stays at the head of the FIFO (backpressure) until
a retire frees pages — it is never errored.  A request that could not
fit in an empty pool is rejected (its stream closes) instead of
livelocking.

Refcounted prefix cache (``cfg.prefix_cache``)
----------------------------------------------
Page ownership is *shared*, not exclusive: the ``PageAllocator`` is
refcounted and a radix-tree ``PrefixIndex`` (``serve.prefix_cache``)
maps blocks of prompt tokens to the physical pages that already hold
their K/V.  Retiring requests *decref* their prompt pages into the
index instead of freeing them; a later request whose prompt shares the
prefix attaches those pages (incref) and starts its chunked prefill at
the divergence point — a fully cached prompt's TTFT is one decode-sized
step.  Writes below the matched offset are suppressed in the kernels
(``cache_offset``), and the first write *past* a shared page — the
catch-up prefill crossing a mid-page divergence, or decode growing past
a fully matched prompt — copies the page first (copy-on-write via the
layout), so shared pages stay bit-stable for every sequence aliasing
them.  Cached prefixes linger until pool pressure LRU-evicts them;
eviction always runs before any live slot is preempted.  Preemption of
a slot holding shared pages spills only its private suffix — the
parked record keeps the refcounts and resume re-attaches the same
physical pages.  Sharing needs every page group of the ``CacheLayout``
to declare itself shareable: flat GQA, MLA latent, and int8+scale
groups are; gemma3's ring-of-pages local group is not (ring content
depends on wrap position), so gemma3 keeps exclusive pages.

Tiered KV memory (``cfg.kv_host_tier_bytes``)
---------------------------------------------
With the prefix cache enabled, a bounded host-RAM tier
(``serve.kv_tiers``) sits behind the page pool.  Prefix eviction
*demotes* the evicted node's pages to the host store (one staged,
batched device->host gather) instead of dropping their bytes; a later
prompt that misses the device index but hits the host store *promotes*
the matched block chain back — pages are allocated, payloads scattered
in one staged transfer, the blocks re-inserted into the ``PrefixIndex``
— and the admission then proceeds as an ordinary shared-page hit
(catch-up chunk only), bit-identical to the cold run.  Preemption
spill/resume routes through the same ``StagedTransferEngine`` (all
groups' gathers dispatched before the first blocking copy), and an
optional on-disk snapshot (``kv_tier_snapshot``) persists the host
store across batcher restarts so cached system prompts survive
redeploys.  ``tier_restore_min_tokens`` is the recompute-vs-restore
policy: spans shorter than the knob recompute from tokens (rehits fall
through to plain prefill; short preempted sequences park as
*recompute* records that re-admit and replay their generated tokens
through suppressed-output decode steps) — below the crossover, prefill
FLOPs are cheaper than staging pages through host RAM.

Speculative multi-token decode (``cfg.speculate_k``)
----------------------------------------------------
Paged mode can retire several tokens per jitted call without a second
model: a *self-speculative n-gram drafter* proposes up to ``speculate_k``
tokens per slot by suffix-matching the slot's own history (prompt +
generated tokens), and ONE batched ``(speculate_k + 1)``-length *verify*
call — the chunked-prefill forward path with per-row causal masking and
fused greedy argmax — scores every proposed position at once.  The
longest prefix of drafts agreeing with the model's own argmax commits
(always at least one token: a slot with no draft commits exactly 1, so
mixed spec/non-spec batches share the single compiled program); the
rejected tail rolls back by *block-table swap*: inside the verify jit,
every drafting slot's span pages are repointed at freshly allocated
private scratch pages (old contents copied in, both from padded index
arrays planned on the host), so speculative KV writes can never touch a
shared/refcounted page and the batcher's device table is never mutated
by speculation — commit scatters the scratch pages into the slot's page
list and the table, rollback just frees them (the table never saw
them).  Scratch lives entirely within one ``step()`` call, so
preemption, SLA expiry, and crash recovery never observe it.  Greedy
verification accepts exactly the tokens greedy decode would have
produced, so the output token stream is bit-identical to non-speculative
decode; a per-slot acceptance-rate EWMA stops drafting when it drops
below ``speculate_min_accept`` (adversarial workloads degrade to the
plain decode path instead of paying useless verify FLOPs).

Chunked prefill
---------------
Dense admission prefils a full ``n_slots``-row padded batch per pow2
bucket — one compiled shape per bucket (<= log2(max_seq) compiles), but
a single long admission blocks every in-flight slot for the whole
prompt, and a single short admission still pays n_slots rows.  Paged
mode instead admits prompts in fixed-size *chunks* (one compiled shape
per chunk size, total TWO serving programs: chunk + decode) interleaved
with decode steps inside ``run``: ``cfg.prefill_interleave`` decode
steps run between consecutive chunks, so a 4k-token prompt admitted
mid-stream costs active slots at most one chunk of latency per token
instead of one full prefill — bounded inter-token p99.
"""

from __future__ import annotations

import bisect
import collections
import collections.abc
import contextlib
import dataclasses
import functools
import os
import re
import time
import warnings
import weakref
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P_spec

from ..configs.base import ModelConfig
from ..core.stream import Stream, StreamClosed
from ..kernels.platform import paged_decode_kernel
from ..models import registry
from ..models import params as PP
from ..models.cache_layouts import get_layout
from .kv_tiers import KVTierManager, SnapshotCorruptError, StagedTransferEngine
from .prefix_cache import PageAllocator, PrefixIndex
from .resilience import (BatcherFault, FaultPlan, InjectedFault, StallFault,
                         TerminalEvent, class_rank)
from .telemetry import ENGINE_RID, ServeTelemetry
from .serve_loop import (make_chunk_prefill_step, make_paged_decode_step,
                         make_spec_verify_step, moe_load_init,
                         named_shardings, paged_sharding_specs,
                         serving_mesh_for)

_MIN_BUCKET = 8            # smallest prefill bucket (pad-to-power-of-two)
_MIN_CHUNK = 16            # smallest auto-selected prefill chunk
_NULLCTX = contextlib.nullcontext()      # a span site with telemetry off
# compiled HLO text: the module's name, and each op's metadata path
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_HLO_OP_NAME = re.compile(
    r"^\s*(?:ROOT )?%?([^\s=]+) = .*?metadata=\{op_name=\"([^\"]*)\"", re.M)


def _compiled_ops(fn, args) -> Tuple[str, Dict[str, str]]:
    """A jitted program's module name and ``{op: op_name}`` over the ops
    of its optimized HLO, where ``op_name`` is the op's metadata path
    with its ``jax.named_scope`` names (``.../attn_read/...``).

    JAX's persistent compile cache leaves metadata out of its key, so
    the executable a call loaded may carry the metadata of an older
    version of the program.  So the program is compiled once more with
    the metadata in the key, and with an option of its own that keeps
    the in-process cache from answering; XLA names the ops of one HLO
    the same way in every compile."""
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        text = fn.lower(*args).compile(
            compiler_options={"xla_embed_ir_in_executable": True}).as_text()
    finally:
        jax.config.update(key, was)
    return (_HLO_MODULE.search(text).group(1),
            dict(_HLO_OP_NAME.findall(text)))


class _ProgramOps(collections.abc.Mapping):
    """``stats()["programs"]`` while profiling
    (``ContinuousBatcher._program_ops``): compiled when first read, by a
    profile's reader after serving, not when ``stats()`` is taken.  It
    holds the batcher weakly, so a kept ``stats()`` snapshot does not
    keep the batcher's device state alive; once the batcher is gone it
    reads empty."""

    def __init__(self, batcher: "ContinuousBatcher"):
        self._batcher = weakref.ref(batcher)

    def _ops(self) -> Dict[str, Dict[str, str]]:
        batcher = self._batcher()
        return batcher._program_ops() if batcher is not None else {}

    def __getitem__(self, module: str) -> Dict[str, str]:
        return self._ops()[module]

    def __iter__(self):
        return iter(self._ops())

    def __len__(self) -> int:
        return len(self._ops())


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# --- jitted step factories (dense path) -----------------------------------------------


@functools.lru_cache(maxsize=32)
def _make_step_fn(cfg: ModelConfig, max_seq: int) -> Callable:
    """Donated jitted decode step over all slots (shared across batcher
    instances with the same model/max_seq — ``ModelConfig`` is frozen and
    hashable, so the compiled program is reused)."""
    i32 = jnp.int32

    def step_fn(params, cache, last_tok, pos, remaining, active):
        def decode_one(cache1, tok, p):
            logits, cache1 = registry.forward(
                cfg, params, {"tokens": tok[None, None]}, mode="decode",
                cache=cache1, pos=p)
            return jnp.argmax(logits[0, -1], -1).astype(i32), cache1

        nxt, cache = jax.vmap(decode_one)(cache, last_tok, pos)
        nxt = jnp.where(active, nxt, last_tok)
        pos = jnp.where(active, pos + 1, pos)
        remaining = jnp.where(active, remaining - 1, remaining)
        finished = active & ((remaining <= 0) | (pos >= max_seq - 1))
        active = active & ~finished
        out = jnp.stack([nxt, finished.astype(i32)])   # (2, n_slots)
        return cache, nxt, pos, remaining, active, out

    # donate cache + all state vectors: the step is a pure in-place
    # pipeline stage; nothing round-trips through the host.
    return jax.jit(step_fn, donate_argnums=(1, 2, 3, 4, 5))


@functools.lru_cache(maxsize=64)
def _make_admit_fn(cfg: ModelConfig, max_seq: int, n_slots: int,
                   bucket: int) -> Callable:
    """Jitted batched-prefill + scatter for one bucket length."""
    i32 = jnp.int32

    def admit_fn(params, cache, last_tok, pos, remaining, active,
                 prompts, lens, slot_idx, max_new):
        # One padded call for all rows: vmap of single-sequence prefill
        # gives every cache leaf a leading row axis that scatters
        # straight into the slot axis.
        def prefill_one(prompt, last_p):
            logits, c1 = registry.forward(
                cfg, params, {"tokens": prompt[None]}, mode="prefill",
                cache_len=max_seq, last_pos=last_p[None])
            return jnp.argmax(logits[0, -1], -1).astype(i32), c1

        tok0, cache1 = jax.vmap(prefill_one)(prompts, lens - 1)
        # rows for free capacity carry slot_idx == n_slots -> dropped.
        cache = jax.tree.map(
            lambda c, c1: c.at[slot_idx].set(c1, mode="drop"),
            cache, cache1)
        last_tok = last_tok.at[slot_idx].set(tok0, mode="drop")
        pos = pos.at[slot_idx].set(lens, mode="drop")
        remaining = remaining.at[slot_idx].set(max_new - 1, mode="drop")
        alive = (max_new > 1) & (lens < max_seq - 1)
        active = active.at[slot_idx].set(alive, mode="drop")
        return cache, last_tok, pos, remaining, active, tok0

    return jax.jit(admit_fn, donate_argnums=(1, 2, 3, 4, 5))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,) int32
    max_new: int
    priority: int = 0            # higher = preempted later
    # SLA lifecycle (serve.resilience): the class maps onto preemption
    # rank (latency > standard > batch) and — with schedule="sla" —
    # admission order; ``deadline_ms`` is wall time from submit() after
    # which the request is expired (queued) or cancelled (in flight).
    klass: str = "standard"      # "latency" | "standard" | "batch"
    deadline_ms: Optional[float] = None
    submitted_at: float = 0.0    # stamped by submit() / first pop
    out: Stream = dataclasses.field(
        default_factory=lambda: Stream(depth=4096, name="resp"))


@dataclasses.dataclass
class _Admission:
    """A request mid-chunked-prefill: owns a slot + pages, not yet decoding.

    ``start`` is the first prompt position the catch-up prefill actually
    computes (0 for a cold request; the divergence point for a
    prefix-cache hit); ``cache_offset`` is the read-only boundary below
    which the slot's pages are shared with the prefix cache and must not
    be rewritten (== the matched token count).

    ``resume`` marks a recompute-mode resume (tiered memory's
    recompute-from-prompt policy): the final chunk suppresses the
    first-token push (it was emitted before the preemption), restores
    the parked decode budget, and arms the suppressed-output decode
    replay that regenerates the already-emitted tokens' KV through the
    decode path — bit-identical to the uncontended run.
    """
    req: Request
    slot: int
    plen: int
    next_chunk: int
    n_chunks: int
    start: int = 0
    cache_offset: int = 0
    resume: Optional["_Preempted"] = None


@dataclasses.dataclass
class _Preempted:
    """A preempted decode: its KV pages parked host-side, slot released.

    ``pos``/``last_tok``/``remaining`` are the host mirrors of the slot's
    device state at preemption time; ``data``/``counts`` hold the spilled
    page payloads (per page group) and how many *private* pages each
    group owned.  ``shared`` lists the leading prefix-cache pages the
    slot still references: those are never spilled — their content is
    immutable while shared — and the parked record keeps the slot's
    refcount on them, so resume simply re-attaches the same physical
    pages.  Resume restores the private pages bit-identically into
    freshly allocated pages, so post-resume tokens exactly match an
    uncontended run.

    ``mode == "recompute"`` (tiered memory, sequences shorter than
    ``tier_restore_min_tokens``): nothing was spilled — the slot's
    prompt blocks went to the prefix index at preemption and resume
    re-admits the original prompt (prefix hits recover surviving
    blocks) then replays the ``pos - plen`` already-emitted decode
    steps with output pushes suppressed: greedy decode is
    deterministic, so the replay rebuilds the generated tokens' KV
    through the *decode* path — the cache bits, and hence every later
    token, exactly match the uncontended run.
    """
    req: Request
    pos: int
    last_tok: int
    remaining: int
    data: Dict[str, Any]
    counts: Dict[str, int]
    seq: int                     # admission order (preemption tie-break)
    shared: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    mode: str = "restore"        # "restore" (spilled pages) | "recompute"
    # replay pushes still owed suppression when the slot was preempted
    # MID-replay (tokens beyond ``pos`` already reached the consumer).
    skip: int = 0
    # the slot's token history (prompt + generated) parked for the
    # speculative drafter; recompute-mode records leave it empty (the
    # replay rebuilds it token by token).
    hist: List[int] = dataclasses.field(default_factory=list)


class ContinuousBatcher:
    """Fixed-slot continuous batcher with device-resident slot state.

    The host keeps only the slot -> ``Request`` mapping, the per-group
    page allocators, and the block tables' mirror; everything the decode
    loop reads or writes stays on device across steps.
    ``cfg.kv_page_size`` selects paged KV + chunked prefill (see module
    docstring); recurrent families (nothing to page) fall back to the
    dense path automatically.
    """

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int,
                 max_seq: int, n_pages=None,
                 page_size: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_interleave: Optional[int] = None,
                 reserve_decode: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_block: Optional[int] = None,
                 prefill_exact: Optional[bool] = None,
                 host_tier_bytes: Optional[int] = None,
                 tier_snapshot: Optional[str] = None,
                 tier_restore_min: Optional[int] = None,
                 schedule: Optional[str] = None,
                 overload: Optional[str] = None,
                 queue_depth: Optional[int] = None,
                 faults=None,
                 clock: Optional[Callable[[], float]] = None,
                 telemetry: Optional[ServeTelemetry] = None,
                 transfer_retries: int = 2,
                 tier_fault_limit: int = 3):
        if cfg.family in ("vlm", "audio"):
            raise NotImplementedError("batcher demo covers LM families")
        self.cfg, self.params = cfg, params
        self.n_slots = n_slots
        self.max_seq = max_seq
        # Resilience layer (serve.resilience): deterministic fault plan,
        # SLA scheduling knobs, explicit bounded-queue overload policy.
        self._fault: FaultPlan = FaultPlan.resolve(faults, cfg.fault_plan)
        self.schedule = str(schedule or cfg.serve_schedule)
        if self.schedule not in ("fifo", "sla"):
            raise ValueError(f"schedule must be fifo|sla, got "
                             f"{self.schedule!r}")
        self.overload = str(overload or cfg.serve_overload)
        if self.overload not in ("block", "reject"):
            raise ValueError(f"overload must be block|reject, got "
                             f"{self.overload!r}")
        qd = int(cfg.serve_queue_depth if queue_depth is None
                 else queue_depth)
        # One time base for scheduling AND telemetry: an explicit
        # ``clock`` wins; otherwise adopt the telemetry object's clock
        # (so a fake-clocked ServeTelemetry makes the whole batcher
        # deterministic); otherwise wall time.  The telemetry object is
        # then re-bound to whatever we chose — every trace stamp and
        # every deadline computation shares it.
        if clock is not None:
            self._clock = clock
        elif telemetry is not None:
            self._clock = telemetry.clock
        else:
            self._clock = time.monotonic
        self._telemetry = telemetry
        if telemetry is not None:
            telemetry.bind_clock(self._clock)
            telemetry.add_collector(self._sync_telemetry)
        self.requests: Stream = Stream(depth=qd or 2 * n_slots,
                                       name="requests")
        # lifecycle counters (stats()); ``rejections`` is keyed by the
        # typed rejection reason a consumer sees in its RequestRejected.
        self.rejections: Dict[str, int] = {}
        self.expired = 0
        self.errored = 0
        self.cancelled = 0
        self.tier_faults = 0
        self.tier_disabled = False
        self.restarts = 0
        self.snapshot_cold_start = False
        self.transfer_retries = int(transfer_retries)
        self.tier_fault_limit = int(tier_fault_limit)
        self._ewma_step_s = 0.0      # smoothed decode-step wall time
        self._ewma_step_tok = 0.0    # smoothed tokens RETIRED per step —
        # the load-shedding delay model divides by this, not by
        # n_slots: partially filled batches and speculative multi-token
        # commits both move real throughput away from 1 tok/slot/step.
        # speculative-decode counters (stats()["speculation"]).
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_rolled_back = 0
        self.spec_verify_steps = 0
        # supervisor wiring (ServeSupervisor sets these).
        self._heartbeat = None
        self._supervised = False
        self._stalled = False
        # any request in the system carrying a deadline? (keeps the
        # per-step expiry sweep off the hot path when nobody uses them)
        self._deadlines_live = False
        self.steps = 0
        self.retired = 0
        self.prefill_compiles = 0
        self.prefill_chunks = 0
        self.preemptions = 0
        self.resumes = 0
        self.peak_pages = 0
        # decode read (stats()["decode_read"], counted while telemetry is
        # on): table entries holding each live slot's positions, against
        # the entries its table has, summed over decode calls (not
        # verify rounds) and page groups.
        self.decode_live_pages = 0
        self.decode_table_pages = 0
        self._moe_load = None        # expert load, device-resident
        self.preempted_rids: List[int] = []    # observability (tests/benches)
        self._programs: Optional[Dict[str, Dict[str, str]]] = None
        # prefix-cache observability (stats(); all zero when disabled).
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.cow_copies = 0
        self.prefix_evictions = 0
        # tiered-memory observability (zero when the tier is disabled).
        self.recompute_resumes = 0

        # host mirror: which Request occupies each slot (None = free).
        self._slot_req: List[Optional[Request]] = [None] * n_slots
        # requests popped from the FIFO but not yet placed (admission
        # backpressure, and the idle-path re-queue in run()).
        self._pending: Deque[Request] = collections.deque()

        # device-resident slot state.
        i32 = jnp.int32
        self.last_tok = jnp.zeros((n_slots,), i32)
        self.pos = jnp.zeros((n_slots,), i32)
        self.remaining = jnp.zeros((n_slots,), i32)
        self.active = jnp.zeros((n_slots,), bool)

        psz = page_size or cfg.kv_page_size
        self.layout = get_layout(cfg, int(psz)) if psz else None
        self.paged = bool(psz) and self.layout is not None
        # mesh-sharded serving (cfg.mesh_shape, paged mode only): pools
        # and params are pinned to their PartitionSpec trees, block
        # tables and slot vectors replicated, so every jitted step (a
        # shard_map program — see serve_loop) starts from arguments
        # already laid out the way its in_specs demand.
        self.mesh = None
        self._pool_ns = None       # pools' NamedSharding tree
        self._rep_ns = None        # replicated NamedSharding
        if self.paged:
            self.page_size = int(psz)
            self.reserve_decode = bool(
                cfg.kv_reserve_decode if reserve_decode is None
                else reserve_decode)
            self.n_blocks = {g.name: self.layout.n_blocks(g.name, max_seq)
                             for g in self.layout.groups}
            # default pool = dense-equivalent capacity; benchmarks pass a
            # smaller pool to show the memory-proportionality win.  An
            # int applies to every growing group; window-bounded ring
            # groups never need more than n_slots * n_blocks pages.
            dense_eq = {name: n_slots * nb
                        for name, nb in self.n_blocks.items()}
            if n_pages is None:
                self.n_pages = dense_eq
            elif isinstance(n_pages, dict):
                self.n_pages = {**dense_eq, **{k: int(v) for k, v
                                               in n_pages.items()}}
            else:
                self.n_pages = {
                    g.name: (min(int(n_pages), dense_eq[g.name])
                             if g.ring else int(n_pages))
                    for g in self.layout.groups}
            self.chunk = int(prefill_chunk or cfg.prefill_chunk
                             or max(self.page_size, _MIN_CHUNK))
            self.prefill_interleave = int(
                cfg.prefill_interleave if prefill_interleave is None
                else prefill_interleave)
            self._alloc = {name: PageAllocator(n)
                           for name, n in self.n_pages.items()}
            self._slot_pages: Dict[str, List[List[int]]] = {
                name: [[] for _ in range(n_slots)] for name in self.n_pages}
            # leading run of each slot's pages still shared with the
            # prefix cache (writes there require copy-on-write first).
            self._slot_nshared: Dict[str, List[int]] = {
                name: [0] * n_slots for name in self.n_pages}
            self.prefill_exact = bool(
                cfg.prefill_exact if prefill_exact is None else prefill_exact)
            self.prefix_block = int(prefix_block or cfg.prefix_block
                                    or self.page_size)
            want_prefix = bool(cfg.prefix_cache if prefix_cache is None
                               else prefix_cache)
            # sharing needs EVERY group shareable: gemma3's ring local
            # group is not, so it keeps exclusive pages silently.
            self.prefix_cache = want_prefix and self.layout.prefix_shareable
            self._prefix: Optional[PrefixIndex] = (
                PrefixIndex([g.name for g in self.layout.groups],
                            self.page_size, self.prefix_block)
                if self.prefix_cache else None)
            self._admitting: Deque[_Admission] = collections.deque()
            self._preempted: List[_Preempted] = []
            # Tiered KV memory: ONE staged-transfer engine carries every
            # device<->host page movement (preemption spill/resume plus
            # the host tier's demote/promote); the T1 store only exists
            # with a byte budget AND the prefix cache (demotion is keyed
            # by the prefix index's token paths).
            self._xfer = StagedTransferEngine(self.layout,
                                              faults=self._fault,
                                              clock=self._clock,
                                              telemetry=telemetry)
            self.tier_restore_min = int(
                cfg.tier_restore_min_tokens if tier_restore_min is None
                else tier_restore_min)
            htb = int(cfg.kv_host_tier_bytes if host_tier_bytes is None
                      else host_tier_bytes)
            self.host_tier_bytes = htb if self.prefix_cache else 0
            self._tiers: Optional[KVTierManager] = (
                KVTierManager(self.layout, self.page_size,
                              self.prefix_block, self.host_tier_bytes,
                              self._xfer)
                if self.host_tier_bytes > 0 else None)
            self.tier_snapshot = str(
                cfg.kv_tier_snapshot if tier_snapshot is None
                else tier_snapshot) if self._tiers is not None else ""
            if self.tier_snapshot and os.path.exists(self.tier_snapshot):
                try:
                    self._tiers.load(self.tier_snapshot)
                except SnapshotCorruptError as e:
                    # storage rot is an availability event, not a config
                    # error: log it and serve cold.  (A geometry
                    # mismatch — ValueError — still raises: the snapshot
                    # is intact but belongs to a different layout.)
                    warnings.warn(f"kv tier snapshot unusable, serving "
                                  f"from cold start: {e}")
                    self.snapshot_cold_start = True
            # decode steps left to replay with output pushes suppressed
            # (recompute-mode resume re-emits already-delivered tokens).
            self._replay_skip = [0] * n_slots
            # invalid page id == n_pages[group]: reads clamp (and are
            # masked), writes scatter-drop.
            self.block_tab = {
                name: jnp.full((n_slots, self.n_blocks[name]),
                               self.n_pages[name], i32)
                for name in self.n_pages}
            self.mesh, _ = serving_mesh_for(cfg)
            if self.mesh is not None:
                p_specs, pool_specs = paged_sharding_specs(
                    cfg, self.page_size, self.mesh)
                self._pool_ns = named_shardings(self.mesh, pool_specs)
                self._rep_ns = NamedSharding(self.mesh, P_spec())
                # a no-op for weights already built on the mesh
                # (serve_loop.serving_params).
                self.params = jax.device_put(
                    self.params, named_shardings(self.mesh, p_specs))
                self.block_tab = jax.device_put(self.block_tab,
                                                self._rep_ns)
                self.last_tok = jax.device_put(self.last_tok, self._rep_ns)
                self.pos = jax.device_put(self.pos, self._rep_ns)
                self.remaining = jax.device_put(self.remaining,
                                                self._rep_ns)
                self.active = jax.device_put(self.active, self._rep_ns)
            # each pool shard is built on the device that holds it.
            self.pools = PP.init_params(
                registry.paged_cache_decls(cfg, self.n_pages,
                                           self.page_size),
                shardings=self._pool_ns)
            # host mirrors of per-slot decode state (drive lazy growth
            # and preemption without device readbacks).
            self._host_pos = [0] * n_slots
            self._host_last_tok = [0] * n_slots
            self._host_remaining = [0] * n_slots
            self._slot_seq = [0] * n_slots
            self._admit_seq = 0
            # expert load (stats()["moe"]): counted on the device by the
            # decode and chunk programs while telemetry is on.
            if telemetry is not None and cfg.n_experts:
                self._moe_load = moe_load_init(cfg)
                if self.mesh is not None:
                    self._moe_load = jax.device_put(self._moe_load,
                                                    self._rep_ns)
            self._count_kw = ({"count_moe": True}
                              if self._moe_load is not None else {})
            self._step = make_paged_decode_step(cfg, max_seq, self.page_size,
                                                **self._count_kw)
            self._chunk_fn = make_chunk_prefill_step(
                cfg, self.chunk, max_seq, self.page_size, **self._count_kw)
            # speculative decode (paged only: rollback needs the block
            # tables).  History/acceptance state exists even at k=0 so
            # the bookkeeping paths stay branch-free.
            self.speculate_k = max(int(cfg.speculate_k), 0)
            self.speculate_ngram = max(int(cfg.speculate_ngram), 1)
            self.speculate_min_accept = float(cfg.speculate_min_accept)
            self.speculate_probe = max(int(cfg.speculate_probe), 0)
            self._history: List[List[int]] = [[] for _ in range(n_slots)]
            self._accept_ewma = [1.0] * n_slots
            # re-probe schedule for self-disabled drafter slots: next
            # step allowed to probe, and the current (exponentially
            # backed-off) gap between failed probes.
            self._probe_at = [0] * n_slots
            self._probe_gap = [0] * n_slots
            # per-slot n-gram position index: ngram tuple -> sorted
            # positions of its occurrences in the slot's history, built
            # incrementally (_ng_done = positions indexed so far) so a
            # draft lookup is O(log occurrences) instead of an O(n)
            # backward scan every step — on novel text the drafter
            # never fires, so without the index the scan cost would
            # grow with the sequence while returning nothing.
            self._ng_idx: List[Dict[Tuple[int, ...], List[int]]] = \
                [{} for _ in range(n_slots)]
            self._ng_done = [0] * n_slots
            if self.speculate_k:
                self._verify = make_spec_verify_step(
                    cfg, self.speculate_k + 1, max_seq, self.page_size)
        else:
            self.prefix_cache = False
            self._prefix = None
            self._tiers = None
            self._xfer = None
            self.speculate_k = 0     # dense path: no block-table rollback
            cache_d = registry.cache_decls(cfg, 1, max_seq)
            one = PP.init_params(cache_d)  # zeros (init=zeros decls)
            self.cache = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_slots,) + a.shape).copy(),
                one)
            self._step = _make_step_fn(cfg, max_seq)

    # -- shared helpers -------------------------------------------------------------

    def _next_request(self) -> Optional[Request]:
        if self._pending:
            r = self._pending.popleft()
        else:
            r = self.requests.TryPop()
        if r is not None:
            if r.submitted_at == 0.0:      # direct Push (bypassed submit)
                r.submitted_at = self._clock()
                if self._telemetry:
                    self._telemetry.note_submit(r)
            if r.deadline_ms is not None:
                self._deadlines_live = True
        return r

    def _fail_request(self, r: Request, event: TerminalEvent) -> None:
        """Terminate a request with a typed in-band event: the event is
        pushed into its output stream BEFORE the close, so ``drain()``
        re-raises the original cause instead of timing out.  A full or
        already-closed stream degrades to close-only (the consumer still
        unblocks; it just sees a short result)."""
        try:
            r.out.Push(event, timeout=1.0)
        except (TimeoutError, StreamClosed):
            pass
        r.out.close()
        if event.kind == "rejected":
            self.rejections[event.reason] = \
                self.rejections.get(event.reason, 0) + 1
        elif event.kind == "expired":
            self.expired += 1
        elif event.kind == "errored":
            self.errored += 1
        else:
            self.cancelled += 1
        if self._telemetry:
            self._telemetry.note_terminal(r.rid, event.kind, event.reason)

    def _reject(self, r: Request, reason: str = "unservable") -> None:
        """Unservable request (bypassed submit() validation, or needs
        more pages than the whole pool): typed Rejected event + close so
        its consumer ends with the reason instead of raising inside the
        batcher PE."""
        self._fail_request(r, TerminalEvent.rejected(r.rid, reason))
        self.retired += 1

    def _expiry_left_ms(self, r: Request) -> float:
        """Milliseconds of deadline budget left (+inf when none)."""
        if r.deadline_ms is None:
            return float("inf")
        return r.deadline_ms - (self._clock() - r.submitted_at) * 1e3

    def _pinned(self, pools):
        """Re-assert the mesh sharding on a pools tree after a host-side
        page mutation (CoW copy, staged restore, rebuild).  Eager updates
        on sharded leaves already propagate their sharding; device_put
        with an identical sharding is a no-op, so this is a cheap
        invariant check, not a copy.  Identity when unsharded."""
        if self._pool_ns is None:
            return pools
        return jax.device_put(pools, self._pool_ns)

    def total_used_pages(self) -> int:
        return sum(a.used_pages for a in self._alloc.values())

    def total_free_pages(self) -> int:
        return sum(a.free_pages for a in self._alloc.values())

    def stats(self) -> Dict[str, Any]:
        """Serving observability snapshot: scheduling counters plus —
        in paged mode — per-group pool occupancy and the prefix-cache
        counters (hit rate, shared/CoW/eviction activity)."""
        s: Dict[str, Any] = {
            "steps": self.steps, "retired": self.retired,
            "preemptions": self.preemptions, "resumes": self.resumes,
            "prefill_chunks": self.prefill_chunks,
            "peak_pages": self.peak_pages,
            "rejections": dict(self.rejections),
            "expired": self.expired, "errored": self.errored,
            "cancelled": self.cancelled,
        }
        if self._telemetry:
            # bucket-derived p50/p90/p99 per latency histogram — the
            # registry is the source of truth; stats() is a view.
            s["latency"] = self._telemetry.latency_summary()
        if not self.paged:
            return s
        s["tier_faults"] = self.tier_faults
        s["tier_disabled"] = self.tier_disabled
        s["restarts"] = self.restarts
        s["snapshot_cold_start"] = self.snapshot_cold_start
        s["pools"] = {name: {"free": a.free_pages, "used": a.used_pages,
                             "shared": a.shared_pages}
                      for name, a in self._alloc.items()}
        s["shared_pages"] = sum(a.shared_pages for a in self._alloc.values())
        s["decode_read"] = {
            "path": ("kernel" if paged_decode_kernel(self.cfg)
                     and not self.cfg.mla else "gather"),
            "live_pages": self.decode_live_pages,
            "table_pages": self.decode_table_pages,
        }
        s["cow_copies"] = self.cow_copies
        s["prefix_cache"] = self.prefix_cache
        s["transfers"] = self._xfer.stats()
        if self.mesh is not None:
            tp = int(self.cfg.mesh_shape[-1])
            shard_bytes = total_bytes = 0
            for leaf in jax.tree.leaves(self.pools):
                total_bytes += int(leaf.nbytes)
                local = leaf.sharding.shard_shape(leaf.shape)
                shard_bytes += int(np.prod(local)) * leaf.dtype.itemsize
            s["mesh"] = {
                "shape": tuple(self.cfg.mesh_shape),
                "axes": tuple(self.mesh.axis_names),
                "tp": tp,
                "pool_bytes_per_shard": shard_bytes,
                "pool_bytes_total": total_bytes,
            }
        # every accepted draft token is one decode step the slot skipped;
        # rolled_back counts draft tokens whose speculative KV was
        # discarded by block-table rollback.
        s["speculation"] = {
            "k": self.speculate_k,
            "tokens_drafted": self.spec_drafted,
            "tokens_accepted": self.spec_accepted,
            "tokens_rolled_back": self.spec_rolled_back,
            "verify_rounds": self.spec_verify_steps,
            "acceptance_rate": (self.spec_accepted
                                / max(self.spec_drafted, 1)),
            "decode_steps_saved": self.spec_accepted,
        }
        if self._moe_load is not None:
            ld = jax.tree.map(lambda a: np.asarray(a).tolist(),
                              self._moe_load)
            s["moe"] = {"decode_tokens": ld["decode"],
                        "prefill_tokens": ld["prefill"],
                        "decode_experts_hit": ld["hit"],
                        "decode_calls": ld["calls"]}
        if self._telemetry and self._telemetry.profile:
            s["programs"] = _ProgramOps(self)
        if self._tiers is not None:
            s["tiers"] = {**self._tiers.stats(),
                          "recompute_resumes": self.recompute_resumes}
        if self.prefix_cache:
            s["prefix_lookups"] = self.prefix_lookups
            s["prefix_hits"] = self.prefix_hits
            s["prefix_hit_rate"] = (self.prefix_hits
                                    / max(self.prefix_lookups, 1))
            s["prefix_hit_tokens"] = self.prefix_hit_tokens
            s["prefix_evictions"] = self.prefix_evictions
            s["cached_prefixes"] = self._prefix.n_nodes
            s["cached_prefix_pages"] = self._prefix.n_pages
        return s

    def _program_ops(self) -> Dict[str, Dict[str, str]]:
        """For reading a device profile: the decode and chunk programs,
        once both have run, by module name, as ``{op: op_name}`` over
        the ops of their compiled HLO (``_compiled_ops``), lowered from
        the batcher's own state; computed once."""
        if self._programs is None and self.steps and self.prefill_chunks:
            chunk = self._chunk_inputs(np.zeros((1, self.chunk), np.int32),
                                       0, 0, 0, False, 1, 1, 0)
            state = (self.params, self.pools, self.block_tab, self.last_tok,
                     self.pos, self.remaining, self.active)
            load = () if self._moe_load is None else (self._moe_load,)
            self._programs = dict(
                _compiled_ops(fn, args) for fn, args in
                ((self._step, state + load),
                 (self._chunk_fn, state + chunk + load)))
        return self._programs or {}

    def _sync_telemetry(self) -> None:
        """Collector: mirror the plain-attribute lifetime counters into
        the telemetry registry.  Runs on every registry read (scrape /
        snapshot), not per event — hot paths keep bumping cheap python
        ints and this reconciles them, so enabling metrics adds no
        per-token dict lookups."""
        tel = self._telemetry
        if tel is None:
            return
        m = tel.metrics
        c, g = m.counter, m.gauge
        c("serve_steps_total", "batched decode jit calls").set(self.steps)
        c("serve_retired_total", "requests fully finished (any outcome)"
          ).set(self.retired)
        c("serve_prefill_chunks_total", "chunked-prefill jit calls"
          ).set(self.prefill_chunks)
        c("serve_preemptions_total", "slots preempted").set(
            self.preemptions)
        c("serve_resumes_total", "preempted slots resumed").set(
            self.resumes)
        c("serve_expired_total", "requests expired past deadline"
          ).set(self.expired)
        c("serve_errored_total", "requests failed with an error").set(
            self.errored)
        c("serve_cancelled_total", "requests cancelled").set(
            self.cancelled)
        for reason, n in self.rejections.items():
            c("serve_rejections_total", "requests rejected, by reason",
              labels={"reason": reason}).set(n)
        g("serve_queue_depth", "requests waiting in the admission queue"
          ).set(len(self._pending) + self.requests.Size())
        g("serve_slots_live", "slots with an active request").set(
            sum(1 for r in self._slot_req if r is not None))
        if not self.paged:
            return
        c("serve_restarts_total", "supervised crash recoveries").set(
            self.restarts)
        c("serve_tier_faults_total", "injected/real tier-transfer faults"
          ).set(self.tier_faults)
        g("serve_peak_pages", "high-water mark of used pages").set(
            self.peak_pages)
        for name, a in self._alloc.items():
            g("serve_pool_pages", "page-pool occupancy by group/state",
              labels={"group": name, "state": "free"}).set(a.free_pages)
            g("serve_pool_pages", "page-pool occupancy by group/state",
              labels={"group": name, "state": "used"}).set(a.used_pages)
            g("serve_pool_pages", "page-pool occupancy by group/state",
              labels={"group": name, "state": "shared"}
              ).set(a.shared_pages)
        c("serve_spec_tokens_drafted_total", "speculative tokens drafted"
          ).set(self.spec_drafted)
        c("serve_spec_tokens_accepted_total",
          "speculative tokens accepted (decode steps saved)").set(
            self.spec_accepted)
        c("serve_spec_tokens_rolled_back_total",
          "speculative tokens rolled back").set(self.spec_rolled_back)
        c("serve_spec_verify_rounds_total", "speculative verify rounds"
          ).set(self.spec_verify_steps)
        c("serve_transfer_gathers_total", "staged D2H gathers").set(
            self._xfer.gathers)
        c("serve_transfer_scatters_total", "staged H2D scatters").set(
            self._xfer.scatters)
        c("serve_transfer_d2h_bytes_total", "bytes spilled to host").set(
            self._xfer.d2h_bytes)
        c("serve_transfer_h2d_bytes_total", "bytes restored to device"
          ).set(self._xfer.h2d_bytes)
        if self.prefix_cache:
            c("serve_prefix_lookups_total", "prefix-cache lookups").set(
                self.prefix_lookups)
            c("serve_prefix_hits_total", "prefix-cache hits").set(
                self.prefix_hits)
            c("serve_prefix_hit_tokens_total",
              "prompt tokens served from cached prefixes").set(
                self.prefix_hit_tokens)
            c("serve_cow_copies_total", "copy-on-write page copies").set(
                self.cow_copies)
            c("serve_prefix_evictions_total", "prefix nodes evicted"
              ).set(self.prefix_evictions)
        if self._tiers is not None:
            t = self._tiers
            g("serve_t1_bytes", "host-tier resident bytes").set(
                t.store.nbytes)
            c("serve_t1_demotions_total", "prefix blocks demoted to T1"
              ).set(t.demotions)
            c("serve_t1_rehits_total", "T1 promote-back hits").set(
                t.rehits)
            c("serve_t1_recomputes_total",
              "tier misses recomputed from tokens").set(t.recomputes)

    # -- paged admission (chunked prefill) --------------------------------------------

    def _full_pages_needed(self, r: Request, group: str) -> int:
        """Worst-case pages the request can ever hold in this group."""
        total = min(len(r.prompt) + r.max_new, self.max_seq)
        return self.layout.blocks_for(group, total, self.max_seq)

    def _admit_pages_needed(self, r: Request, group: str,
                            cover: Optional[int] = None) -> int:
        """Pages reserved at admission: prompt-only under lazy growth,
        the full worst case under ``reserve_decode``.  ``cover`` raises
        the floor to a token position (recompute-mode resume reserves
        through ``pos + 1`` so the re-admitted slot can always replay
        and emit at least one token before it can be preempted again —
        the same headroom rule the restore path uses)."""
        if self.reserve_decode:
            return self._full_pages_needed(r, group)
        tokens = max(len(r.prompt), cover or 0)
        return self.layout.blocks_for(group, tokens, self.max_seq)

    def _set_table_row(self, group: str, slot: int,
                       pages: Sequence[int]) -> None:
        row = np.full((self.n_blocks[group],), self.n_pages[group], np.int32)
        row[:len(pages)] = pages
        self.block_tab[group] = \
            self.block_tab[group].at[slot].set(jnp.asarray(row))

    def _note_peak(self) -> None:
        self.peak_pages = max(self.peak_pages, self.total_used_pages())

    def _tier_op(self, what: str, fn: Callable[[], Any],
                 backoff: float = 0.005) -> Tuple[bool, Any]:
        """Run a tier transfer with capped-backoff retries — rung 1 of
        the degradation ladder.  Returns ``(ok, result)``; on final
        failure the caller falls through to its recompute path (rung 2),
        and after ``tier_fault_limit`` failed operations the host tier
        is disabled outright (rung 3, tier-off) — the batcher keeps
        serving, just without T1.  Only ``RuntimeError`` (which includes
        ``InjectedFault``) is retried: anything else is a genuine bug
        and propagates."""
        err: Optional[BaseException] = None
        for attempt in range(self.transfer_retries + 1):
            if attempt:
                time.sleep(min(backoff * (2 ** (attempt - 1)), 0.05))
            try:
                return True, fn()
            except RuntimeError as e:
                err = e
        self.tier_faults += 1
        warnings.warn(f"tier {what} failed after "
                      f"{self.transfer_retries + 1} attempts: {err}")
        if (self.tier_faults >= self.tier_fault_limit
                and self._tiers is not None):
            self._tiers = None
            self.tier_disabled = True
            warnings.warn(f"host KV tier disabled after "
                          f"{self.tier_faults} transfer faults "
                          f"(degraded to recompute-only)")
        return False, None

    def _alloc_evict(self, name: str, n: int) -> Optional[List[int]]:
        """Alloc ``n`` pages, evicting LRU cached prefixes under
        pressure.  Cached prefixes are strictly lower-value than any
        live request, so they are freed (decref'd — pages still shared
        by live slots survive via those refs) before admission
        backpressures or any live slot is preempted.  With the host
        tier enabled, each evicted node's page payload is DEMOTED to
        T1 first (staged gather while the pages are still live), so a
        later rehit restores instead of recomputing."""
        if self._fault.fire("alloc"):
            # simulated pool exhaustion: the caller takes its normal
            # dry-pool path (backpressure / preemption) — allocator
            # invariants must survive it (chaos tests check).
            return None
        got = self._alloc[name].alloc(n)
        while got is None and self._prefix is not None \
                and self._prefix.n_nodes:
            evicted = self._prefix.evict_lru()
            if evicted is None:
                break
            path_toks, pages = evicted
            if self._tiers is not None:
                # demote failure just loses the T1 copy — the eviction
                # itself proceeds (a rehit will recompute).
                self._tier_op("demote", lambda: self._tiers.demote(
                    path_toks, pages, self.pools))
            for gname, pgs in pages.items():
                self._alloc[gname].free(pgs)
            self.prefix_evictions += 1
            got = self._alloc[name].alloc(n)
        return got

    def _tier_promote(self, prompt: np.ndarray) -> int:
        """Restore the longest T1-cached block chain the device index is
        missing for this prompt: allocate pages per group, scatter the
        host payloads back in one staged transfer, and INSERT the blocks
        into the ``PrefixIndex`` — the admission's normal match then
        attaches them exactly like any other cached prefix, so a T1
        rehit inherits the full shared-page machinery (incref pinning,
        CoW, catch-up-chunk bit-identity).  Returns tokens promoted.

        Chains shorter than ``tier_restore_min_tokens`` recompute
        instead (a short prefill is cheaper than staging pages through
        host RAM).  Allocation pressure during the promote can itself
        evict blocks of this very prompt out of the index (demoting
        them to T1); the promote detects the moved anchor and retries
        against the new tree state."""
        tiers = self._tiers
        for _ in range(2):
            nb = self._prefix.matched_blocks(prompt)
            chain = tiers.match(prompt, start_block=nb)
            if not chain:
                return 0
            if len(chain) * tiers.block < self.tier_restore_min:
                tiers.recomputes += 1
                return 0
            bpp = tiers.bpp
            new_pages: Dict[str, List[int]] = {g.name: []
                                               for g in self.layout.groups}
            taken = 0
            for _entry in chain:                 # leading blocks, best effort
                grabbed: Dict[str, List[int]] = {}
                ok = True
                for g in self.layout.groups:
                    got = self._alloc_evict(g.name, bpp)
                    if got is None:
                        ok = False
                        break
                    grabbed[g.name] = got
                if not ok:
                    for gname, pgs in grabbed.items():
                        self._alloc[gname].free(pgs)
                    break
                for gname in new_pages:
                    new_pages[gname].extend(grabbed[gname])
                taken += 1
            if not taken or taken * tiers.block < self.tier_restore_min:
                # nothing allocatable, or pool pressure truncated the
                # chain below the recompute crossover: staging a span
                # this short through host RAM is slower than prefill.
                for gname, pgs in new_pages.items():
                    if pgs:
                        self._alloc[gname].free(pgs)
                if taken:
                    tiers.recomputes += 1
                return 0
            if self._prefix.matched_blocks(prompt) != nb:
                # our own allocation pressure evicted on-path blocks;
                # hand the pages back and re-anchor (they are in T1 now).
                for gname, pgs in new_pages.items():
                    if pgs:
                        self._alloc[gname].free(pgs)
                continue
            ok, pools = self._tier_op(
                "promote", lambda: tiers.restore_chain(
                    self.pools, chain[:taken], new_pages))
            if not ok:
                # promotion failed: hand the pages back and recompute
                # (rung 2) — the prompt prefills from tokens instead.
                for gname, pgs in new_pages.items():
                    if pgs:
                        self._alloc[gname].free(pgs)
                tiers.recomputes += 1
                return 0
            self.pools = self._pinned(pools)
            total = (nb + taken) * tiers.block
            # blocks below nb already exist in the tree — insert ignores
            # their (placeholder) entries and absorbs only ours.
            pages_arg = {gname: [-1] * (nb * bpp) + pgs
                         for gname, pgs in new_pages.items()}
            absorbed = set(self._prefix.insert(
                np.asarray(prompt[:total], np.int32), pages_arg))
            dup = [i for i in range(nb * bpp, (nb + taken) * bpp)
                   if i not in absorbed]
            for gname in new_pages:              # defensive: racing insert
                pgs = [pages_arg[gname][i] for i in dup]
                if pgs:
                    self._alloc[gname].free(pgs)
            tiers.rehits += 1
            tiers.rehit_tokens += taken * tiers.block
            return taken * tiers.block
        return 0

    def _match_prefix(self, prompt) -> Tuple[int, Dict[str, List[int]]]:
        """The prefix cache's longest match for ``prompt`` (T1-cached
        blocks the index lacks are promoted first): matched tokens and
        the matched pages of each group."""
        prompt_i32 = np.asarray(prompt, np.int32)
        if self._tiers is not None:
            self._tier_promote(prompt_i32)
        return self._prefix.match(prompt_i32)

    def _alloc_admission(self, r: Request, n_attach: int,
                         resume: Optional[_Preempted]
                         ) -> Optional[Dict[str, List[int]]]:
        """The private pages an admission needs in every group (evicting
        cached prefixes under pressure), all or nothing: None, with
        nothing held, when a group's pool is dry."""
        grabbed: Dict[str, List[int]] = {}
        for g in self.layout.groups:
            need = self._admit_pages_needed(
                r, g.name, cover=(resume.pos + 1) if resume else None)
            if g.shareable:
                need -= n_attach
            pages = self._alloc_evict(g.name, max(need, 0))
            if pages is None:
                for name, pgs in grabbed.items():
                    self._alloc[name].free(pgs)
                return None
            grabbed[g.name] = pages
        return grabbed

    def _try_admit_paged(self, r: Request, slot: int,
                         resume: Optional[_Preempted] = None) -> bool:
        """Reserve admission pages + a slot and start chunked prefill.
        Returns False (leaving ``r`` to the caller) when any group's
        pool is dry — all-or-nothing across page groups.

        With the prefix cache enabled the prompt is first matched
        against the ``PrefixIndex``: the matched span's pages are
        *attached* (incref, shared read-only) instead of allocated, and
        the catch-up prefill starts at the divergence point — a fully
        cached prompt prefills a single final token (its TTFT is one
        decode-sized step).  A partially matched page on the divergence
        boundary is copied (copy-on-write) into the first private page
        when the catch-up prefill — or, under ``reserve_decode``, a
        decode step that will never consult ``_grow_slot`` — is going to
        write past the match.  With the host tier enabled, T1-cached
        blocks missing from the index are promoted first, so the match
        sees them.

        ``resume`` re-admits a recompute-mode preempted request: same
        path (including prefix hits on its own retired-at-preemption
        prompt blocks), but the final chunk restores the parked decode
        budget and arms the suppressed-output replay instead of
        emitting a first token."""
        tel = self._telemetry
        plen = len(r.prompt)
        m = 0
        shared: Dict[str, List[int]] = {g.name: [] for g in self.layout.groups}
        if self.prefix_cache:
            self.prefix_lookups += 1
            if tel:
                with tel.span(r.rid, "serve.prefix_match") as sp:
                    m, shared = self._match_prefix(r.prompt)
                    sp.set(prefix_hit_tokens=m)
            else:
                m, shared = self._match_prefix(r.prompt)
        n_matched = _ceil_div(m, self.page_size)
        partial = bool(m % self.page_size)
        cow = partial and (m < plen or self.reserve_decode)
        n_attach = n_matched - (1 if cow else 0)
        # Pin the matched pages BEFORE anything can evict: _alloc_evict
        # below may LRU-evict the very nodes just matched, and without
        # this reference their pages would return to the free list and
        # could be handed straight back as this request's own private
        # pages — aliasing the prefix it is about to read.  The pin IS
        # the slot's reference for the attached pages; the CoW source's
        # pin is dropped again right after the copy.
        pinned = {name: pgs[:n_matched] for name, pgs in shared.items()}
        for name, pgs in pinned.items():
            if pgs:
                self._alloc[name].incref(pgs)
        if tel:
            with tel.span(r.rid, "serve.alloc") as sp:
                evictions = self.prefix_evictions
                grabbed = self._alloc_admission(r, n_attach, resume)
                sp.set(pages=sum(map(len, (grabbed or {}).values())),
                       evicted=self.prefix_evictions - evictions)
        else:
            grabbed = self._alloc_admission(r, n_attach, resume)
        if grabbed is None:
            for name, pgs in pinned.items():
                if pgs:
                    self._alloc[name].free(pgs)
            return False
        for g in self.layout.groups:
            name = g.name
            attach = shared[name][:n_attach] if g.shareable else []
            if cow and shared[name][n_attach:]:
                # divergence mid-page: duplicate the boundary page into
                # the first private page before any differing write.
                with (tel.span(r.rid, "serve.cow_copy", pages=1) if tel
                      else _NULLCTX):
                    self.pools = self._pinned(self.layout.copy_pages(
                        self.pools, name,
                        shared[name][n_attach:n_attach + 1],
                        grabbed[name][:1]))
            if pinned[name][n_attach:]:            # unpin the CoW source
                self._alloc[name].free(pinned[name][n_attach:])
            row = attach + grabbed[name]
            self._set_table_row(name, slot, row)
            self._slot_pages[name][slot] = list(row)
            self._slot_nshared[name][slot] = len(attach)
        if cow:
            self.cow_copies += 1
        if m:
            self.prefix_hits += 1
            self.prefix_hit_tokens += m
        self._note_peak()
        # The catch-up prefill starts at the CHUNK-GRID point at or
        # below the divergence (not the divergence itself): its chunks
        # then cover exactly the [k*chunk, (k+1)*chunk) spans a cold run
        # covers, reading the same pool bytes + full-precision own-chunk
        # overlay — and since a shared page's bits depend only on the
        # matched tokens (causality), a hit is BIT-identical to a cold
        # run, not merely argmax-stable.  Positions in [start, m) are
        # recomputed as queries but their writes stay suppressed
        # (cache_offset): the shared pages already hold those exact
        # bits.  A fully cached prompt still pays a single chunk.
        start = min(m, plen - 1)
        start -= start % self.chunk
        if resume is None:
            self._slot_seq[slot] = self._admit_seq
            self._admit_seq += 1
        else:                  # keep the original admission order (victim
            self._slot_seq[slot] = resume.seq      # tie-breaks stay stable)
        n_chunks = max(1, _ceil_div(plen - start, self.chunk))
        self._admitting.append(_Admission(
            req=r, slot=slot, plen=plen, next_chunk=0,
            n_chunks=n_chunks,
            start=start, cache_offset=m, resume=resume))
        if self._telemetry:
            self._telemetry.note_admit(
                r, slot, prefix_hit_tokens=m, cow=cow, start=start,
                n_chunks=n_chunks, resume=resume is not None)
        return True

    def _prefill_step(self) -> None:
        """Run ONE chunk of the oldest mid-admission request.

        Chunks cover ``[start + c*chunk, ...)`` — ``start`` is 0 for a
        cold prompt and the prefix-cache divergence point for a hit.
        ``prefill_exact`` swaps the FINAL chunk for one pow2-bucketed
        pass over the whole remaining span ``[start, plen)``: every
        prompt position's K/V is recomputed with full-precision
        own-chunk attention, so the installed cache is bit-identical to
        a single dense prefill no matter how the prompt was chunked (the
        intermediate chunks still run, keeping the decode-interleaving
        latency bound; exactness costs up to one extra prefill of
        FLOPs)."""
        a = self._admitting[0]
        if self._deadlines_live and self._expiry_left_ms(a.req) <= 0:
            self._admitting.popleft()
            self._fail_request(a.req, TerminalEvent.expired(
                a.req.rid, "deadline passed during prefill"))
            self._release_slot(a.slot)
            self.retired += 1
            return
        try:
            # injected chunk fault, checked BEFORE the jit call touches
            # the donated pools: only this request dies (typed Errored
            # event); every other slot keeps decoding untouched.
            self._fault.check("chunk")
        except InjectedFault as e:
            self._admitting.popleft()
            self._fail_request(a.req, TerminalEvent.errored(a.req.rid, e))
            self._release_slot(a.slot)
            self.retired += 1
            return
        C, c = self.chunk, a.next_chunk
        final = c == a.n_chunks - 1
        base = a.start + c * C
        fn = self._chunk_fn
        if final and self.prefill_exact:
            base = a.start
            C = max(_next_pow2(a.plen - base), _MIN_CHUNK)
            fn = make_chunk_prefill_step(self.cfg, C, self.max_seq,
                                         self.page_size, **self._count_kw)
        tel = self._telemetry
        rid = a.req.rid
        t0 = tel.clock() if tel else 0.0
        with (tel.span(rid, "serve.prefill_inputs") if tel else _NULLCTX):
            seg = np.zeros((1, C), np.int32)
            part = np.asarray(a.req.prompt[base:base + C], np.int32)
            seg[0, :len(part)] = part
            last_in_chunk = (a.plen - 1 - base) if final else (C - 1)
            # A resume re-admission needs no special budget: pos +
            # remaining == plen + max_new - 1 at every step (set at
            # admission, kept in lockstep by decode, re-established by
            # both resume modes), so installing max_new - 1 again leaves
            # exactly (replay steps + parked remaining) on the device
            # counter.
            inputs = self._chunk_inputs(seg, base, last_in_chunk, a.slot,
                                        final, a.plen, a.req.max_new,
                                        a.cache_offset)
        try:
            with (tel.span(rid, "serve.prefill_chunk",
                           step=self.prefill_chunks)
                  if tel else _NULLCTX):
                (self.pools, self.last_tok, self.pos, self.remaining,
                 self.active, tok0) = self._counted(
                    fn, self.params, self.pools, self.block_tab,
                    self.last_tok, self.pos, self.remaining, self.active,
                    *inputs)
        except Exception as e:
            # a genuine failure inside the jitted prefill may have
            # consumed the donated pools — fatal; the supervisor owns
            # the rebuild.
            raise BatcherFault(e) from e
        self.prefill_chunks += 1
        a.next_chunk += 1
        if not final:
            # nothing is read back: the chunk runs on behind the host, and
            # its span ends with the dispatch.
            if tel:
                tel.note_chunk(rid, a.slot, c, t0, tel.clock(), base=base,
                               final=False)
            return
        self._admitting.popleft()
        if not tel:
            self._emit_first_token(a, int(tok0), 0.0)
            return
        with tel.span(rid, "serve.readback"):
            tok0 = int(tok0)
        t_tok = tel.clock()
        tel.note_chunk(rid, a.slot, c, t0, t_tok, base=base, final=True)
        with tel.span(rid, "serve.emit") as sp:
            sp.set(tokens=int(a.resume is None),
                   retired=self._emit_first_token(a, tok0, t_tok))

    def _chunk_inputs(self, seg, base, last_in_chunk, slot, final, plen,
                      max_new, cache_offset):
        """The chunk program's per-chunk arguments, after the batcher's
        state, as device arrays."""
        return (jnp.asarray(seg), jnp.full((1,), base, jnp.int32),
                jnp.full((1,), last_in_chunk, jnp.int32), jnp.int32(slot),
                jnp.asarray(final), jnp.int32(plen), jnp.int32(max_new),
                jnp.int32(cache_offset))

    def _emit_first_token(self, a: _Admission, tok0: int, ts: float) -> int:
        """Install a finished admission's first token: arm the slot for
        decode, or retire the request at admission.  Returns the number
        of requests retired (0 or 1)."""
        tel = self._telemetry
        # drafter history = every token the model has consumed
        # (prompt + first sampled token); invariant len == pos + 1.
        self._history[a.slot] = [int(t) for t in a.req.prompt] + [tok0]
        self._accept_ewma[a.slot] = 1.0
        self._probe_at[a.slot] = 0
        self._probe_gap[a.slot] = 0
        self._ng_idx[a.slot].clear()
        self._ng_done[a.slot] = 0
        if a.resume is not None:
            # first token already reached the consumer before the
            # preemption: arm the suppressed-output replay instead.
            replay = a.resume.pos - a.plen
            self._slot_req[a.slot] = a.req
            self._host_pos[a.slot] = a.plen
            self._host_last_tok[a.slot] = tok0
            self._host_remaining[a.slot] = a.resume.remaining + replay
            self._replay_skip[a.slot] = replay + a.resume.skip
            self.resumes += 1
            self.recompute_resumes += 1
            if tel:
                tel.note_resume(a.req.rid, a.slot, "recompute")
            return 0
        a.req.out.Push(tok0)
        if tel:
            tel.note_first_token(a.req.rid, a.slot, ts, pos=a.plen)
        if a.req.max_new > 1 and a.plen < self.max_seq - 1:
            self._slot_req[a.slot] = a.req
            self._host_pos[a.slot] = a.plen
            self._host_last_tok[a.slot] = tok0
            self._host_remaining[a.slot] = a.req.max_new - 1
            return 0
        a.req.out.close()                  # retired at admission
        self.retired += 1
        self._release_slot(a.slot, prompt=a.req.prompt)
        if tel:
            tel.note_retire(a.req.rid, a.slot)
        return 1

    def _release_slot(self, slot: int,
                      prompt: Optional[np.ndarray] = None,
                      keep_shared: bool = False) -> None:
        """Release the slot's pages (every group) and invalidate its
        block table rows so later (masked) decode writes can never touch
        reused pages.

        With the prefix cache enabled and a retiring ``prompt`` given,
        the prompt's full token blocks are first inserted into the
        ``PrefixIndex``: pages backing newly indexed blocks transfer the
        slot's reference to the index — the retired prefix *lingers* as
        cache until LRU-evicted under pool pressure — while everything
        else (already-indexed blocks, the partial tail page, decode
        pages) is decref'd, so pages shared with other live sequences
        survive through their remaining refs.

        ``keep_shared`` (preemption): the leading shared-prefix pages
        keep their references — the parked ``_Preempted`` record owns
        them until resume re-attaches the same physical pages."""
        absorbed: frozenset = frozenset()
        if self._prefix is not None and prompt is not None and len(prompt):
            pages = {name: self._slot_pages[name][slot]
                     for name in self._slot_pages}
            absorbed = frozenset(self._prefix.insert(
                np.asarray(prompt, np.int32), pages))
        for name in self._slot_pages:
            ns = self._slot_nshared[name][slot] if keep_shared else 0
            rest = [p for i, p in enumerate(self._slot_pages[name][slot])
                    if i >= ns and i not in absorbed]
            if rest:
                self._alloc[name].free(rest)
            self._slot_pages[name][slot] = []
            self._slot_nshared[name][slot] = 0
            self.block_tab[name] = self.block_tab[name].at[slot].set(
                self.n_pages[name])
        self._history[slot] = []

    # -- lazy decode growth + preemption ------------------------------------------------

    def _pick_victim(self) -> Optional[int]:
        """Lowest-ranked decoding slot: SLA class first (batch parks
        before standard before latency), then the explicit priority knob,
        ties broken toward the most recently admitted.  Defaults (all
        "standard", priority 0) reduce to the original policy."""
        cands = [i for i, r in enumerate(self._slot_req) if r is not None]
        if not cands:
            return None
        return min(cands, key=lambda i: (class_rank(self._slot_req[i].klass),
                                         self._slot_req[i].priority,
                                         -self._slot_seq[i]))

    def _preempt(self, slot: int) -> None:
        """Spill the slot's PRIVATE pages host-side, free them, park the
        request.  Pages still shared with the prefix cache are skipped:
        their content is immutable while shared (writes copy first), so
        there is nothing to spill — the parked record simply keeps the
        slot's refcount on them and resume re-attaches the same physical
        pages.  Freeing them would reclaim no memory anyway unless every
        other holder also let go.

        The spill is ONE staged transfer for all page groups (device
        gathers dispatched before the first blocking copy) instead of a
        blocking per-group round-trip; leaf dtypes are preserved, so
        int8 pages park as int8 with their bf16 scale pages intact.

        Tiered-memory recompute policy: a sequence with fewer than
        ``tier_restore_min_tokens`` positions materialized is cheaper to
        re-prefill than to stage through host RAM — nothing is spilled;
        its prompt blocks retire into the prefix index (where pool
        pressure may demote them to T1) and resume re-admits + replays.
        """
        r = self._slot_req[slot]
        pos = self._host_pos[slot]
        recompute = self._tiers is not None and pos < self.tier_restore_min
        if not recompute:
            counts: Dict[str, int] = {}
            shared: Dict[str, List[int]] = {}
            priv_by_group: Dict[str, List[int]] = {}
            for g in self.layout.groups:
                pages = self._slot_pages[g.name][slot]
                ns = self._slot_nshared[g.name][slot]
                shared[g.name] = pages[:ns]
                priv_by_group[g.name] = pages[ns:]
                counts[g.name] = len(pages) - ns
            tel = self._telemetry
            t0 = tel.clock() if tel else 0.0
            ok, gathered = self._tier_op(
                "spill", lambda: self._xfer.gather_host(self.pools,
                                                        priv_by_group))
            if ok:
                data = {name: gathered.get(name) for name in priv_by_group}
                self._preempted.append(_Preempted(
                    req=r, pos=pos,
                    last_tok=self._host_last_tok[slot],
                    remaining=self._host_remaining[slot],
                    data=data, counts=counts, seq=self._slot_seq[slot],
                    shared=shared, skip=self._replay_skip[slot],
                    hist=list(self._history[slot])))
                self._replay_skip[slot] = 0
                self.active = self.active.at[slot].set(False)
                self._slot_req[slot] = None
                self._release_slot(slot, keep_shared=True)
                self.preemptions += 1
                self.preempted_rids.append(r.rid)
                if tel:
                    tel.note_spill(r.rid, t0, tel.clock())
                    tel.note_preempt(r.rid, slot, pos, "spill")
                return
            # spill failed (rung 2): park as a recompute record instead —
            # greedy replay is deterministic, so the resumed output is
            # still bit-identical; the spilled bytes were never needed.
        self._preempted.append(_Preempted(
            req=r, pos=pos, last_tok=self._host_last_tok[slot],
            remaining=self._host_remaining[slot],
            data={}, counts={}, seq=self._slot_seq[slot],
            mode="recompute", skip=self._replay_skip[slot]))
        self._replay_skip[slot] = 0
        self.active = self.active.at[slot].set(False)
        self._slot_req[slot] = None
        self._release_slot(slot, prompt=r.prompt)
        self.preemptions += 1
        self.preempted_rids.append(r.rid)
        if self._telemetry:
            self._telemetry.note_preempt(r.rid, slot, pos, "recompute")

    def _grow_slot(self, slot: int) -> int:
        """Ensure every group holds a WRITABLE page for the slot's next
        decode write; preempts other slots when the pool is dry
        (self-preempts as a last resort).

        Two cases need pages: the write position crosses into an
        unallocated logical page (plain lazy growth), or it lands inside
        a page still shared with the prefix cache — the first write past
        a shared prefix triggers copy-on-write: the page is duplicated
        into a fresh private page and the block table redirected, so the
        cached original stays bit-stable for every other sequence
        aliasing it.  Returns the pages it took."""
        nxt = self._host_pos[slot]             # position decode writes next
        taken = 0

        def take_one(name: str) -> Optional[List[int]]:
            got = self._alloc_evict(name, 1)
            while got is None:
                # the victim may be the growing slot itself: a
                # low-priority grower parks rather than evicting a
                # higher-priority decode.
                victim = self._pick_victim()
                if victim is None or victim == slot:
                    self._preempt(slot)
                    return None
                self._preempt(victim)
                got = self._alloc_evict(name, 1)
            return got

        for g in self.layout.groups:
            need = self.layout.blocks_for(g.name, nxt + 1, self.max_seq)
            pages = self._slot_pages[g.name][slot]
            while len(pages) < need:
                got = take_one(g.name)
                if got is None:
                    return taken
                taken += 1
                pages.append(got[0])
                self.block_tab[g.name] = self.block_tab[g.name].at[
                    slot, len(pages) - 1].set(got[0])
            j = need - 1                       # page holding the write
            if j < self._slot_nshared[g.name][slot]:
                got = take_one(g.name)
                if got is None:
                    return taken
                taken += 1
                self.pools = self._pinned(self.layout.copy_pages(
                    self.pools, g.name, [pages[j]], got))
                self._alloc[g.name].free([pages[j]])   # drop the shared ref
                pages[j] = got[0]
                self.block_tab[g.name] = self.block_tab[g.name].at[
                    slot, j].set(got[0])
                self._slot_nshared[g.name][slot] = j
                self.cow_copies += 1
        self._note_peak()
        return taken

    def _try_resume(self) -> int:
        """Restore preempted requests into free slots, highest priority
        (then oldest) first; all page groups alloc-or-nothing.  Restore
        mode scatters every group's spilled payload in one staged
        transfer; recompute mode re-admits the original prompt (prefix
        hits recover whatever blocks survived) and replays."""
        resumed = 0
        while self._preempted:
            busy = {a.slot for a in self._admitting}
            free = [i for i, r in enumerate(self._slot_req)
                    if r is None and i not in busy]
            if not free:
                break
            order = sorted(
                range(len(self._preempted)),
                key=lambda i: (-class_rank(self._preempted[i].req.klass),
                               -self._preempted[i].req.priority,
                               self._preempted[i].seq))
            idx = order[0]
            rec = self._preempted[idx]
            slot = free[0]
            if self._expiry_left_ms(rec.req) <= 0:
                # expired while parked: free its held shared refs and
                # terminate the consumer — no slot spent on a dead SLA.
                self._preempted.pop(idx)
                for name, pgs in rec.shared.items():
                    if pgs:
                        self._alloc[name].free(pgs)
                self._fail_request(rec.req, TerminalEvent.expired(
                    rec.req.rid, "deadline passed while preempted"))
                self.retired += 1
                continue
            if rec.mode == "recompute":
                self._preempted.pop(idx)
                if self._try_admit_paged(rec.req, slot, resume=rec):
                    resumed += 1
                    continue
                self._preempted.insert(idx, rec)   # pool dry: park again
                break
            grabbed: Dict[str, List[int]] = {}
            ok = True
            for g in self.layout.groups:
                # headroom: also cover the next decode write, so a
                # resumed slot always emits at least one token before it
                # can be preempted again — without this, resuming into a
                # still-dry pool thrashes spill/restore every step.
                # Shared prefix pages re-attach as-is (the parked record
                # kept the slot's refs) and count toward coverage.
                ns = len(rec.shared.get(g.name, ()))
                need = max(rec.counts[g.name],
                           self.layout.blocks_for(g.name, rec.pos + 1,
                                                  self.max_seq) - ns)
                pages = self._alloc_evict(g.name, need)
                if pages is None:
                    ok = False
                    break
                grabbed[g.name] = pages
            if not ok:
                for name, pgs in grabbed.items():
                    self._alloc[name].free(pgs)
                break
            self._preempted.pop(idx)
            tel = self._telemetry
            t0 = tel.clock() if tel else 0.0
            ok, pools = self._tier_op(
                "restore", lambda: self._xfer.scatter_device(
                    self.pools,
                    {name: rec.data[name] for name in grabbed
                     if rec.counts[name]},
                    {name: grabbed[name][:rec.counts[name]]
                     for name in grabbed if rec.counts[name]}))
            if not ok:
                # restore failed: drop the spilled payload and convert
                # to a recompute record (rung 2) — deterministic replay
                # regenerates the same KV from tokens.  Our refs on the
                # shared prefix pages return to the index's own holders,
                # and the re-admission's prefix match re-attaches them.
                for name, pgs in grabbed.items():
                    self._alloc[name].free(pgs)
                for name, pgs in rec.shared.items():
                    if pgs:
                        self._alloc[name].free(pgs)
                rec.mode = "recompute"
                rec.data, rec.counts, rec.shared = {}, {}, {}
                self._preempted.insert(idx, rec)
                continue
            self.pools = self._pinned(pools)
            for name, priv in grabbed.items():
                pages = rec.shared.get(name, []) + priv
                self._set_table_row(name, slot, pages)
                self._slot_pages[name][slot] = list(pages)
                self._slot_nshared[name][slot] = len(
                    rec.shared.get(name, ()))
            self._note_peak()
            i32 = jnp.int32
            self.last_tok = self.last_tok.at[slot].set(
                jnp.asarray(rec.last_tok, i32))
            self.pos = self.pos.at[slot].set(jnp.asarray(rec.pos, i32))
            self.remaining = self.remaining.at[slot].set(
                jnp.asarray(rec.remaining, i32))
            self.active = self.active.at[slot].set(True)
            self._slot_req[slot] = rec.req
            self._slot_seq[slot] = rec.seq
            self._host_pos[slot] = rec.pos
            self._host_last_tok[slot] = rec.last_tok
            self._host_remaining[slot] = rec.remaining
            self._replay_skip[slot] = rec.skip
            self._history[slot] = list(rec.hist)
            self._accept_ewma[slot] = 1.0
            self._probe_at[slot] = 0
            self._probe_gap[slot] = 0
            self._ng_idx[slot].clear()
            self._ng_done[slot] = 0
            self.resumes += 1
            resumed += 1
            if tel:
                tel.note_restore(rec.req.rid, t0, tel.clock())
                tel.note_resume(rec.req.rid, slot, "restore")
        return resumed

    # -- fatal faults: shutdown vs crash recovery --------------------------------------

    def fail_inflight(self, cause: BaseException) -> int:
        """Terminate every request the batcher still owes an outcome —
        active slots, mid-admission, parked, queued — with typed events
        (Errored for work in flight, Cancelled for work never admitted)
        so no consumer waits out a drain timeout.  Called on a fatal
        fault once recovery is off the table; deliberately touches NO
        device state (the fault may have consumed the donated buffers).
        Returns the number of requests terminated."""
        n = 0
        for i, r in enumerate(self._slot_req):
            if r is not None:
                self._fail_request(r, TerminalEvent.errored(r.rid, cause))
                self._slot_req[i] = None
                self.retired += 1
                n += 1
        if self.paged:
            while self._admitting:
                a = self._admitting.popleft()
                self._fail_request(a.req,
                                   TerminalEvent.errored(a.req.rid, cause))
                self.retired += 1
                n += 1
            while self._preempted:
                rec = self._preempted.pop()
                self._fail_request(rec.req,
                                   TerminalEvent.errored(rec.req.rid, cause))
                self.retired += 1
                n += 1
        while True:
            r = self._pending.popleft() if self._pending \
                else self.requests.TryPop()
            if r is None:
                break
            self._fail_request(r, TerminalEvent.cancelled(
                r.rid, "batcher shut down before admission"))
            self.retired += 1
            n += 1
        return n

    def _rebuild_paged_state(self) -> None:
        """Fresh device pools + allocators + block tables + slot state
        after a fatal step fault (the donated buffers are gone).  The
        host tier (``self._tiers``) survives — its payloads are host
        copies gathered before the fault and still exact; the prefix
        index is rebuilt empty (its pages died with the pools)."""
        i32 = jnp.int32
        n_slots = self.n_slots
        self._alloc = {name: PageAllocator(n)
                       for name, n in self.n_pages.items()}
        self._slot_pages = {name: [[] for _ in range(n_slots)]
                            for name in self.n_pages}
        self._slot_nshared = {name: [0] * n_slots for name in self.n_pages}
        if self._prefix is not None:
            self._prefix = PrefixIndex(
                [g.name for g in self.layout.groups],
                self.page_size, self.prefix_block)
        self.pools = PP.init_params(
            registry.paged_cache_decls(self.cfg, self.n_pages,
                                       self.page_size),
            shardings=self._pool_ns)
        self.block_tab = {
            name: jnp.full((n_slots, self.n_blocks[name]),
                           self.n_pages[name], i32)
            for name in self.n_pages}
        self.last_tok = jnp.zeros((n_slots,), i32)
        self.pos = jnp.zeros((n_slots,), i32)
        self.remaining = jnp.zeros((n_slots,), i32)
        self.active = jnp.zeros((n_slots,), bool)
        if self._moe_load is not None and any(
                a.is_deleted() for a in jax.tree.leaves(self._moe_load)):
            # the failed call consumed the donated counts: start again
            self._moe_load = moe_load_init(self.cfg)
        if self._rep_ns is not None:
            if self._moe_load is not None:
                self._moe_load = jax.device_put(self._moe_load,
                                                self._rep_ns)
            self.block_tab = jax.device_put(self.block_tab, self._rep_ns)
            self.last_tok = jax.device_put(self.last_tok, self._rep_ns)
            self.pos = jax.device_put(self.pos, self._rep_ns)
            self.remaining = jax.device_put(self.remaining, self._rep_ns)
            self.active = jax.device_put(self.active, self._rep_ns)
        self._host_pos = [0] * n_slots
        self._host_last_tok = [0] * n_slots
        self._host_remaining = [0] * n_slots
        self._slot_seq = [0] * n_slots
        self._replay_skip = [0] * n_slots
        self._history = [[] for _ in range(n_slots)]
        self._accept_ewma = [1.0] * n_slots
        self._probe_at = [0] * n_slots
        self._probe_gap = [0] * n_slots
        self._ng_idx = [{} for _ in range(n_slots)]
        self._ng_done = [0] * n_slots
        self._admitting.clear()
        self._preempted = []

    def recover(self) -> int:
        """Crash recovery after a fatal step fault (``ServeSupervisor``
        calls this between run() attempts): journal every in-flight
        request as a recompute-mode record, rebuild the device pools
        from scratch, and resubmit the journal.  Greedy decode is
        deterministic and recompute-mode resume replays the
        already-emitted tokens with output pushes suppressed, so every
        surviving request's token stream is bit-identical to a
        fault-free run.  Returns the number of requests resubmitted."""
        if not self.paged:
            raise RuntimeError("recover() requires the paged batcher; "
                               "the dense path has no journaled replay")
        journal: List[_Preempted] = []
        for slot, r in enumerate(self._slot_req):
            if r is None:
                continue
            journal.append(_Preempted(
                req=r, pos=self._host_pos[slot],
                last_tok=self._host_last_tok[slot],
                remaining=self._host_remaining[slot],
                data={}, counts={}, seq=self._slot_seq[slot],
                mode="recompute", skip=self._replay_skip[slot]))
            self._slot_req[slot] = None
        # mid-admission: a resume re-journals its (recompute) record —
        # it still owes the same suppressed replay; a fresh admission
        # emitted nothing yet and simply re-queues, order preserved.
        fresh: List[Request] = []
        while self._admitting:
            a = self._admitting.popleft()
            if a.resume is not None:
                journal.append(a.resume)
            else:
                fresh.append(a.req)
        # parked records: spilled payloads died with nothing? No — they
        # are host copies and technically still valid, but their shared
        # prefix pages referenced the dead pools, so convert everything
        # to recompute: deterministic replay is always correct.
        for rec in self._preempted:
            rec.mode = "recompute"
            rec.data, rec.counts, rec.shared = {}, {}, {}
            journal.append(rec)
        self._rebuild_paged_state()
        self._preempted = journal
        self._pending.extendleft(reversed(fresh))
        self.restarts += 1
        self._stalled = False
        if self._telemetry:
            # same rid as the pre-fault events: the replayed request's
            # trace stitches to its original across the restart.
            for rec in journal:
                self._telemetry.note_recover_journal(
                    rec.req.rid, rec.pos, "recompute", self.restarts)
            for r in fresh:
                self._telemetry.event(r.rid, "recover_requeue",
                                      restart=self.restarts)
        return len(journal) + len(fresh)

    # -- T2 snapshots -------------------------------------------------------------------

    def save_tier_snapshot(self, path: Optional[str] = None
                           ) -> Optional[str]:
        """Persist the host tier to disk (T2): the live device index is
        flushed through ``demote`` first, so cached prefixes survive a
        batcher restart — a new batcher constructed with the same
        ``kv_tier_snapshot`` path serves its first system-prompt hit
        from the reloaded store with only the catch-up chunk.  Returns
        the path written, or None when the tier is disabled."""
        if self._tiers is None:
            return None
        p = path or self.tier_snapshot
        if not p:
            raise ValueError("no snapshot path: pass one or set "
                             "cfg.kv_tier_snapshot / tier_snapshot=")
        self._tiers.save(p, index=self._prefix, pools=self.pools)
        return p

    # -- dense bucketed admission -----------------------------------------------------

    def _bucket_for(self, plen: int) -> int:
        """Pad-to-power-of-two bucket for a prompt length.

        Recurrent families (ssm/hybrid) fall back to exact length:
        conv/ssd state reduces over the WHOLE padded sequence, so padding
        tokens would corrupt the state itself, which no ``last_pos``
        gather can fix.  Attention caches are safe for ANY bucket —
        padded positions are masked or (sliding window) excluded by the
        mask-aware ring emission — so windowed configs now bucket too."""
        if self.cfg.family in ("ssm", "hybrid"):
            return plen
        return min(max(_MIN_BUCKET, _next_pow2(plen)), self.max_seq)

    def _admit_fn(self, bucket: int) -> Callable:
        """Per-bucket jitted admission program.  The LRU bound lives on
        the module-level ``_make_admit_fn`` cache; ``prefill_compiles``
        counts actual factory misses (each product traces exactly once,
        since its input shapes are fixed by the bucket), so the metric
        reflects real XLA compilations, not per-instance lookups."""
        before = _make_admit_fn.cache_info().misses
        fn = _make_admit_fn(self.cfg, self.max_seq, self.n_slots, bucket)
        if _make_admit_fn.cache_info().misses > before:
            self.prefill_compiles += 1
        return fn

    def _admit_batch(self, pairs: Sequence[Tuple[int, Request]]) -> None:
        """Admit (slot, request) pairs; one padded prefill per bucket.

        Every admission call runs at a fixed n_slots rows (unused rows
        are zero prompts whose results scatter-drop): one compiled shape
        per bucket keeps the log2(max_seq) compile bound, at the cost of
        up to (n_slots-1)/n_slots wasted prefill FLOPs when admitting a
        single request.  The paged path's chunked prefill is the fix;
        this is the dense fallback."""
        groups: Dict[int, List[Tuple[int, Request]]] = {}
        for slot, r in pairs:
            if len(r.prompt) >= self.max_seq:
                self._reject(r)    # bypassed submit() validation
                continue
            groups.setdefault(self._bucket_for(len(r.prompt)),
                              []).append((slot, r))
        for bucket, grp in groups.items():
            fn = self._admit_fn(bucket)
            prompts = np.zeros((self.n_slots, bucket), np.int32)
            lens = np.ones((self.n_slots,), np.int32)
            slot_idx = np.full((self.n_slots,), self.n_slots, np.int32)
            max_new = np.ones((self.n_slots,), np.int32)
            for row, (slot, r) in enumerate(grp):
                p = np.asarray(r.prompt, np.int32)
                prompts[row, :len(p)] = p
                lens[row] = len(p)
                slot_idx[row] = slot
                max_new[row] = r.max_new
            (self.cache, self.last_tok, self.pos, self.remaining,
             self.active, tok0) = fn(
                self.params, self.cache, self.last_tok, self.pos,
                self.remaining, self.active, jnp.asarray(prompts),
                jnp.asarray(lens), jnp.asarray(slot_idx),
                jnp.asarray(max_new))
            tok0 = np.asarray(tok0)           # (n_slots,) int32
            for row, (slot, r) in enumerate(grp):
                r.out.Push(int(tok0[row]))
                tel = self._telemetry
                if tel:
                    tel.note_first_token(r.rid, slot, tel.clock(),
                                         pos=len(r.prompt))
                if r.max_new > 1 and len(r.prompt) < self.max_seq - 1:
                    self._slot_req[slot] = r
                else:                          # retired at admission
                    r.out.close()
                    self.retired += 1
                    if tel:
                        tel.note_retire(r.rid, slot)

    # -- scheduling ---------------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Validate + enqueue; returns True iff the request entered the
        queue.  Degenerate requests are rejected HERE, in the producer's
        thread, with a clear error — instead of burning a slot and pages
        on an admission whose slot is immediately non-alive (or one bad
        request killing the batcher PE mid-flight with other requests in
        its slots):

        * ``prompt >= max_seq - 1``: prefill would leave no room to
          decode even one token past the first.
        * ``max_new <= 1``: the request retires at admission (its single
          token comes from the prefill itself) — a full prefill for a
          dead slot.

        A raised ``ValueError`` also pushes the typed Rejected event +
        close into ``req.out``, so a consumer thread that never sees the
        producer's exception still terminates.

        Overload policy (``overload=`` / ``cfg.serve_overload``): with a
        full request queue, ``"block"`` (default) backpressures the
        producer — the hlslib bounded-FIFO behavior — while ``"reject"``
        sheds the request instead: a typed ``queue_full`` rejection, no
        blocking, return False.  Shed requests never entered the
        pipeline, so they do NOT count toward ``retired`` (run(total)
        totals must count only requests the batcher owes a terminal
        outcome)."""
        reason = None
        if len(req.prompt) >= self.max_seq - 1:
            reason = (f"prompt length {len(req.prompt)} >= max_seq - 1 "
                      f"({self.max_seq - 1}); no decode budget left")
        elif req.max_new <= 1:
            reason = (f"max_new={req.max_new} <= 1 would retire at "
                      f"admission; request at least 2 tokens")
        if reason is not None:
            self._fail_request(req, TerminalEvent.rejected(
                req.rid, f"invalid: {reason}"))
            raise ValueError(f"request {req.rid}: {reason}")
        req.submitted_at = self._clock()
        if self._telemetry:
            self._telemetry.note_submit(req)
        if req.deadline_ms is not None:
            self._deadlines_live = True
        if self.overload == "reject":
            if not self.requests.TryPush(req):
                self._fail_request(req, TerminalEvent.rejected(
                    req.rid, "queue_full"))
                return False
        else:
            self.requests.Push(req)
        return True

    def _schedule_pending(self) -> None:
        """SLA mode: drain every queued arrival into ``_pending`` and
        keep it ordered by (class rank desc, deadline asc, submit
        order) — a latency-class arrival overtakes queued batch work.
        The sort is stable against the original submit order so
        equal-SLA requests still serve FIFO."""
        while True:
            r = self.requests.TryPop()
            if r is None:
                break
            if r.submitted_at == 0.0:
                r.submitted_at = self._clock()
                if self._telemetry:
                    self._telemetry.note_submit(r)
            if r.deadline_ms is not None:
                self._deadlines_live = True
            self._pending.append(r)
        if len(self._pending) > 1:
            self._pending = collections.deque(sorted(
                self._pending,
                key=lambda r: (-class_rank(r.klass),
                               r.deadline_ms if r.deadline_ms is not None
                               else float("inf"),
                               r.submitted_at, r.rid)))

    def _backlog_tokens(self) -> int:
        """Tokens of work already owed ahead of a new admission (active
        decode budgets + admitting prefill remainders + parked work) —
        the load-shedding delay model's numerator."""
        t = sum(self._host_remaining[i]
                for i, r in enumerate(self._slot_req) if r is not None)
        t += sum((a.n_chunks - a.next_chunk) * self.chunk + a.req.max_new
                 for a in self._admitting)
        t += sum(rec.remaining for rec in self._preempted)
        return t

    def _note_rate(self, dt: float, toks: int) -> None:
        """Fold one decode/verify step into the smoothed throughput
        model: wall time AND tokens actually retired (a speculative
        step commits several per slot; a half-empty batch commits fewer
        than n_slots)."""
        self._ewma_step_s = (dt if self._ewma_step_s == 0.0
                             else 0.8 * self._ewma_step_s + 0.2 * dt)
        self._ewma_step_tok = (float(toks) if self._ewma_step_tok == 0.0
                               else 0.8 * self._ewma_step_tok + 0.2 * toks)

    def _projected_delay_ms(self) -> float:
        """Projected queueing delay for a new admission: backlog tokens
        at the smoothed measured throughput (tokens retired per step,
        NOT steps x n_slots — the old per-step model undercounted when
        slots sat empty and overcounts under speculative multi-token
        commits)."""
        return (self._ewma_step_s * 1e3
                * self._backlog_tokens() / max(self._ewma_step_tok, 1.0))

    def admit(self) -> int:
        """Fill free slots: resume preempted requests first, then pop the
        request stream.

        Paged: each placed request reserves its admission pages (or
        waits — admission backpressure) and enters chunked prefill.
        Dense: one batched padded prefill per bucket.

        Lifecycle gates run here, before any slot or page is spent: a
        request whose deadline already passed in the queue expires
        (typed event), and — paged SLA mode — batch-class work whose
        remaining deadline budget is smaller than the projected queue
        delay is load-shed with a typed ``deadline_unmeetable``
        rejection rather than admitted to miss it."""
        if self.schedule == "sla":
            self._schedule_pending()
        if not self.paged:
            free = [i for i, r in enumerate(self._slot_req) if r is None]
            pairs: List[Tuple[int, Request]] = []
            while len(pairs) < len(free):
                r = self._next_request()
                if r is None:
                    break
                if self._expiry_left_ms(r) <= 0:
                    self._fail_request(r, TerminalEvent.expired(
                        r.rid, "deadline passed in queue"))
                    self.retired += 1
                    continue
                pairs.append((free[len(pairs)], r))
            if pairs:
                self._admit_batch(pairs)
            return len(pairs)
        admitted = self._try_resume()
        busy = {a.slot for a in self._admitting}
        free = [i for i, r in enumerate(self._slot_req)
                if r is None and i not in busy]
        fi = 0
        while fi < len(free):
            r = self._next_request()
            if r is None:
                break
            left = self._expiry_left_ms(r)
            if left <= 0:
                self._fail_request(r, TerminalEvent.expired(
                    r.rid, "deadline passed in queue"))
                self.retired += 1
                continue
            if (self.schedule == "sla" and r.klass == "batch"
                    and left < self._projected_delay_ms()):
                self._fail_request(r, TerminalEvent.rejected(
                    r.rid, "deadline_unmeetable"))
                self.retired += 1
                continue
            if len(r.prompt) >= self.max_seq or r.max_new < 1:
                self._reject(r)    # bypassed submit() validation
                continue
            if any(self._full_pages_needed(r, g.name) > self.n_pages[g.name]
                   for g in self.layout.groups):
                self._reject(r)    # can never fit, even in an empty pool
                continue
            if not self._try_admit_paged(r, free[fi]):
                # pool dry: hold the request at the FIFO head until a
                # retire frees pages — never an error.
                self._pending.appendleft(r)
                break
            fi += 1
            admitted += 1
        return admitted

    def _cancel_expired_slots(self) -> int:
        """Cancel in-flight requests whose deadline passed: typed
        Expired event (with the partial tokens already streamed), pages
        freed IMMEDIATELY — a dead SLA must not hold pool capacity."""
        n = 0
        for i, r in enumerate(self._slot_req):
            if r is None or self._expiry_left_ms(r) > 0:
                continue
            self._fail_request(r, TerminalEvent.expired(
                r.rid, "deadline passed mid-decode"))
            self.active = self.active.at[i].set(False)
            self._slot_req[i] = None
            if self.paged:
                self._release_slot(i)
                self._replay_skip[i] = 0
            self.retired += 1
            n += 1
        return n

    # -- speculative decode (draft / verify / commit-or-rollback) ----------------------

    def _draft(self, slot: int) -> List[int]:
        """Self-speculative n-gram draft for one slot: find the most
        recent earlier occurrence of the history's trailing
        ``speculate_ngram``-gram and propose the tokens that followed
        it.  No
        second model — repetitive continuations (code, templated text,
        greedy cycles) hit; novel text simply returns no draft.  A slot
        whose acceptance EWMA fell below ``speculate_min_accept`` stops
        drafting (self-disable) but *re-probes* after ``speculate_probe``
        steps — a probe that accepts well re-enables speculation (text
        that turned repetitive mid-request, e.g. a greedy cycle settling
        in), while failed probes back off exponentially so adversarial
        workloads pay a vanishing verify overhead."""
        probing = self._accept_ewma[slot] < self.speculate_min_accept
        if probing and not (
                self.speculate_probe
                and self.steps >= self._probe_at[slot]
                and self.steps % self.speculate_probe == 0):
            # probes only fire on the global step grid so several
            # disabled slots share one verify round instead of each
            # paying their own.
            return []
        cap = min(self.speculate_k, self._host_remaining[slot] - 1)
        if cap <= 0:
            return []
        hist = self._history[slot]
        n = len(hist)
        # the FULL trailing speculate_ngram must match — shorter matches
        # on novel text are overwhelmingly single-token coincidences
        # whose drafts get rejected, and each one burns a full-priced
        # verify round before the EWMA can learn anything.  Repetitive
        # text reaches an ngram-length repeat within a few tokens of the
        # cycle starting, so requiring the full context costs it at most
        # a round or two of onset.
        ng = self.speculate_ngram
        if n > ng:
            # extend the incremental position index over the tokens
            # appended since the last call (the index is cleared
            # whenever the history is replaced: admission, resume,
            # recovery), then look the trailing ngram up.
            idx = self._ng_idx[slot]
            done = self._ng_done[slot]
            if done > n - ng:
                idx.clear()
                done = 0
            for j in range(done, n - ng):
                idx.setdefault(tuple(hist[j:j + ng]), []).append(j)
            self._ng_done[slot] = max(done, n - ng)
            posns = idx.get(tuple(hist[n - ng:]))
            if posns:
                # most recent occurrence whose continuation fills the
                # WHOLE span (j + ng + cap <= n) — an occurrence near
                # the history end (periodic text: every position
                # matches) only supplies a truncated draft.  Short
                # drafts are not proposed at all: the verify span costs
                # the same k+1 positions regardless, so a 1-2 token
                # draft can't pay for its round.
                i = bisect.bisect_right(posns, n - ng - cap) - 1
                if i >= 0:
                    j = posns[i]
                    return hist[j + ng:j + ng + cap]
        if probing:
            # the probe asked "has the text become draftable?" and the
            # scan answered no — consume the probe and back off just
            # like a failed round, except this one cost nothing.
            # Without this, adversarial text keeps probe_at pinned at
            # its last value until a stray n-gram match appears, and
            # the match fires a full-priced verify round every time.
            self._probe_gap[slot] = max(2 * self._probe_gap[slot],
                                        self.speculate_probe // 2, 1)
            self._probe_at[slot] = self.steps + self._probe_gap[slot]
        return []

    def _collect_drafts(self) -> Dict[int, List[int]]:
        """Drafts for every decoding slot; an empty dict sends the step
        down the plain decode path.  The verify span statically writes
        ``speculate_k + 1`` positions per ACTIVE slot (pad rows included),
        so the whole batch must satisfy ``pos + speculate_k <= max_seq -
        2`` — any slot that close to the end forces a plain step (its
        final tokens aren't worth speculating anyway)."""
        lim = self.max_seq - 2 - self.speculate_k
        drafts: Dict[int, List[int]] = {}
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            if self._host_pos[i] > lim:
                return {}
            d = self._draft(i)
            if d:
                drafts[i] = d
        return drafts

    def _spec_setup(self, drafts: Dict[int, List[int]]):
        """Plan the scratch redirection for every drafting slot's
        verify span — freshly allocated private scratch pages (old
        contents copied in) so speculative KV writes can never touch a
        shared/refcounted page or a CoW boundary.  HOST-ONLY: the copy
        and table swap execute *inside* the verify jit from the padded
        index arrays built here, so the batcher's own device table is
        never mutated by speculation and rollback costs nothing.

        Per slot and group, the span covers logical pages
        ``pos // page .. (pos + k) // page`` (k = speculate_k; the span
        writes positions pos..pos+k).  Records are ``(group, logical,
        entry, old_page | None, scratch_page)``; entries without an
        allocated page yet record ``old = None`` (nothing to copy).  A
        slot whose scratch allocation fails simply drops its draft —
        speculation never preempts and never backpressures; ``drafts``
        is pruned in place.

        Returns ``(swaps, xfer)`` where xfer is the 5-tuple of padded
        per-group arrays ``(copy_src, copy_dst, swap_rows, swap_cols,
        swap_vals)`` with fixed length (compile-stable): copy padding
        points dst at ``n_pages`` (scatter-dropped) and swap padding
        points rows at ``n_slots`` (ditto)."""
        k_span = self.speculate_k + 1
        swaps: Dict[int, List[Tuple[str, int, int, Optional[int], int]]] = {}
        for slot in list(drafts):
            pos = self._host_pos[slot]
            recs: List[Tuple[str, int, int, Optional[int], int]] = []
            ok = True
            for g in self.layout.groups:
                name = g.name
                nb = self.n_blocks[name]
                pages = self._slot_pages[name][slot]
                for l in range(pos // self.page_size,
                               (pos + k_span - 1) // self.page_size + 1):
                    j = l % nb if g.ring else l
                    if j >= nb:          # flat span past the table —
                        continue         # impossible under the pos gate
                    got = self._alloc_evict(name, 1)
                    if got is None:
                        ok = False
                        break
                    old = pages[j] if j < len(pages) else None
                    recs.append((name, l, j, old, got[0]))
                if not ok:
                    break
            if not ok:       # dry pool: free grabbed scratch, drop draft
                for name, _, _, _, scr in recs:
                    self._alloc[name].free([scr])
                del drafts[slot]
                continue
            swaps[slot] = recs
        cap = self.n_slots * ((k_span - 1) // self.page_size + 2)
        copy_src, copy_dst = {}, {}
        rows, cols, vals = {}, {}, {}
        fill = {}
        for g in self.layout.groups:
            copy_src[g.name] = np.zeros(cap, np.int32)
            copy_dst[g.name] = np.full(cap, self.n_pages[g.name], np.int32)
            rows[g.name] = np.full(cap, self.n_slots, np.int32)
            cols[g.name] = np.zeros(cap, np.int32)
            vals[g.name] = np.zeros(cap, np.int32)
            fill[g.name] = 0
        for slot, recs in swaps.items():
            for name, _, j, old, scr in recs:
                i = fill[name]
                fill[name] = i + 1
                rows[name][i] = slot
                cols[name][i] = j
                vals[name][i] = scr
                if old is not None:
                    copy_src[name][i] = old
                    copy_dst[name][i] = scr
        self._note_peak()
        return swaps, (copy_src, copy_dst, rows, cols, vals)

    def _spec_unwind(self, swaps) -> None:
        """Abort path (injected verify fault / jit failure): free every
        scratch page so the allocator stays consistent for
        fail_inflight/recover.  The device table was never touched (the
        swap lives inside the failed jit call), so there is nothing to
        restore."""
        for _, recs in swaps.items():
            for name, _, _, _, scr in recs:
                self._alloc[name].free([scr])

    def _spec_resolve(self, swaps, commit: np.ndarray) -> None:
        """Commit-or-rollback by block-table swap.  Pages holding
        committed positions (logical <= page of ``pos + commit - 1``)
        swap their scratch page into the slot's page list AND the device
        table (the old page, if any, is freed); pages beyond simply free
        the scratch — the device table never saw them.  A committed page
        may still carry rejected rows past the commit point — those
        positions are causally masked on every read until sequential
        decode overwrites them."""
        updates: Dict[str, List[Tuple[int, int, int]]] = {}
        for slot, recs in swaps.items():
            c = int(commit[slot])
            last_page = (self._host_pos[slot] + c - 1) // self.page_size
            for name, l, j, old, scr in recs:
                pages = self._slot_pages[name][slot]
                if c > 0 and l <= last_page:           # commit
                    if j < len(pages):
                        if old is not None:
                            self._alloc[name].free([old])
                        pages[j] = scr
                    else:
                        assert j == len(pages)
                        pages.append(scr)
                    updates.setdefault(name, []).append((slot, j, scr))
                else:                                  # rollback (free)
                    self._alloc[name].free([scr])
        self._scatter_tab(updates)

    def _scatter_tab(self, updates: Dict[str, List[Tuple[int, int, int]]]
                     ) -> None:
        """One batched block-table entry scatter per group."""
        for name, items in updates.items():
            self.block_tab[name] = self.block_tab[name].at[
                np.asarray([s for s, _, _ in items], np.int32),
                np.asarray([j for _, j, _ in items], np.int32)].set(
                np.asarray([v for _, _, v in items], np.int32))

    def _spec_step(self, drafts: Dict[int, List[int]], swaps,
                   xfer) -> int:
        """One batched draft-verify-commit step covering ALL active
        slots: drafting slots feed [last_tok, d_1..d_n, pad...], the
        rest feed [last_tok, pad...] (n_draft = 0 -> commit exactly 1 =
        plain decode), so mixed batches share one compiled program.
        Commit/rollback bookkeeping mirrors ``step()`` but advances
        every host mirror by the per-slot commit count."""
        k = self.speculate_k + 1
        n = self.n_slots
        tokens = np.zeros((n, k), np.int32)
        n_draft = np.zeros((n,), np.int32)
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            tokens[i, 0] = self._host_last_tok[i]
            d = drafts.get(i, ())
            tokens[i, 1:1 + len(d)] = d
            tokens[i, 1 + len(d):] = tokens[i, len(d)]   # pad (masked)
            n_draft[i] = len(d)
        tel = self._telemetry
        tel_t0 = tel.clock() if tel else 0.0
        t0 = time.monotonic()
        try:
            # injected verify fault fires AFTER scratch setup — the
            # unwind below must leave the allocator consistent.
            self._fault.check("verify")
            copy_src, copy_dst, rows, cols, vals = xfer
            with (tel.span(ENGINE_RID, "serve.verify") if tel
                  else _NULLCTX):
                (self.pools, self.last_tok, self.pos, self.remaining,
                 self.active, out) = self._verify(
                    self.params, self.pools, self.block_tab,
                    jnp.asarray(tokens), jnp.asarray(n_draft),
                    self.pos, self.remaining, self.active,
                    copy_src, copy_dst, rows, cols, vals)
        except Exception as e:
            self._spec_unwind(swaps)
            raise BatcherFault(e) from e
        # the ONLY per-step transfer
        with (tel.span(ENGINE_RID, "serve.readback") if tel else _NULLCTX):
            out = np.asarray(out)
        dt = time.monotonic() - t0
        t_round = 0.0
        if tel:
            t_round = tel.clock()
            tel.note_verify_round(tel_t0, t_round,
                                  n_drafting=int((n_draft > 0).sum()))
        preds, commit, finished = out[:k], out[k], out[k + 1]
        self._spec_resolve(swaps, commit)
        done = 0
        committed = 0
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            c = int(commit[i])
            for t in range(c):
                tok = int(preds[t, i])
                if self._replay_skip[i] > 0:
                    self._replay_skip[i] -= 1
                else:
                    r.out.Push(tok)
                    if tel:
                        # every committed token of the round shares its
                        # end stamp: they genuinely arrive together.
                        tel.note_token(r.rid, i, t_round,
                                       pos=self._host_pos[i] + t)
                self._history[i].append(tok)
            self._host_last_tok[i] = int(preds[c - 1, i])
            self._host_pos[i] += c
            self._host_remaining[i] -= c
            committed += c
            nd = int(n_draft[i])
            if nd:
                acc = c - 1
                if tel:
                    tel.note_spec(r.rid, i, nd, acc)
                self.spec_drafted += nd
                self.spec_accepted += acc
                self.spec_rolled_back += nd - acc
                floor = self.speculate_min_accept
                if self._accept_ewma[i] < floor:
                    # probe round: re-enable only on decisively good
                    # acceptance (2x the disable floor — hysteresis, so
                    # a marginal probe can't oscillate the drafter
                    # on/off), and back off exponentially while probes
                    # keep failing.
                    bar = min(1.0, 2.0 * floor)
                    good = acc >= bar * nd
                    self._accept_ewma[i] = acc / nd if good else 0.0
                    if not good:
                        self._probe_gap[i] *= 2
                        self._probe_at[i] = self.steps + self._probe_gap[i]
                elif acc == 0:
                    # a fully rejected span is maximal evidence — don't
                    # wait for the blend to drift below the floor, a
                    # second wasted verify round costs more than the
                    # risk of a probe re-enabling a good drafter.
                    self._accept_ewma[i] = 0.25 * self._accept_ewma[i]
                    if self._accept_ewma[i] < floor:
                        self._probe_gap[i] = max(self.speculate_probe // 2, 1)
                        self._probe_at[i] = self.steps + self._probe_gap[i]
                else:
                    self._accept_ewma[i] = (0.5 * self._accept_ewma[i]
                                            + 0.5 * (acc / nd))
                    if self._accept_ewma[i] < floor:
                        # just disabled: schedule the first probe for
                        # the next grid tick (gap of half a period, so
                        # ``steps % probe == 0`` doesn't skip it).
                        self._probe_gap[i] = max(self.speculate_probe // 2, 1)
                        self._probe_at[i] = self.steps + self._probe_gap[i]
            if finished[i]:
                r.out.close()
                self._slot_req[i] = None
                self._release_slot(i, prompt=r.prompt)
                done += 1
                if tel:
                    tel.note_retire(r.rid, i)
        self.spec_verify_steps += 1
        self.steps += 1
        self.retired += done
        self._note_rate(dt, committed)
        return done

    def step(self) -> int:
        """One batched decode step; returns number of sequences retired.

        Paged + lazy growth: before the jitted step, every decoding
        slot's block tables are grown to cover its next write position —
        allocating pages on demand and preempting the lowest-priority
        slot if the pool is dry.

        A failure inside (or injected before) the jitted call is FATAL
        for the batcher — the donated device state is unrecoverable in
        place — and surfaces as ``BatcherFault``; under a
        ``ServeSupervisor`` the in-flight requests are journaled,
        pools rebuilt, and the journal replayed (``recover``)."""
        tel = self._telemetry
        if self._deadlines_live:
            self._cancel_expired_slots()
        if self.paged and not self.reserve_decode:
            if tel:
                with tel.span(ENGINE_RID, "serve.grow_tables") as sp:
                    preemptions = self.preemptions
                    sp.set(pages=self._grow_tables(),
                           preemptions=self.preemptions - preemptions)
            else:
                self._grow_tables()
        if all(r is None for r in self._slot_req):
            return 0
        if self.paged and self.speculate_k:
            drafts = self._collect_drafts()
            if drafts:
                swaps, xfer = self._spec_setup(drafts)
                if drafts:       # setup may prune drafts (dry pool)
                    return self._spec_step(drafts, swaps, xfer)
        n_live = sum(1 for r in self._slot_req if r is not None)
        if tel and self.paged:
            self._count_decode_read()
        tel_t0 = tel.clock() if tel else 0.0
        t0 = time.monotonic()
        try:
            self._fault.check("step")
            with (tel.span(ENGINE_RID, "serve.decode_step", step=self.steps,
                           n_live=n_live) if tel else _NULLCTX):
                if self.paged:
                    (self.pools, self.last_tok, self.pos, self.remaining,
                     self.active, out) = self._counted(
                        self._step, self.params, self.pools, self.block_tab,
                        self.last_tok, self.pos, self.remaining,
                        self.active)
                else:
                    (self.cache, self.last_tok, self.pos, self.remaining,
                     self.active, out) = self._step(
                        self.params, self.cache, self.last_tok, self.pos,
                        self.remaining, self.active)
        except Exception as e:
            raise BatcherFault(e) from e
        # the ONLY per-step transfer; the step ends when its tokens are
        # on the host.
        with (tel.span(ENGINE_RID, "serve.readback") if tel else _NULLCTX):
            out = np.asarray(out)
        self._note_rate(time.monotonic() - t0, n_live)
        if tel:
            t_step = tel.clock()
            tel.note_decode_step(tel_t0, t_step, n_live)
            with tel.span(ENGINE_RID, "serve.emit") as sp:
                pushed, done = self._emit_step(out, t_step)
                sp.set(tokens=pushed, retired=done)
        else:
            done = self._emit_step(out, 0.0)[1]
        self.steps += 1
        self.retired += done
        return done

    def _counted(self, fn, *args):
        """Call a paged step program, threading the expert load through
        it where the batcher keeps one (the load is its last argument
        and output)."""
        if self._moe_load is None:
            return fn(*args)
        *out, self._moe_load = fn(*args, self._moe_load)
        return tuple(out)

    def _count_decode_read(self) -> None:
        """Add a decode call's read to ``stats()["decode_read"]``, from
        the host's positions: the table entries holding each live slot's
        positions 0..pos (a ring group's at most its width; the kernel
        walks these, less the pages before a flat window), and the
        table's width."""
        for name, width in self.n_blocks.items():
            for i, r in enumerate(self._slot_req):
                if r is not None:
                    self.decode_live_pages += self.layout.blocks_for(
                        name, self._host_pos[i] + 1, self.max_seq)
                    self.decode_table_pages += width

    def _grow_tables(self) -> int:
        """Lazy growth before a decode step: every decoding slot's block
        tables cover its next write position (``_grow_slot``).  Returns
        the pages taken."""
        return sum(self._grow_slot(slot) for slot in range(self.n_slots)
                   if self._slot_req[slot] is not None)

    def _emit_step(self, out: np.ndarray, ts: float) -> Tuple[int, int]:
        """Hand a decode step's tokens (``out``: tokens, finished flags)
        to their requests' streams, stamped ``ts``, and retire the
        finished ones.  Returns (tokens pushed, requests retired)."""
        tel = self._telemetry
        toks, finished = out[0], out[1]
        pushed = done = 0
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            if self.paged and self._replay_skip[i] > 0:
                # recompute-mode resume replay: this token already
                # reached the consumer before the preemption — the step
                # only rebuilds its KV through the decode path.
                self._replay_skip[i] -= 1
            else:
                r.out.Push(int(toks[i]))
                pushed += 1
                if tel:
                    tel.note_token(
                        r.rid, i, ts,
                        pos=self._host_pos[i] if self.paged else -1)
            if self.paged:
                self._host_last_tok[i] = int(toks[i])
                self._host_pos[i] += 1
                self._host_remaining[i] -= 1
                if self.speculate_k:
                    self._history[i].append(int(toks[i]))
            if finished[i]:
                r.out.close()
                self._slot_req[i] = None
                if self.paged:
                    # retire: the prompt's full pages are offered to the
                    # prefix cache (decref instead of free) so a later
                    # identical prefix skips its prefill.
                    self._release_slot(i, prompt=r.prompt)
                done += 1
                if tel:
                    tel.note_retire(r.rid, i)
        return pushed, done

    def run(self, total_requests: int, *, poll_timeout: float = 1.0) -> None:
        """Batcher PE: admit + decode until ``total_requests`` retire.

        Paged mode interleaves chunked prefill with decode:
        ``prefill_interleave`` decode steps run between consecutive
        prompt chunks (0 = prefill drains before any decode), so a long
        admission never freezes in-flight slots for a full prefill.

        When everything is idle the batcher blocks on the request stream
        with a timeout + re-check loop (never an unbounded ``Pop``): if a
        producer dies without closing the stream, the batcher keeps
        polling instead of deadlocking, and a closed stream ends the
        loop cleanly.  An idle-path arrival is re-queued through
        ``admit()`` so the allocator — not a hardcoded slot — picks its
        placement.  Preempted requests count as pending work: the loop
        never blocks (or exits on a closed stream) while any wait to
        resume.

        A ``BatcherFault`` escaping the loop body is fatal: when
        unsupervised, every in-flight request is errored (typed events —
        no consumer hangs) before it propagates; under a
        ``ServeSupervisor`` the fault propagates as-is and the
        supervisor drives ``recover()``/``fail_inflight``."""
        tel = self._telemetry
        decodes_since_chunk = 0
        try:
            while self.retired < total_requests:
                if self._heartbeat is not None:
                    self._heartbeat.beat("batcher")
                if self._stalled:
                    raise BatcherFault(StallFault(
                        "batcher run loop missed its heartbeat window"))
                if tel:
                    with tel.span(ENGINE_RID, "serve.admit") as sp:
                        retired = self.retired    # expired or rejected
                        sp.set(admitted=self.admit(),
                               expired_or_rejected=self.retired - retired)
                else:
                    self.admit()
                busy = any(r is not None for r in self._slot_req)
                if self.paged and self._admitting:
                    if busy and decodes_since_chunk < self.prefill_interleave:
                        self.step()
                        decodes_since_chunk += 1
                    else:
                        self._prefill_step()
                        decodes_since_chunk = 0
                    continue
                if busy:
                    self.step()
                    continue
                if self._pending or (self.paged and self._preempted):
                    continue       # waiting on pages with idle slots:
                                   # admit() above will retry/reject.
                try:
                    with (tel.span(ENGINE_RID, "serve.idle_wait") if tel
                          else _NULLCTX):
                        r = self.requests.Pop(timeout=poll_timeout)
                except TimeoutError:
                    continue               # re-check; producer may be slow
                except StreamClosed:
                    return                 # no more work will ever arrive
                self._pending.appendleft(r)  # admit() places it next loop
        except BatcherFault as e:
            if not self._supervised:
                self.fail_inflight(e.cause)
            raise


def drain(req: Request, timeout: float = 30.0) -> List[int]:
    """Consumer PE helper: collect a request's full output stream.

    ``StreamClosed`` is the normal end-of-sequence signal.  A typed
    ``TerminalEvent`` in the stream (the batcher's in-band failure
    marker) re-raises as the matching ``RequestFailed`` subclass —
    carrying the partial tokens and chaining the original cause — so a
    failed request surfaces its real error immediately instead of
    timing out here 30 s later.  A timeout still means the batcher
    stalled without managing to say so."""
    out: List[int] = []
    while True:
        try:
            v = req.out.Pop(timeout=timeout)
        except StreamClosed:
            return out
        except TimeoutError:
            raise TimeoutError(
                f"drain(rid={req.rid}) timed out after {timeout:.0f}s with "
                f"{len(out)} token(s) received — batcher stalled or died")
        if isinstance(v, TerminalEvent):
            raise v.to_error(out) from v.cause
        out.append(v)
