"""Tiered KV memory: device pools (T0), a host-RAM prefix/spill store
(T1), and on-disk snapshots (T2).

hlslib's core move is packaging the memory-hierarchy plumbing every
design rewrites by hand — burst-friendly memory adapters, inter-stage
FIFOs — as reusable plug-in modules.  The serving analogue: every
consumer of the page pool (prefix-cache eviction, preemption spill)
used to hand-roll its own blocking device<->host copies.  This module
owns ALL page movement between tiers:

* ``StagedTransferEngine`` — batched, double-buffered device<->host
  page transfers.  A spill dispatches ONE device-side gather per pool
  leaf (every page of every group in a single ``take``) before the
  first device->host copy blocks, so the copy of leaf *k* overlaps the
  gather of leaf *k+1*; a restore stages every host payload onto the
  device (async H2D) before the first scatter runs.  This replaces the
  per-page, per-group blocking round-trips the batcher used to issue.
  Leaf dtypes are preserved end-to-end: int8 pages spill as int8 with
  their bf16 scale pages intact, and the layout's ``restore_pages``
  *raises* on a dtype mismatch instead of silently casting.

* ``HostPageStore`` (T1) — a bounded host-RAM page store.  Entries are
  content-addressed by a digest of the FULL token path of a prefix
  block (the same radix-path identity ``PrefixIndex`` uses, hashed to
  a fixed-size key), each holding the host copies of the
  ``pages_per_block`` physical pages of every page group.
  The store LRU-evicts under its own byte budget; entries are plain
  host buffers — T1 never holds device page references, so its
  eviction can never strand a refcounted device page.

* ``KVTierManager`` — the facade the batcher talks to:
  - ``demote``: prefix-cache eviction hands the evicted node's pages
    here *before* freeing them; the payload is gathered to T1 so a
    later identical prompt restores instead of recomputing.
  - ``match``/``restore_chain``: admission promotes the longest T1
    block chain missing from the device index — pages are allocated,
    payloads scattered back in one staged transfer, and the blocks
    re-inserted into the ``PrefixIndex`` so the normal shared-page
    admission path (incref, CoW, catch-up chunk) takes over.
  - ``save``/``load`` (T2): pickle the T1 store — optionally flushing
    the live device index through ``demote`` first — so cached system
    prompts survive batcher restarts: a restarted batcher's first
    admission promotes from the loaded store and pays only the
    catch-up chunk.

The recompute-vs-restore policy (``tier_restore_min_tokens``) lives in
the batcher: spans shorter than the knob are cheaper to recompute from
tokens than to stage through host RAM, so short rehits fall through to
plain prefill and short preempted sequences park as recompute records
(re-admission + suppressed-output decode replay) instead of spilling.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.params import Decl
from .resilience import FaultPlan

# v2 wraps the payload in a {version, sha256, blob} envelope so load can
# verify content integrity before unpickling the payload proper.
_SNAPSHOT_VERSION = 2


class SnapshotCorruptError(RuntimeError):
    """A T2 snapshot failed integrity verification (truncated file,
    checksum mismatch, unreadable pickle).  Callers treat this as a
    logged cold start — unlike a geometry mismatch (``ValueError``),
    which means the snapshot is *valid but wrong for this layout* and
    keeps raising."""


def _tree_nbytes(tree) -> int:
    return sum(int(np.asarray(leaf).nbytes) for leaf in jax.tree.leaves(tree))


def _flip_bit(path: str) -> None:
    """Simulated bit-rot: flip one bit in the middle of the file (inside
    the checksummed blob, so load's digest check must catch it)."""
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        mid = f.tell() // 2
        f.seek(mid)
        b = f.read(1)
        f.seek(mid)
        f.write(bytes([b[0] ^ 0x40]))


def _truncate_half(path: str) -> None:
    """Simulated torn write / partial copy: drop the file's second half."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)


def _content_key(tokens) -> bytes:
    """Content address of a prefix: SHA-1 digest of its canonical int64
    token bytes.  Fixed 20-byte keys keep the store's key memory O(1)
    per entry (a raw token-tuple key would hold the whole prefix —
    O(L^2) ints across a chain) and hash in O(L); ``KVTierManager.
    match`` computes the per-block digests incrementally, so a whole
    chain walk is O(L) too."""
    return hashlib.sha1(
        np.ascontiguousarray(np.asarray(tokens, np.int64)).tobytes()).digest()


class StagedTransferEngine:
    """Batched, double-buffered device<->host page movement.

    One engine per batcher serves every transfer consumer — preemption
    spill/resume, prefix demote to T1, T1 promote back to device — so
    the transfer counters in ``stats()`` describe all tier traffic.

    ``clock`` is the batcher's injectable time base (deterministic
    under a fake clock in tests); every staged call is timed with it,
    accumulated into ``gather_seconds``/``scatter_seconds`` and — when
    a ``ServeTelemetry`` is attached — observed into the
    ``serve_transfer_{gather,scatter}_seconds`` histograms.
    """

    def __init__(self, layout, faults: Optional[FaultPlan] = None,
                 clock: Optional[Callable[[], float]] = None,
                 telemetry=None):
        self.layout = layout
        self.faults = faults or FaultPlan()
        self._clock = clock or time.monotonic
        self._telemetry = telemetry
        self.gathers = 0             # staged spill/demote calls
        self.scatters = 0            # staged restore/promote calls
        self.d2h_bytes = 0
        self.h2d_bytes = 0
        self.gather_s = 0.0          # cumulative wall time (clock units)
        self.scatter_s = 0.0

    def gather_host(self, pools, pages_by_group: Dict[str, Sequence[int]]
                    ) -> Dict[str, Any]:
        """Spill the given pages of every group to host arrays.

        Stage 1 dispatches the device-side gather for EVERY group (one
        ``take`` per pool leaf, all pages at once); stage 2 pulls the
        results to host.  With async dispatch the D2H copy of one leaf
        overlaps the gather of the next — the double buffer — instead
        of the old per-page gather -> blocking copy -> gather loop.
        Groups with no pages are omitted from the result."""
        if not any(pages_by_group.values()):
            return {}                   # nothing to move: not a transfer
        self.faults.check("t1_d2h")
        t0 = self._clock()
        dev = {name: self.layout.gather_pages(pools, name, pages)
               for name, pages in pages_by_group.items() if pages}
        out = {name: jax.tree.map(np.asarray, tree)
               for name, tree in dev.items()}
        dt = self._clock() - t0
        self.gathers += 1
        self.d2h_bytes += sum(_tree_nbytes(t) for t in out.values())
        self.gather_s += dt
        if self._telemetry:
            self._telemetry.h_gather.observe(dt)
        return out

    def scatter_device(self, pools, data_by_group: Dict[str, Any],
                       pages_by_group: Dict[str, Sequence[int]]):
        """Restore host payloads into the given physical pages.

        Stage 1 moves every group's payload onto the device (async
        H2D, dtype preserved leaf-wise); stage 2 runs one scatter per
        pool leaf.  Returns the updated pools dict."""
        if not any(pages_by_group.get(name) for name in data_by_group):
            return pools                # nothing to move: not a transfer
        self.faults.check("t1_h2d")
        t0 = self._clock()
        staged = {name: jax.tree.map(jnp.asarray, data_by_group[name])
                  for name in data_by_group
                  if pages_by_group.get(name)}
        for name, tree in staged.items():
            pools = self.layout.restore_pages(pools, name, tree,
                                              pages_by_group[name])
            self.h2d_bytes += _tree_nbytes(tree)
        dt = self._clock() - t0
        self.scatters += 1
        self.scatter_s += dt
        if self._telemetry:
            self._telemetry.h_scatter.observe(dt)
        return pools

    def stats(self) -> Dict[str, Any]:
        return {"gathers": self.gathers,
                "scatters": self.scatters,
                "d2h_bytes": self.d2h_bytes,
                "h2d_bytes": self.h2d_bytes,
                "gather_seconds": self.gather_s,
                "scatter_seconds": self.scatter_s}


class _T1Entry:
    __slots__ = ("data", "nbytes", "stamp")

    def __init__(self, data: Dict[str, Any], nbytes: int, stamp: int):
        self.data = data             # {group: host page payload tree}
        self.nbytes = nbytes
        self.stamp = stamp


class HostPageStore:
    """Bounded host-RAM store of prefix-block page payloads (T1).

    Content-addressed: the key is a digest of the block's FULL token
    path (root..block inclusive — see ``_content_key``), so identical
    prefixes demoted by different batchers — or reloaded from a
    snapshot — unify.  ``put`` LRU-evicts until the new entry fits its
    byte budget; an entry larger than the whole budget is refused.
    Entries are host buffers only (no device page ids), so nothing
    here can strand a refcounted device page.
    """

    def __init__(self, budget_bytes: int):
        self.budget = int(budget_bytes)
        self._store: Dict[Any, _T1Entry] = {}
        self._clock = 0
        self.nbytes = 0
        self.evictions = 0
        self.rejected = 0

    def __len__(self) -> int:
        return len(self._store)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def touch(self, key) -> bool:
        """Refresh the LRU stamp; True iff the key is present (lets a
        demote of an already-cached block skip its device->host copy)."""
        e = self._store.get(key)
        if e is None:
            return False
        e.stamp = self._tick()
        return True

    def get(self, key) -> Optional[Dict[str, Any]]:
        e = self._store.get(key)
        if e is None:
            return None
        e.stamp = self._tick()
        return e.data

    def put(self, key, data: Dict[str, Any]) -> bool:
        nbytes = sum(_tree_nbytes(t) for t in data.values())
        if nbytes > self.budget:
            self.rejected += 1
            return False
        old = self._store.pop(key, None)
        if old is not None:
            self.nbytes -= old.nbytes
        while self.nbytes + nbytes > self.budget and self._store:
            self._evict_lru()
        self._store[key] = _T1Entry(data, nbytes, self._tick())
        self.nbytes += nbytes
        return True

    def _evict_lru(self) -> None:
        victim = min(self._store, key=lambda k: self._store[k].stamp)
        self.nbytes -= self._store.pop(victim).nbytes
        self.evictions += 1

    def items_lru_order(self):
        """(key, entry) pairs, least recently used first (snapshot
        serialization order: a reload re-``put``s in this order so the
        reconstructed LRU matches)."""
        return sorted(self._store.items(), key=lambda kv: kv[1].stamp)


class KVTierManager:
    """Page movement policy between the device pools and T1/T2.

    Owns the T1 ``HostPageStore`` and the shared ``StagedTransferEngine``
    (the batcher passes its own so spill traffic and tier traffic share
    one set of counters).  ``block`` is the prefix-index block size —
    T1 entries are exactly one index node's worth of pages per group.
    """

    def __init__(self, layout, page_size: int, block: int,
                 budget_bytes: int, engine: StagedTransferEngine,
                 faults: Optional[FaultPlan] = None):
        self.layout = layout
        self.faults = faults or engine.faults
        self.page = int(page_size)
        self.block = int(block)
        self.bpp = self.block // self.page     # pages per block, per group
        self.store = HostPageStore(budget_bytes)
        self.engine = engine
        self.demotions = 0
        self.demote_skips = 0        # content already in T1 (no copy)
        self.rehits = 0              # promote chains restored
        self.rehit_tokens = 0
        self.recomputes = 0          # policy chose recompute over restore
        self.snapshot_loaded = 0     # entries loaded from T2

    # -- T0 -> T1 (demote on prefix eviction) -------------------------------------

    def demote(self, path_tokens: Sequence[int],
               pages_by_group: Dict[str, Sequence[int]], pools) -> None:
        """Stage an evicted prefix node's pages into T1.  Called with
        the pages still live on device (the caller frees them after);
        a content hit skips the device->host copy entirely — indexed
        page bits are immutable while shared (CoW), so the cached copy
        is still exact.  A payload the byte budget can never hold is
        rejected BEFORE the gather (sizes come from the pool leaf
        shapes), so an undersized budget degrades to tier-off instead
        of taxing every eviction with a wasted device->host copy."""
        key = _content_key(path_tokens)
        if self.store.touch(key):
            self.demote_skips += 1
            return
        nbytes = 0
        for name, pages in pages_by_group.items():
            if not pages:
                continue
            ax = self.layout.page_axis(name)
            nbytes += sum(a.nbytes // a.shape[ax] * len(pages)
                          for a in jax.tree.leaves(pools[name]))
        if nbytes > self.store.budget:
            self.store.rejected += 1
            return
        data = self.engine.gather_host(pools, pages_by_group)
        if self.store.put(key, data):
            self.demotions += 1

    # -- T1 -> T0 (promote on rehit) ------------------------------------------------

    def match(self, prompt: np.ndarray, start_block: int
              ) -> List[Dict[str, Any]]:
        """Longest chain of consecutive T1 entries covering blocks
        ``start_block, start_block+1, ...`` of the prompt.  The
        per-block content keys are computed INCREMENTALLY (one rolling
        digest extended block by block), so the whole walk is O(prompt
        length), not O(length^2)."""
        toks = np.ascontiguousarray(np.asarray(prompt, np.int64))
        h = hashlib.sha1(toks[:start_block * self.block].tobytes())
        chain: List[Dict[str, Any]] = []
        b = start_block
        while (b + 1) * self.block <= len(toks):
            h.update(toks[b * self.block:(b + 1) * self.block].tobytes())
            data = self.store.get(h.digest())
            if data is None:
                break
            chain.append(data)
            b += 1
        return chain

    def restore_chain(self, pools, chain: List[Dict[str, Any]],
                      pages_by_group: Dict[str, Sequence[int]]):
        """Scatter a matched chain's payloads into freshly allocated
        pages — ONE staged transfer for the whole chain per group (the
        per-entry payloads are concatenated along the page axis on
        host, then moved + scattered together)."""
        data: Dict[str, Any] = {}
        for name, pages in pages_by_group.items():
            if not pages:
                continue
            ax = self.layout.page_axis(name)
            parts = [entry[name] for entry in chain]
            data[name] = (parts[0] if len(parts) == 1 else jax.tree.map(
                lambda *xs, _ax=ax: np.concatenate(xs, axis=_ax), *parts))
        return self.engine.scatter_device(pools, data, pages_by_group)

    # -- T2 snapshots ----------------------------------------------------------------

    def _payload_signature(self) -> Dict[str, list]:
        """Per-group (shape, dtype) of every pool leaf at one block's
        worth of pages — the exact geometry of a T1 entry payload.
        Stored in the snapshot and compared at load, so a snapshot from
        a different cache dtype or architecture (same page/block/group
        names, different leaves) fails cleanly at construction instead
        of crashing the serve loop at its first rehit."""
        decls = self.layout.pool_decls({g.name: self.bpp
                                        for g in self.layout.groups})
        return {name: sorted((tuple(d.shape), np.dtype(d.dtype).name)
                             for d in jax.tree.leaves(
                                 tree, is_leaf=lambda x: isinstance(x, Decl)))
                for name, tree in decls.items()}

    def save(self, path: str, index=None, pools=None) -> int:
        """Persist the T1 store to ``path``.  When the live ``index``
        (+ ``pools``) is given, every device-resident cached prefix is
        flushed through ``demote`` first, so the snapshot carries the
        device tier too (bounded by the T1 byte budget).  Returns the
        number of entries written.  The write is atomic (tmp + rename):
        a crash mid-save never corrupts the previous snapshot.  The
        payload is pickled once and wrapped with its SHA-256 so load
        detects truncation/bit-rot before touching the entries."""
        if index is not None and pools is not None:
            for path_tokens, pages in index.walk():
                self.demote(path_tokens, pages, pools)
        entries = [(key, e.data, e.stamp)
                   for key, e in self.store.items_lru_order()]
        payload = {
            "page": self.page,
            "block": self.block,
            "groups": sorted(g.name for g in self.layout.groups),
            "leaf_sig": self._payload_signature(),
            "entries": entries,
        }
        blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        envelope = {
            "version": _SNAPSHOT_VERSION,
            "sha256": hashlib.sha256(blob).hexdigest(),
            "blob": blob,
        }
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(envelope, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
        # fault sites for the storage-rot chaos tests: mangle the file
        # AFTER the atomic rename, exactly as bit-rot/truncation would.
        if self.faults.fire("snapshot_corrupt"):
            _flip_bit(path)
        if self.faults.fire("snapshot_truncate"):
            _truncate_half(path)
        return len(entries)

    def load(self, path: str) -> int:
        """Repopulate T1 from a snapshot.  Geometry (page size, block
        size, page groups) must match the current layout — silently
        restoring pages of a different shape would corrupt the pools,
        so a mismatch raises.  Entries re-enter in LRU order under the
        current byte budget (oldest dropped first if the budget shrank
        since the save).

        Integrity failures (unreadable file, bad version envelope,
        checksum mismatch, truncation) raise ``SnapshotCorruptError`` —
        the batcher degrades those to a logged cold start.  A snapshot
        that verifies but doesn't fit this layout raises ``ValueError``
        as before: that is a configuration error, not storage rot."""
        try:
            with open(path, "rb") as f:
                envelope = pickle.load(f)
            version = envelope.get("version")
            blob = envelope.get("blob")
            digest = envelope.get("sha256")
            if (version != _SNAPSHOT_VERSION or not isinstance(blob, bytes)
                    or hashlib.sha256(blob).hexdigest() != digest):
                raise SnapshotCorruptError(
                    f"kv tier snapshot {path}: bad envelope or checksum "
                    f"mismatch (version={version!r})")
            payload = pickle.loads(blob)
            if not isinstance(payload, dict) or "entries" not in payload:
                raise SnapshotCorruptError(
                    f"kv tier snapshot {path}: payload malformed")
        except SnapshotCorruptError:
            raise
        except Exception as e:   # OSError, pickle errors, EOF, attribute…
            raise SnapshotCorruptError(
                f"kv tier snapshot {path}: unreadable "
                f"({type(e).__name__}: {e})") from e
        groups = sorted(g.name for g in self.layout.groups)
        if (payload["page"] != self.page or payload["block"] != self.block
                or payload["groups"] != groups):
            raise ValueError(
                f"kv tier snapshot {path} geometry mismatch: snapshot "
                f"(page={payload['page']}, block={payload['block']}, "
                f"groups={payload['groups']}) vs layout (page={self.page}, "
                f"block={self.block}, groups={groups})")
        sig = self._payload_signature()
        if payload.get("leaf_sig") != sig:
            raise ValueError(
                f"kv tier snapshot {path} geometry mismatch: pool leaf "
                f"shapes/dtypes {payload.get('leaf_sig')} != {sig} — the "
                f"snapshot was taken with a different cache dtype or "
                f"architecture; restoring it would corrupt the pools")
        n = 0
        for key, data, _stamp in payload["entries"]:
            if self.store.put(key, data):
                n += 1
        self.snapshot_loaded += n
        return n

    # -- observability ----------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        return {
            "t1_entries": len(self.store),
            "t1_bytes": self.store.nbytes,
            "t1_budget_bytes": self.store.budget,
            "t1_evictions": self.store.evictions,
            "t1_rejected": self.store.rejected,
            "demotions": self.demotions,
            "demote_skips": self.demote_skips,
            "rehits": self.rehits,
            "rehit_tokens": self.rehit_tokens,
            "recomputes": self.recomputes,
            "snapshot_loaded": self.snapshot_loaded,
            **self.engine.stats(),
        }
