"""Paged KV cache: one preallocated block pool shared by all sequences.

The pool is a fixed-shape array

    [num_layers, num_blocks, 2, kv_heads, block_size, head_dim]

(dim 2 is K/V). Sequences own *block tables* — lists of pool indices — so a
sequence of any length lives in ceil(len / block_size) blocks and every
engine step runs with static shapes: the decode step sees the whole pool
plus fixed-size [slots, max_blocks] tables and never retraces as sequences
grow (asserted by the engine's trace counter, the ``static.Executor``
no-retrace discipline).

Block 0 is a reserved scratch block: inactive decode slots carry all-zero
tables, so their (masked-out) K/V writes land in scratch instead of a live
sequence's block. The allocator therefore hands out ids 1..num_blocks-1.

Host side: :class:`BlockAllocator` (refcounted free-list) and
:class:`PagedKVCache` (pool + per-sequence tables + the prefix cache).
Trace side: :class:`PagedCacheView`, the per-step functional view the
jitted engine functions thread through
``LlamaForCausalLM.forward(cache=...)`` — it scatters new K/V into the
pool and attends through the ragged paged-attention kernel.
:class:`DenseKVCache` is the simple concatenating (HF ``past_kv``-style)
cache used for parity testing and one-off decode.

Prefix caching (``PagedKVCache(prefix_cache=True)``, docs/SERVING.md):
blocks carry refcounts, full token-blocks are content-addressed through a
hash chain (dict keyed on ``(parent_hash, block_tokens)``), admission maps
the longest cached block-aligned prefix into the new sequence's table as
*shared* blocks (rc += 1) so only the divergent tail is prefilled, and a
first write into a shared block triggers copy-on-write. Unreferenced
completed prefixes (rc == 0 but still indexed) sit in an LRU pool that is
evicted on demand — the scheduler admits against *effective* free blocks
(free + evictable). The ragged paged-attention kernel gathers K/V through
per-sequence block tables, so shared blocks are purely host-side
bookkeeping: no kernel change.

State layers (``PagedKVCache(state_layers=...)``, docs/SERVING.md §2a): a
model whose layers carry a recurrence (a state-space mixer) keeps, beside
the pool, two arrays indexed by *decode slot*, not by block,
``[state_layers, max_slots, ...]``: the recurrent state and the causal
conv's last inputs. Their size does not grow with the context; a decode
step reads and rewrites a live slot's rows whole (the state in place,
``kernels/ssm_state_update.py``), a prefill writes its slot's rows from a
zero state, so a slot never inherits its predecessor's. They travel through
the jitted steps donated, as the pool does. A prefix hit would need the
state at the prefix's end, which nothing snapshots: such a model runs
without prefix reuse.

Tiered host-RAM spill (``spill_blocks=N``, docs/ROBUSTNESS.md "Degradation
ladder"): with a spill tier armed, LRU eviction *demotes* instead of
destroys — the evicted block's K/V is copied to a bounded host (numpy)
pool keyed by the same content address and stamped with a CRC32. A later
prefix match that runs off the end of the device index continues through
the spill pool: each spilled block is **promoted** back to a device block
(CRC verified against the stamp first — a corrupt or faulted promotion
drops the entry and falls back to full prefill, never wrong tokens) and
parked in the device LRU so the ordinary shared-block refcounting takes
over. Every allocation path already funnels through ``_alloc_evict``, so
"demote then retry" is the universal step before preempt/fail. Fault
sites ``serving.kv.spill`` / ``serving.kv.promote`` drive the failure
paths deterministically.
"""
from __future__ import annotations

import hashlib
import zlib
from collections import OrderedDict
from dataclasses import dataclass
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..nn.functional.attention import sdpa_ref, sdpa_window_ref
from ..utils import faults

__all__ = ["BlockAllocator", "PagedKVCache", "PagedCacheView", "DenseKVCache",
           "SCRATCH_BLOCK"]

SCRATCH_BLOCK = 0  # reserved: masked writes from inactive slots land here


# prefix-cache metric families (process-global; per-engine gauges live on
# the engine's labeled series). Lazy so importing serving never forces the
# registry up before package init finishes.
_PM = None


def _prefix_metrics() -> SimpleNamespace:
    global _PM
    if _PM is None:
        reg = telemetry.registry()
        _PM = SimpleNamespace(
            hits=reg.counter("kv_prefix_hits_total",
                             "admissions that matched a cached prefix"),
            misses=reg.counter("kv_prefix_misses_total",
                               "admissions that matched nothing"),
            blocks_saved=reg.counter(
                "kv_prefix_blocks_saved_total",
                "KV blocks mapped shared instead of re-prefilled"),
            tokens_saved=reg.counter(
                "kv_prefix_tokens_saved_total",
                "prompt tokens whose prefill was skipped via prefix hits"),
            cow=reg.counter("kv_prefix_cow_copies_total",
                            "copy-on-write private block copies"),
            evictions=reg.counter(
                "kv_prefix_evictions_total",
                "cached prefix blocks reclaimed from the LRU pool"),
            stale=reg.counter(
                "kv_prefix_stale_drops_total",
                "prefix matches dropped whole (stale/corrupt index)"),
            cached=reg.gauge("kv_prefix_cached_blocks",
                             "blocks held rc==0 in the evictable LRU pool"),
            spills=reg.counter(
                "kv_spill_total",
                "cached blocks demoted to the host-RAM spill tier"),
            spill_dropped=reg.counter(
                "kv_spill_dropped_total",
                "spill entries destroyed for host-pool capacity"),
            spill_errors=reg.counter(
                "kv_spill_errors_total",
                "demotions that failed (eviction destroyed instead)"),
            promotes=reg.counter(
                "kv_promote_total",
                "spilled blocks promoted back to device blocks"),
            promote_errors=reg.counter(
                "kv_promote_errors_total",
                "promotions that failed (entry dropped, full prefill)"),
            promote_corrupt=reg.counter(
                "kv_promote_corrupt_total",
                "promotions refused by the CRC check (entry dropped)"),
            spilled=reg.gauge(
                "kv_spill_blocks", "blocks resident in the host spill pool"),
            spilled_bytes=reg.gauge(
                "kv_spill_bytes", "host-RAM bytes held by the spill pool"),
            t_cached=reg.gauge(
                "tenant_cached_blocks",
                "rc==0 cached prefix blocks held, by owning tenant",
                ("tenant",)),
            t_quota_evict=reg.counter(
                "tenant_quota_evictions_total",
                "cached blocks evicted ahead of LRU order because their "
                "tenant exceeded its block quota", ("tenant",)),
        )
    return _PM


class BlockAllocator:
    """Refcounted free-list allocator over the pool's block ids
    (1..num_blocks-1).

    Every allocated block carries a refcount: ``alloc`` hands it out with
    rc=1, :meth:`share` maps it into another table (rc += 1), and
    :meth:`free` decrements — only an rc==0 block returns to the free
    list. :meth:`release` is the prefix-cache variant of the last
    dereference: instead of the free list, the block parks in the *cached*
    set (content retained, evictable) until :meth:`share` promotes it back
    or :meth:`reclaim` evicts it. Tracks a high-water mark so tests can
    assert the pool never overflows and the engine can report peak cache
    pressure.
    """

    def __init__(self, num_blocks: int, reserved: int = 1):
        if num_blocks <= reserved:
            raise ValueError(
                f"need more than {reserved} block(s), got {num_blocks}")
        self.num_blocks = num_blocks
        self.reserved = reserved
        # pop() takes from the end: hand out low ids first
        self._free = list(range(num_blocks - 1, reserved - 1, -1))
        self._rc: dict[int, int] = {}     # allocated blocks (cached: rc==0)
        self._cached: set[int] = set()    # rc==0, content retained
        self.high_water = 0

    @property
    def num_usable(self) -> int:
        return self.num_blocks - self.reserved

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        """Blocks referenced by at least one table (rc >= 1)."""
        return len(self._rc) - len(self._cached)

    @property
    def num_cached(self) -> int:
        """Evictable blocks: rc == 0 but content retained for prefix hits."""
        return len(self._cached)

    @property
    def num_effective_free(self) -> int:
        """What admission control sees: free plus evictable."""
        return len(self._free) + len(self._cached)

    @property
    def _live(self) -> set[int]:
        """The rc>=1 block set (kept as a view for the invariant tests)."""
        return {b for b, rc in self._rc.items() if rc > 0}

    def refcount(self, block: int) -> int:
        return self._rc.get(block, 0)

    def alloc(self, n: int = 1):
        """Allocate ``n`` blocks at rc=1; returns their ids, or None if the
        free list cannot satisfy the request (caller evicts, preempts, or
        queues)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        # chaos site: an "exhaust" fault makes the pool look dry for this
        # call, exercising the caller's preempt/queue/fail path
        if faults.inject("serving.kv.alloc", n=n) == "exhaust":
            telemetry.record_event("kv.alloc", n=n, granted=False,
                                   free=len(self._free), injected=True)
            return None
        if n > len(self._free):
            telemetry.record_event("kv.alloc", n=n, granted=False,
                                   free=len(self._free))
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._rc[b] = 1
        self.high_water = max(self.high_water, self.num_used)
        telemetry.record_event("kv.alloc", n=n, granted=True,
                               live=self.num_used, free=len(self._free))
        return out

    def share(self, blocks):
        """Add one reference per block (mapping it into another table). A
        cached (rc==0) block is promoted back to live."""
        blocks = list(blocks)
        for b in blocks:
            if b not in self._rc:
                raise ValueError(f"share of unallocated block id {b}")
        for b in blocks:
            self._cached.discard(b)
            self._rc[b] += 1
        self.high_water = max(self.high_water, self.num_used)
        telemetry.record_event("kv.share", n=len(blocks),
                               live=self.num_used, cached=len(self._cached))

    def free(self, blocks):
        """Drop one reference per block; blocks reaching rc==0 return to
        the free list."""
        blocks = list(blocks)
        for b in blocks:
            if self._rc.get(b, 0) <= 0:
                raise ValueError(f"double free / foreign block id {b}")
        for b in blocks:
            self._rc[b] -= 1
            if self._rc[b] == 0:
                del self._rc[b]
                self._free.append(b)
        telemetry.record_event("kv.free", n=len(blocks),
                               live=self.num_used, free=len(self._free))

    def release(self, blocks) -> list[int]:
        """Drop one reference per block, parking rc==0 blocks in the cached
        set instead of the free list (their K/V stays valid for prefix
        hits). Returns the blocks that became cached."""
        blocks = list(blocks)
        for b in blocks:
            if self._rc.get(b, 0) <= 0:
                raise ValueError(f"double free / foreign block id {b}")
        became = []
        for b in blocks:
            self._rc[b] -= 1
            if self._rc[b] == 0:
                self._cached.add(b)
                became.append(b)
        return became

    def reclaim(self, blocks):
        """Evict cached blocks back to the free list (the cache removed
        their index entries first). Never touches referenced blocks."""
        for b in blocks:
            if b not in self._cached:
                raise ValueError(
                    f"reclaim of non-cached block id {b} (rc="
                    f"{self._rc.get(b, 0)})")
            self._cached.discard(b)
            del self._rc[b]
            self._free.append(b)


# module-level so jax's jit cache keys on shapes alone: every cache
# instance with the same pool geometry shares ONE compiled scatter, and a
# promotion after warmup costs a dispatch, not a compile
@jax.jit
def _promote_write(pool, block, kv):
    return pool.at[:, block].set(kv)


@dataclass
class _SpillEntry:
    """One block's K/V demoted to host RAM: the index key it answered to
    on device, its chain hash, the numpy copy, and the CRC32 stamped at
    demotion time — promotion refuses to serve bytes that no longer match
    the stamp."""

    key: tuple
    hash: str
    kv: np.ndarray          # [num_layers, 2, kv_heads, block_size, head_dim]
    crc: int


def _chain_hash(parent_hash: str, block_tokens) -> str:
    """Content address of a full token-block given its prefix's hash: the
    chain makes a block's hash identify the *entire* token prefix ending at
    it, so equal hashes mean equal K/V content (decode is deterministic in
    the token prefix)."""
    payload = parent_hash + "|" + ",".join(str(int(t)) for t in block_tokens)
    return hashlib.sha1(payload.encode()).hexdigest()


class PagedKVCache:
    """The block pool plus per-sequence block tables (host bookkeeping).

    With ``prefix_cache=True`` the cache additionally maintains the
    content-addressed prefix index, the LRU pool of unreferenced
    completed prefixes, and copy-on-write; see the module docstring.
    """

    def __init__(self, num_layers, num_blocks, kv_heads, block_size,
                 head_dim, dtype=jnp.float32, prefix_cache: bool = False,
                 spill_blocks: int | None = None, state_layers=(),
                 max_slots: int = 0):
        self.pool = jnp.zeros(
            (num_layers, num_blocks, 2, kv_heads, block_size, head_dim),
            dtype)
        # what the state layers (``StateLayer``, all alike) keep a decode
        # slot: (recurrent state, conv inputs), or None without such layers
        self.state = None
        if state_layers:
            if len(set(state_layers)) != 1:
                raise ValueError(
                    f"one array holds every state layer's rows, so the "
                    f"layers must agree; the model has {set(state_layers)}")
            if prefix_cache:
                raise ValueError(
                    "a prefix hit skips the prefill that builds a state "
                    "layer's state: no prefix cache with state layers")
            rows = (len(state_layers), int(max_slots))
            self.state = tuple(
                jnp.zeros(rows + tuple(shape), dt or dtype)
                for shape, dt in state_layers[0])
        self.allocator = BlockAllocator(num_blocks)
        self.block_size = int(block_size)
        self.tables: dict[object, list[int]] = {}
        self.prefix_cache = bool(prefix_cache)
        # content-addressed index: (parent_hash, block_tokens) -> block id
        self._index: dict[tuple[str, tuple[int, ...]], int] = {}
        self._block_key: dict[int, tuple] = {}   # registered block -> key
        self._block_hash: dict[int, str] = {}    # registered block -> hash
        self._lru: OrderedDict[int, None] = OrderedDict()  # rc==0, evictable
        self._seq_hashes: dict[object, list[str]] = {}   # committed chain
        self.seq_cached_tokens: dict[object, int] = {}   # last admission hit
        # host-RAM spill tier: key -> _SpillEntry, LRU order (oldest first);
        # bounded at spill_blocks entries, 0/None = eviction destroys
        self.spill_blocks = int(spill_blocks or 0)
        self._spill: OrderedDict[tuple, _SpillEntry] = OrderedDict()
        # blocks a match walk has collected but not yet refcounted: a
        # promotion allocating mid-walk must not evict them out from
        # under the caller (``_evict_one`` skips pinned entries)
        self._pinned: set[int] = set()
        # per-tenant prefix-block quotas (serving/tenancy.py): every
        # cached (rc==0, LRU-parked) block is attributed to the tenant
        # whose sequence parked it; past a tenant's quota its blocks are
        # FIRST in eviction order (oldest of that tenant), so one
        # tenant's giant system prompt cannot evict the fleet's shared
        # working set
        self._seq_tenant: dict[object, str] = {}
        self._block_tenant: dict[int, str] = {}
        self._tenant_cached: dict[str, int] = {}
        self._tenant_quota: dict[str, int] = {}
        self._park_tenant: str | None = None   # allocate() in progress
        self.quota_evictions: dict[str, int] = {}
        self._block_nbytes = int(self.pool.nbytes) // max(int(num_blocks), 1)
        # running totals (prefix_stats(); the telemetry counters mirror them)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_blocks_saved = 0
        self.prefix_tokens_saved = 0
        self.cow_copies = 0
        self.prefix_evictions = 0
        self.stale_drops = 0
        self.spills = 0
        self.spill_drops = 0
        self.spill_errors = 0
        self.promotes = 0
        self.promote_errors = 0
        self.promote_corrupt_drops = 0
        # cross-replica KV fabric (serving/kv_fabric.py): donor-side
        # exports and receiver-side ingests of serialized block frames
        self.fabric_exports = 0
        self.fabric_export_frames = 0
        self.fabric_ingests = 0
        self.fabric_ingested_blocks = 0
        self.fabric_ingest_corrupt = 0
        self.fabric_ingest_errors = 0

    def blocks_for(self, num_tokens: int) -> int:
        return -(-int(num_tokens) // self.block_size)

    @property
    def num_effective_free(self) -> int:
        return self.allocator.num_effective_free

    def can_allocate(self, num_tokens: int) -> bool:
        return self.allocator.num_effective_free >= self.blocks_for(
            num_tokens)

    def _table(self, seq_id) -> list[int]:
        try:
            return self.tables[seq_id]
        except KeyError:
            raise ValueError(
                f"unknown sequence {seq_id!r}: no block table (never "
                f"allocated or already freed)") from None

    # -- prefix index ------------------------------------------------------
    def match_prefix(self, tokens):
        """Longest cached block-aligned prefix of ``tokens``: returns
        ``(blocks, hashes)`` walking the hash chain from the root. Capped
        at ``len(tokens) - 1`` so at least one token always prefills (the
        first sampled token needs the last position's logits)."""
        blocks: list[int] = []
        hashes: list[str] = []
        if not self.prefix_cache:
            return blocks, hashes
        # chaos site (consulted once per match attempt, so @k plans index
        # admissions): a stale_hash fault models index corruption — an
        # entry whose block no longer holds the content its key promises;
        # the graceful path drops the whole match and prefills from scratch
        if faults.inject("serving.kv.share", tokens=len(tokens)) \
                == "stale_hash":
            self.stale_drops += 1
            _prefix_metrics().stale.inc()
            telemetry.record_event("kv.share", stale=True,
                                   tokens=len(tokens))
            return [], []
        if not self._index and not self._spill:
            return blocks, hashes
        bs = self.block_size
        limit = (len(tokens) - 1) // bs     # block-aligned, < len(tokens)
        parent = ""
        for i in range(limit):
            toks = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            b = self._index.get((parent, toks))
            if b is None:
                # device chain ends here; the spill tier may continue it —
                # promote consecutive spilled blocks back to device blocks
                # until the chain, the pool, or a CRC check stops us. The
                # walk's blocks are pinned: a promotion's own allocation
                # must not evict what this match is about to share.
                self._pinned = set(blocks)
                try:
                    for j in range(i, limit):
                        toks = tuple(int(t)
                                     for t in tokens[j * bs:(j + 1) * bs])
                        entry = self._spill.get((parent, toks))
                        if entry is None:
                            break
                        pb = self._promote(entry)
                        if pb is None:
                            break
                        self._pinned.add(pb)
                        blocks.append(pb)
                        parent = entry.hash
                        hashes.append(parent)
                finally:
                    self._pinned = set()
                break
            blocks.append(b)
            h = self._block_hash.get(b)
            parent = h if h is not None else _chain_hash(parent, toks)
            hashes.append(parent)
        return blocks, hashes

    def _register(self, block: int, parent: str, toks: tuple) -> None:
        """Idempotent index insert. If the key is already taken (another
        sequence registered equal content first) the duplicate block simply
        stays unregistered and frees normally at rc==0 — the chain hash is
        content-derived, so children registered under it still resolve."""
        key = (parent, toks)
        if key in self._index or block in self._block_key:
            return
        self._index[key] = block
        self._block_key[block] = key
        self._block_hash[block] = _chain_hash(parent, toks)

    def commit_prefix(self, seq_id, tokens) -> None:
        """Register every *full* block of ``tokens`` whose K/V the pool now
        holds (called after prefill and whenever decode fills a block).
        Catch-up style: blocks already committed for this sequence are
        skipped via the per-sequence hash chain."""
        if not self.prefix_cache:
            return
        table = self._table(seq_id)
        hashes = self._seq_hashes.setdefault(seq_id, [])
        bs = self.block_size
        n_full = len(tokens) // bs
        for i in range(len(hashes), n_full):
            parent = hashes[-1] if hashes else ""
            toks = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            self._register(table[i], parent, toks)
            hashes.append(_chain_hash(parent, toks))

    # -- per-tenant quota bookkeeping --------------------------------------
    def set_tenant_quotas(self, quotas) -> None:
        """Arm per-tenant cached-block quotas (``{tenant: max_blocks}``,
        from ``TenantRegistry.block_quotas()``). Enforcement is an
        *eviction-order* policy: an over-quota tenant's cached blocks go
        first (oldest of that tenant), live references are never touched."""
        self._tenant_quota = {str(t): int(q)
                              for t, q in (quotas or {}).items()}

    def _lru_park(self, block: int, tenant: str | None = None) -> None:
        """A block entered the evictable LRU: attribute it to its tenant."""
        self._lru[block] = None
        t = tenant or self._park_tenant or "anonymous"
        self._block_tenant[block] = t
        n = self._tenant_cached.get(t, 0) + 1
        self._tenant_cached[t] = n
        if telemetry.enabled():
            _prefix_metrics().t_cached.labels(tenant=t).set(n)

    def _lru_unpark(self, block: int) -> None:
        """A block left the LRU (shared back in, or evicted)."""
        if block not in self._lru:
            return
        del self._lru[block]
        t = self._block_tenant.pop(block, None)
        if t is None:
            return
        n = max(0, self._tenant_cached.get(t, 1) - 1)
        if n:
            self._tenant_cached[t] = n
        else:
            self._tenant_cached.pop(t, None)
        if telemetry.enabled():
            _prefix_metrics().t_cached.labels(tenant=t).set(n)

    def _quota_victim(self) -> int | None:
        """The oldest unpinned cached block of any over-quota tenant, or
        None when every tenant is within quota (plain LRU order rules)."""
        if not self._tenant_quota:
            return None
        over = {t for t, q in self._tenant_quota.items()
                if self._tenant_cached.get(t, 0) > q}
        if not over:
            return None
        return next((b for b in self._lru
                     if b not in self._pinned
                     and self._block_tenant.get(b) in over), None)

    def _evict_one(self) -> int | None:
        """Reclaim a cached block: drop its index entry, return it to the
        free list. An over-quota tenant's blocks evict first (its oldest);
        otherwise the least-recently-released block goes. Only rc==0
        blocks live in the LRU, so eviction can never touch a referenced
        block. With a spill tier armed, the block's K/V is demoted to the
        host pool first — eviction becomes a tier transition, not a
        destruction. Returns None when every LRU entry is pinned by an
        in-progress match walk (nothing safely evictable)."""
        block = self._quota_victim()
        over_quota = block is not None
        if block is None:
            block = next((b for b in self._lru if b not in self._pinned),
                         None)
        if block is None:
            return None
        tenant = self._block_tenant.get(block)
        self._lru_unpark(block)
        if over_quota:
            self.quota_evictions[tenant] = \
                self.quota_evictions.get(tenant, 0) + 1
            if telemetry.enabled():
                _prefix_metrics().t_quota_evict.labels(
                    tenant=tenant).inc()
            telemetry.record_event(
                "kv.quota_evict", block=block, tenant=tenant,
                cached=self._tenant_cached.get(tenant, 0))
        key = self._block_key.pop(block, None)
        if key is not None and self._index.get(key) == block:
            del self._index[key]
        h = self._block_hash.pop(block, None)
        spilled = False
        if key is not None and h is not None:
            spilled = self._spill_block(block, key, h)
        self.allocator.reclaim([block])
        self.prefix_evictions += 1
        pm = _prefix_metrics()
        pm.evictions.inc()
        pm.cached.set(self.allocator.num_cached)
        telemetry.record_event("kv.evict", block=block, spilled=spilled,
                               cached=self.allocator.num_cached)
        return block

    # -- host-RAM spill tier ----------------------------------------------
    @property
    def spilled_bytes(self) -> int:
        return len(self._spill) * self._block_nbytes

    def _sync_spill_gauges(self, pm=None):
        pm = pm or _prefix_metrics()
        pm.spilled.set(len(self._spill))
        pm.spilled_bytes.set(self.spilled_bytes)

    def _spill_block(self, block: int, key: tuple, h: str) -> bool:
        """Demote an evicted block's K/V to the host pool (CRC32-stamped).
        Failure (injected or real) falls back to destroy-eviction: slower
        later, never wrong. Returns True when the entry landed."""
        if not self.spill_blocks:
            return False
        pm = _prefix_metrics()
        try:
            act = faults.inject("serving.kv.spill", block=block)
            # np.array copies: the host pool must own (writable,
            # device-free) memory, not a read-only view of the device
            # buffer
            kv = np.ascontiguousarray(np.array(self.pool[:, block]))
            crc = zlib.crc32(kv.tobytes())
            if act == "corrupt":
                # simulated host-RAM bit rot *after* the stamp: the
                # stored bytes no longer match the CRC, so a later
                # promotion must detect the mismatch and drop the entry
                kv.view(np.uint8).reshape(-1)[0] ^= 0xFF
        except Exception as e:
            # a failed demotion degrades to destroy-eviction (today's
            # behavior): the prefix re-prefills later, never serves junk
            self.spill_errors += 1
            pm.spill_errors.inc()
            telemetry.record_event(
                "kv.spill", block=block, ok=False,
                error=f"{type(e).__name__}: {e}")
            return False
        while len(self._spill) >= self.spill_blocks:
            self._spill.popitem(last=False)
            self.spill_drops += 1
            pm.spill_dropped.inc()
        self._spill[key] = _SpillEntry(key, h, kv, crc)
        self.spills += 1
        pm.spills.inc()
        self._sync_spill_gauges(pm)
        telemetry.record_event("kv.spill", block=block, ok=True,
                               spilled=len(self._spill))
        return True

    def _promote(self, entry: _SpillEntry) -> int | None:
        """Promote one spilled block back to a device block: verify the
        CRC stamp, allocate a device block (demoting others on demand),
        copy the K/V in, re-register the content address, and park the
        block *cached* so the caller's ordinary share() path owns the
        refcount. Any failure drops the entry from the spill index and
        returns None — the caller stops extending the match and the
        request prefills those tokens from scratch (never wrong K/V)."""
        pm = _prefix_metrics()
        try:
            act = faults.inject("serving.kv.promote",
                                blocks=len(self._spill))
            crc_ok = zlib.crc32(entry.kv.tobytes()) == entry.crc
        except Exception as e:
            self._spill.pop(entry.key, None)
            self.promote_errors += 1
            pm.promote_errors.inc()
            self._sync_spill_gauges(pm)
            telemetry.record_event("kv.promote", ok=False,
                                   error=f"{type(e).__name__}: {e}")
            return None
        if act == "corrupt" or not crc_ok:
            # the host copy no longer matches its stamp: serving it would
            # emit wrong tokens, so the entry is dropped and the request
            # falls back to prefilling these tokens itself
            self._spill.pop(entry.key, None)
            self.promote_corrupt_drops += 1
            pm.promote_corrupt.inc()
            self._sync_spill_gauges(pm)
            telemetry.record_event("kv.promote", ok=False, corrupt=True)
            return None
        if entry.key in self._index:     # equal content re-registered since
            self._spill.pop(entry.key, None)
            self._sync_spill_gauges(pm)
            return self._index[entry.key]
        out = self._alloc_evict(1)
        if out is None:
            # device pool truly dry even after demotion: the entry stays
            # spilled for a later attempt, the match just stops here
            self.promote_errors += 1
            pm.promote_errors.inc()
            telemetry.record_event("kv.promote", ok=False, exhausted=True)
            return None
        [block] = out
        try:
            self.pool = _promote_write(self.pool, jnp.int32(block),
                                       jnp.asarray(entry.kv))
        except Exception as e:
            # the host->device copy itself died: give the block back and
            # drop the entry — the request prefills those tokens itself
            self.allocator.free([block])
            self._spill.pop(entry.key, None)
            self.promote_errors += 1
            pm.promote_errors.inc()
            self._sync_spill_gauges(pm)
            telemetry.record_event("kv.promote", ok=False,
                                   error=f"{type(e).__name__}: {e}")
            return None
        self._spill.pop(entry.key, None)
        self._index[entry.key] = block
        self._block_key[block] = entry.key
        self._block_hash[block] = entry.hash
        self.allocator.release([block])          # rc 1 -> 0: parked cached
        self._lru_park(block)
        self.promotes += 1
        pm.promotes.inc()
        pm.cached.set(self.allocator.num_cached)
        self._sync_spill_gauges(pm)
        telemetry.record_event("kv.promote", ok=True, block=block,
                               spilled=len(self._spill))
        return block

    def _alloc_evict(self, n: int):
        """Allocate ``n`` fresh blocks, evicting LRU cached prefixes on
        demand — this is what makes cached blocks *effectively* free."""
        if n <= 0:
            return []
        out = self.allocator.alloc(n)
        while out is None and self._lru:
            if self._evict_one() is None:    # every LRU entry pinned
                break
            out = self.allocator.alloc(n)
        return out

    # -- sequence lifecycle ------------------------------------------------
    def allocate(self, seq_id, num_tokens: int, tokens=None,
                 tenant: str | None = None) -> bool:
        """Give ``seq_id`` a table covering ``num_tokens`` tokens. With the
        prefix cache on and the token ids supplied, the longest cached
        block-aligned prefix is mapped in as shared blocks and only the
        tail is freshly allocated; ``seq_cached_tokens[seq_id]`` records
        the hit for the caller's tail-only prefill. ``tenant`` attributes
        the sequence's eventually-cached blocks for quota enforcement."""
        if seq_id in self.tables:
            raise ValueError(f"sequence {seq_id!r} already has a table")
        matched: list[int] = []
        hashes: list[str] = []
        self._park_tenant = tenant
        try:
            if self.prefix_cache and tokens is not None:
                matched, hashes = self.match_prefix(tokens)
            if matched:
                self.allocator.share(matched)    # promotes cached ones
                for b in matched:
                    self._lru_unpark(b)
            need = self.blocks_for(num_tokens) - len(matched)
            tail = self._alloc_evict(need)
            if tail is None:
                # roll back the shares; registered blocks park back in
                # the LRU
                if matched:
                    for b in self.allocator.release(matched):
                        self._lru_park(b, tenant)
                    _prefix_metrics().cached.set(self.allocator.num_cached)
                return False
        finally:
            self._park_tenant = None
        self.tables[seq_id] = matched + tail
        self._seq_hashes[seq_id] = list(hashes)
        if tenant is not None:
            self._seq_tenant[seq_id] = str(tenant)
        cached_tokens = len(matched) * self.block_size
        self.seq_cached_tokens[seq_id] = cached_tokens
        if self.prefix_cache and tokens is not None:
            pm = _prefix_metrics()
            if matched:
                self.prefix_hits += 1
                self.prefix_blocks_saved += len(matched)
                self.prefix_tokens_saved += cached_tokens
                pm.hits.inc()
                pm.blocks_saved.inc(len(matched))
                pm.tokens_saved.inc(cached_tokens)
                pm.cached.set(self.allocator.num_cached)
                telemetry.record_event(
                    "kv.share", seq=str(seq_id), blocks=len(matched),
                    cached_tokens=cached_tokens)
            else:
                self.prefix_misses += 1
                pm.misses.inc()
        return True

    def extend(self, seq_id, num_tokens: int) -> bool:
        """Grow ``seq_id``'s table to cover ``num_tokens`` tokens; False on
        pool exhaustion (nothing is allocated partially)."""
        table = self._table(seq_id)
        need = self.blocks_for(num_tokens) - len(table)
        if need <= 0:
            return True
        blocks = self._alloc_evict(need)
        if blocks is None:
            return False
        table.extend(blocks)
        return True

    def ensure_writable(self, seq_id, position: int) -> bool:
        """Copy-on-write guard: the next K/V write for ``seq_id`` lands at
        ``position``. If that block is shared (rc > 1), allocate a private
        block, copy the pool slice, and patch the table; if it is this
        sequence's sole reference but still *indexed*, unregister it (the
        write would make the index entry lie about its content). False when
        the CoW allocation fails — the caller preempts or fails the
        sequence, never writes a shared block."""
        if position < 0:
            return True
        table = self._table(seq_id)
        idx = position // self.block_size
        block = table[idx]
        # chaos site: "exhaust" models the CoW allocation failing mid-decode
        if faults.inject("serving.kv.cow", seq=str(seq_id),
                         block=block) == "exhaust":
            telemetry.record_event("kv.cow", seq=str(seq_id), block=block,
                                   granted=False, injected=True)
            return False
        rc = self.allocator.refcount(block)
        if rc <= 1:
            if block in self._block_key:
                key = self._block_key.pop(block)
                if self._index.get(key) == block:
                    del self._index[key]
                self._block_hash.pop(block, None)
            return True
        new = self._alloc_evict(1)
        if new is None:
            telemetry.record_event("kv.cow", seq=str(seq_id), block=block,
                                   granted=False)
            return False
        [new_block] = new
        self.pool = self.pool.at[:, new_block].set(self.pool[:, block])
        self.allocator.free([block])             # rc > 1: pure decrement
        table[idx] = new_block
        self.cow_copies += 1
        _prefix_metrics().cow.inc()
        telemetry.record_event("kv.cow", seq=str(seq_id), src=block,
                               dst=new_block)
        return True

    def fork(self, parent_id, child_id) -> None:
        """Give ``child_id`` a table sharing every one of ``parent_id``'s
        blocks (rc += 1 each) — the foundation for parallel sampling /
        best-of-n. The first divergent write on either side goes through
        :meth:`ensure_writable`'s copy-on-write."""
        if child_id in self.tables:
            raise ValueError(f"sequence {child_id!r} already has a table")
        table = self._table(parent_id)
        self.allocator.share(table)
        self.tables[child_id] = list(table)
        self._seq_hashes[child_id] = list(self._seq_hashes.get(parent_id, []))
        self.seq_cached_tokens[child_id] = 0
        if parent_id in self._seq_tenant:
            self._seq_tenant[child_id] = self._seq_tenant[parent_id]

    def free_seq(self, seq_id):
        """Drop ``seq_id``'s references. Indexed blocks whose rc reaches 0
        park in the LRU pool instead of the free list (their K/V stays
        valid for prefix hits). Registration itself only ever happens at
        :meth:`commit_prefix` — the points where the caller *knows* the
        K/V is in the pool — so a sequence torn down after a failed
        prefill can never poison the index with unwritten blocks."""
        if seq_id not in self.tables:
            raise ValueError(
                f"unknown sequence {seq_id!r}: no block table (never "
                f"allocated or already freed)")
        table = self.tables.pop(seq_id)
        self._seq_hashes.pop(seq_id, None)
        self.seq_cached_tokens.pop(seq_id, None)
        tenant = self._seq_tenant.pop(seq_id, None)
        registered = [b for b in table if b in self._block_key]
        plain = [b for b in table if b not in self._block_key]
        if plain:
            self.allocator.free(plain)
        if registered:
            for b in self.allocator.release(registered):
                self._lru_park(b, tenant)        # newest end of the LRU
            _prefix_metrics().cached.set(self.allocator.num_cached)

    def utilization(self) -> float:
        return self.allocator.num_used / max(self.allocator.num_usable, 1)

    def prefix_stats(self) -> dict:
        hits, misses = self.prefix_hits, self.prefix_misses
        return {
            "enabled": self.prefix_cache,
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
            "blocks_saved": self.prefix_blocks_saved,
            "tokens_saved": self.prefix_tokens_saved,
            "cow_copies": self.cow_copies,
            "evictions": self.prefix_evictions,
            "stale_drops": self.stale_drops,
            "cached_blocks": self.allocator.num_cached,
            "indexed_blocks": len(self._block_key),
            "tenants": {
                t: {"cached_blocks": self._tenant_cached.get(t, 0),
                    "quota": self._tenant_quota.get(t),
                    "quota_evictions": self.quota_evictions.get(t, 0)}
                for t in sorted(set(self._tenant_cached)
                                | set(self._tenant_quota)
                                | set(self.quota_evictions))},
            "spill": {
                "enabled": self.spill_blocks > 0,
                "limit_blocks": self.spill_blocks,
                "spilled_blocks": len(self._spill),
                "spilled_bytes": self.spilled_bytes,
                "spills": self.spills,
                "spill_drops": self.spill_drops,
                "spill_errors": self.spill_errors,
                "promotes": self.promotes,
                "promote_errors": self.promote_errors,
                "promote_corrupt_drops": self.promote_corrupt_drops,
            },
            "fabric": {
                "exports": self.fabric_exports,
                "export_frames": self.fabric_export_frames,
                "ingests": self.fabric_ingests,
                "ingested_blocks": self.fabric_ingested_blocks,
                "ingest_corrupt": self.fabric_ingest_corrupt,
                "ingest_errors": self.fabric_ingest_errors,
            },
        }

    @property
    def state_nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.state or ())

    def table_array(self, seq_ids, max_blocks: int) -> np.ndarray:
        """Fixed-shape [len(seq_ids), max_blocks] int32 table; absent ids
        and padding rows point at the scratch block."""
        out = np.full((len(seq_ids), max_blocks), SCRATCH_BLOCK, np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is None or sid not in self.tables:
                continue
            t = self.tables[sid]
            out[i, :len(t)] = t
        return out


def _put_row(rows, row, layer_idx, slot):
    """``rows[layer_idx, slot] = row`` as one dynamic-update-slice (in
    place on a donated buffer)."""
    at = (jnp.int32(layer_idx), slot.astype(jnp.int32)) + (
        jnp.int32(0),) * row.ndim
    return jax.lax.dynamic_update_slice(
        rows, row[None, None].astype(rows.dtype), at)


class PagedCacheView:
    """Per-trace functional view of the pool, passed to the model as
    ``cache=``. The model's attention layers call :meth:`attend` once per
    layer; each call takes ``self.pool`` and leaves the updated pool there —
    the jitted step returns it as an output. In decode that is the whole
    pool through the layer's kernel and out again, the new rows written by
    the kernel in place (on the chip one buffer from the step's donated
    argument to its output: nothing between the calls may read the pool);
    the prefills' whole-block writes are functional (``pool.at[...]``).

    ``windows`` is the layers' window (``CacheLayer.window``), static: a
    layer with one sees only its latest ``window`` positions, in every mode
    below. The pool still keeps every position of every layer (one block
    table a sequence); a window shortens the walk, not the table.

    ``state`` is the cache's per-slot arrays for a model with state layers
    (``PagedKVCache.state``: recurrent state ``[L, slots, H, N, P]`` and
    conv inputs ``[L, slots, K - 1, C]``), threaded like the pool: a
    recurrent layer calls :meth:`shift` (its conv's window) and
    :meth:`recur` (the recurrence) where an attention layer calls
    :meth:`attend`. In decode a batch row is its slot's row; a prefill
    (batch 1) starts from zeros and writes row ``slot``: the state after the
    last *valid* token (padding's ``dt`` is zeroed, which leaves a state as
    it is) and the conv's last valid inputs. The view knows no model.

    A model may hand the step integers about itself with :meth:`count`
    (what its sparse layers routed where); they accumulate on
    ``self.counters`` and the jitted step returns them beside the pool.

    Three modes, keyed on the query's token count and the prefix args:
    - decode (S_new == 1): batched slots, one token each; one call
      (``kernels.paged_decode_impl``) writes the token's K/V at position
      ``ctx_lens[s]`` through the block table and runs the ragged
      paged-attention kernel over ``ctx_lens + 1`` tokens.
    - prefill (S_new > 1, batch 1): the padded prompt; scatters whole blocks
      into the pool and attends causally within the prompt
      (``kernels.prefill_attention_impl``) — no pool reads, so concurrent
      sequences are untouched.
    - tail prefill (S_new > 1 with ``prefix_block_tables``): the divergent
      tail of a prefix-cache hit; scatters the tail like prefill, then
      attends over [gathered cached prefix K/V ++ tail K/V] with the
      causal mask offset by ``prefix_len`` — the cached blocks are read,
      never written.
    """

    def __init__(self, pool, block_tables, ctx_lens, block_size,
                 prefix_block_tables=None, prefix_len=None, windows=None,
                 valid_len=None, state=None, slot=None):
        self.pool = pool                      # [L, N, 2, H, bs, D]
        self.state = state                    # None | (recurrent, conv)
        self.slot = slot                      # prefill: the state's row
        self.block_tables = block_tables      # [S, M] int32
        self.ctx_lens = ctx_lens              # [S] int32 (None for prefill)
        self.block_size = int(block_size)
        self.prefix_block_tables = prefix_block_tables  # [1, NPB] or None
        self.prefix_len = prefix_len          # int32 scalar (valid tokens)
        self.windows = windows                # per layer: None | int
        self.valid_len = valid_len            # prefill: tokens before padding
        self.kept_last_rows = False           # set by last_rows (prefill)
        self.counters: dict = {}
        # prefill attention layers traced on each path (trace time)
        self.attention_paths = {"kernel": 0, "materialised": 0}

    def _window(self, layer_idx):
        return None if self.windows is None else self.windows[layer_idx]

    def live_rows(self, shape):
        """bool ``shape`` ([slots, 1] in decode, [1, P] in prefill): the
        rows of this step that are some request's token. An inactive decode
        slot carries the all-zero block table; a prompt's padding lies past
        ``valid_len``."""
        if self.ctx_lens is not None:
            return jnp.broadcast_to(self.block_tables[:, :1] != 0, shape)
        if self.valid_len is None:
            return jnp.ones(shape, bool)
        return jnp.broadcast_to(
            jnp.arange(shape[1], dtype=jnp.int32)[None] < self.valid_len,
            shape)

    def count(self, **named):
        """Add integers (traced scalars) to the step's named counters."""
        for name, v in named.items():
            self.counters[name] = self.counters.get(name, 0) + v

    def last_rows(self, h):
        """Of a step's hidden states ``[B, S, H]``, the rows whose logits
        the step samples: a prefill's last valid position (``[1, 1, H]``;
        a model that calls this computes no ``[P, vocab]`` logits), every
        row of a decode step."""
        if self.ctx_lens is not None or self.valid_len is None:
            return h
        self.kept_last_rows = True
        return jax.lax.dynamic_slice_in_dim(h, self.valid_len - 1, 1, axis=1)

    # the hooks a recurrent layer calls (raw arrays in/out)
    def shift(self, layer_idx, u):
        """The causal conv's window of state layer ``layer_idx``: this
        step's inputs ``u [B, S, C]`` with the ``K - 1`` before them in
        front, ``[B, S + K - 1, C]``; the last ``K - 1`` valid ones are
        kept for the next step."""
        recurrent, conv = self.state
        k1 = conv.shape[2]
        if self.ctx_lens is not None:
            window = jnp.concatenate([conv[layer_idx], u], axis=1)
            kept = window[:, window.shape[1] - k1:]
            self.state = (recurrent, conv.at[layer_idx].set(kept))
            return window
        window = jnp.pad(u, ((0, 0), (k1, 0), (0, 0)))
        # inputs valid_len - (K - 1) .. valid_len - 1, zeros before the
        # sequence, whatever the padding holds
        kept = jax.lax.dynamic_slice_in_dim(window[0], self.valid_len, k1,
                                            axis=0)
        self.state = (recurrent, _put_row(conv, kept, layer_idx, self.slot))
        return window

    def recur(self, layer_idx, x, dt, a, b, c, *, chunk):
        """The selective recurrence of state layer ``layer_idx`` (Mamba-2's
        ``S <- exp(dt a) S + dt x (x) b``, ``y = S c``) over this step's
        tokens, the skip term left to the caller. x ``[B, S, H, P]``; dt
        ``[B, S, H]`` (after softplus); a ``[H]``; b, c ``[B, S, G, N]``.
        Returns y ``[B, S, H, P]`` float32."""
        from ..kernels.ssd_chunk_scan import ssd_chunk_scan
        from ..kernels.ssm_state_update import ssm_state_update

        recurrent, conv = self.state
        if self.ctx_lens is not None:
            # the whole state in, the whole state out, rows updated in
            # place: nothing here slices or scatters it
            y, recurrent = ssm_state_update(
                recurrent, layer_idx, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0])
            self.state = (recurrent, conv)
            return y[:, None]
        if x.shape[0] != 1:
            raise ValueError(f"prefill expects batch 1; got {x.shape[0]}")
        # a padded position neither decays the state nor adds to it
        dt = jnp.where(self.live_rows(dt.shape[:2])[..., None], dt, 0.0)
        y, final = ssd_chunk_scan(x[0], dt[0], a, b[0], c[0], chunk=chunk)
        self.state = (_put_row(recurrent, final, layer_idx, self.slot), conv)
        return y[None]

    # the duck-typed hook LlamaAttention calls (raw arrays in/out)
    def attend(self, layer_idx, q, k, v):
        if q.shape[1] == 1:
            return self._decode(layer_idx, q, k, v)
        return self._prefill(layer_idx, q, k, v)

    def _decode(self, layer_idx, q, k, v):
        from ..kernels import paged_decode_impl

        # the whole pool in, the whole pool out: nothing here slices or
        # scatters it, so that on the chip the layers' kernels hand one
        # buffer down the step (see kernels/paged_attention.py)
        out, self.pool = paged_decode_impl()(
            q[:, 0], k[:, 0], v[:, 0], self.pool, self.block_tables,
            self.ctx_lens.astype(jnp.int32) + 1, layer_idx=layer_idx,
            window=self._window(layer_idx))              # [S, Hq, D]
        return out[:, None]                              # [S, 1, Hq, D]

    def _write_prompt_blocks(self, layer_idx, k, v):
        """Scatter a batch-1 block-multiple prompt segment into the pool."""
        bs = self.block_size
        P = k.shape[1]
        nb = P // bs
        # [1, P, Hkv, D] -> [nb, Hkv, bs, D] block layout
        kb = k[0].reshape(nb, bs, -1, k.shape[-1]).transpose(0, 2, 1, 3)
        vb = v[0].reshape(nb, bs, -1, v.shape[-1]).transpose(0, 2, 1, 3)
        bt = self.block_tables[0, :nb]
        pool = self.pool.at[layer_idx, bt, 0].set(kb)
        pool = pool.at[layer_idx, bt, 1].set(vb)
        self.pool = pool

    def _prefill(self, layer_idx, q, k, v):
        """A batch-1 prompt: its whole blocks written, then causal
        attention within it. The plain prefill takes
        ``kernels.prefill_attention_impl`` (on the chip the flash kernel:
        one Mosaic call, no ``[H, P, P]`` scores, no key block read outside
        the window or past ``valid_len``; rows past it are garbage that
        never flows back, by causality, and is never read: the engine takes
        logits at the last valid position). The tail prefill keeps the
        einsum composition over ``[prefix ++ tail]``: no benchmark cell
        shares a prefix, and each asserts that its prefix counters read 0."""
        bs = self.block_size
        P = k.shape[1]
        if q.shape[0] != 1 or P % bs:
            raise ValueError(
                f"prefill expects batch 1 and a block-multiple length; got "
                f"batch {q.shape[0]}, len {P}, block_size {bs}")
        self._write_prompt_blocks(layer_idx, k, v)
        window = self._window(layer_idx)
        if self.prefix_block_tables is None:
            from ..kernels import prefill_attention_impl

            attend = prefill_attention_impl(P)
            self.attention_paths[
                "materialised" if attend is sdpa_window_ref else "kernel"] += 1
            return attend(q, k, v, is_causal=True, window=window,
                          valid_len=self.valid_len)

        # tail prefill: gather the cached prefix K/V through its block
        # table (padding entries point at scratch and are masked off by
        # prefix_len) and attend causally over [prefix ++ tail]
        self.attention_paths["materialised"] += 1
        pbt = self.prefix_block_tables[0]                # [NPB]
        spfx = pbt.shape[0] * bs
        pkv = self.pool[layer_idx, pbt]                  # [NPB, 2, H, bs, D]
        pk = pkv[:, 0].transpose(0, 2, 1, 3).reshape(
            spfx, -1, k.shape[-1])[None]                 # [1, Spfx, Hkv, D]
        pv = pkv[:, 1].transpose(0, 2, 1, 3).reshape(
            spfx, -1, v.shape[-1])[None]
        k_full = jnp.concatenate([pk, k], axis=1)
        v_full = jnp.concatenate([pv, v], axis=1)
        qi = jnp.arange(P, dtype=jnp.int32)[:, None]
        kj = jnp.arange(spfx + P, dtype=jnp.int32)[None, :]
        mask = jnp.where(kj < spfx, kj < self.prefix_len,
                         (kj - spfx) <= qi)              # [P, Spfx + P]
        if window is not None:
            # key positions: the prefix's own, then prefix_len + tail index
            kpos = jnp.where(kj < spfx, kj, self.prefix_len + kj - spfx)
            mask &= kpos > self.prefix_len + qi - window
        return sdpa_ref(q, k_full, v_full, attn_mask=mask[None, None])


class DenseKVCache:
    """Concatenating KV cache (the classic ``past_kv``): layer i holds the
    full [B, S_past, kv_heads, head_dim] K/V. Quadratic in memory across a
    long decode — the paged cache replaces it in the engine — but it is the
    simplest correct reference, used by the cached-decode parity tests.
    ``windows`` as :class:`PagedCacheView`'s (``CacheLayer.window`` of each
    layer; the past is kept whole all the same)."""

    def __init__(self, num_layers: int, windows=None):
        self.layers: list = [None] * num_layers
        self.windows = windows

    @property
    def seq_len(self) -> int:
        kv = self.layers[0]
        return 0 if kv is None else int(kv[0].shape[1])

    def attend(self, layer_idx, q, k, v):
        past = self.layers[layer_idx]
        if past is not None:
            k = jnp.concatenate([past[0], k], axis=1)
            v = jnp.concatenate([past[1], v], axis=1)
        self.layers[layer_idx] = (k, v)
        # q token i sits at global position (Sk - Sq + i): attends j <= that
        return sdpa_window_ref(
            q, k, v, window=None if self.windows is None
            else self.windows[layer_idx])
