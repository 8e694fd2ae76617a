"""Host-side paged-KV bookkeeping: page allocator with content-hash prefix reuse.

The device cache itself is a JAX array ([L, 2, P, ps, Hk, Dh], models/transformer.py);
this module owns which page holds what:

- free-list allocation,
- automatic prefix caching: completed pages are indexed by chained block hash
  (core/kv_events.hash_block_tokens) and reused by later requests — the engine-side
  feature the reference's prefix-aware routing relies on
  (model-servers.md 'Prefix Cache Reuse'),
- LRU eviction of unreferenced cached pages,
- KV-event emission (BlockStored / BlockRemoved / AllBlocksCleared) for the indexer
  plane (kv-indexer.md:59-63).
"""

from __future__ import annotations

from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Optional

from llmd_tpu.core.kv_events import (
    AllBlocksCleared,
    BlockRemoved,
    BlockStored,
    KVEvent,
    hash_block_tokens,
)


@dataclass
class PageInfo:
    refs: int = 0
    block_hash: Optional[int] = None  # set once the page holds a complete, hashed block
    lora_id: Optional[str] = None     # adapter the block was computed under


class PageAllocator:
    """Reference-counted page allocator with content-addressed reuse."""

    def __init__(
        self,
        num_pages: int,
        page_size: int,
        enable_prefix_caching: bool = True,
        event_sink: Optional[Callable[[list[KVEvent]], None]] = None,
        medium: str = "gpu",
        base_id: int = 0,
    ) -> None:
        self.num_pages = num_pages
        self.page_size = page_size
        self.enable_prefix_caching = enable_prefix_caching
        self.event_sink = event_sink
        self.medium = medium
        # Called (block_hash, page_id) just before a cached page is recycled —
        # the offload connector's HBM→CPU hook (kv/offload.py).
        self.evict_hook: Optional[Callable[[int, int], None]] = None
        # base_id: first page id owned by this allocator — DP rank engines sharing
        # one device pool each manage a disjoint contiguous id range (wide-EP).
        self.base_id = base_id
        self.free: deque[int] = deque(range(base_id, base_id + num_pages))
        self.pages: dict[int, PageInfo] = {}
        # block_hash → page_id for complete blocks still resident (any refcount)
        self.cached: dict[int, int] = {}
        # refcount-0 cached pages in LRU order (evictable)
        self.lru: OrderedDict[int, int] = OrderedDict()  # block_hash → page_id

    # -- events ------------------------------------------------------------
    def _emit(self, events: list[KVEvent]) -> None:
        if self.event_sink and events:
            self.event_sink(events)

    # -- queries -----------------------------------------------------------
    @property
    def num_free(self) -> int:
        """Pages allocatable right now (truly free + evictable cached)."""
        return len(self.free) + len(self.lru)

    @property
    def num_active(self) -> int:
        return self.num_pages - self.num_free

    def utilization(self) -> float:
        return self.num_active / max(1, self.num_pages)

    def match_prefix(self, block_hashes: list[int]) -> list[int]:
        """Longest consecutive resident prefix → page ids (kv-indexer.md scorer walk)."""
        out: list[int] = []
        for h in block_hashes:
            pid = self.cached.get(h)
            if pid is None:
                break
            out.append(pid)
        return out

    # -- allocation --------------------------------------------------------
    def allocate(self) -> Optional[int]:
        """Allocate a fresh (uncached) page; evict LRU cached page if needed."""
        if self.free:
            pid = self.free.popleft()
        elif self.lru:
            h, pid = self.lru.popitem(last=False)
            if self.evict_hook is not None:
                self.evict_hook(h, pid)
            del self.cached[h]
            del self.pages[pid]
            self._emit([BlockRemoved(block_hashes=[h], medium=self.medium)])
        else:
            return None
        self.pages[pid] = PageInfo(refs=1)
        return pid

    def acquire_cached(self, page_id: int) -> None:
        info = self.pages[page_id]
        if info.refs == 0 and info.block_hash is not None:
            self.lru.pop(info.block_hash, None)
        info.refs += 1

    def commit_block(
        self,
        page_id: int,
        block_hash: int,
        token_ids: list[int],
        parent_hash: Optional[int],
        lora_id: Optional[str] = None,
    ) -> None:
        """Mark a page as holding a complete block; index + announce it."""
        if not self.enable_prefix_caching:
            return
        info = self.pages[page_id]
        if info.block_hash == block_hash:
            return
        if self.cached.get(block_hash) is not None:
            # Same content computed twice (two identical prompts prefilling
            # concurrently). Keep the existing index entry; leave THIS page unhashed so
            # it returns to the plain free list on release — re-indexing would corrupt
            # the cached/lru invariant (one page per hash).
            return
        info.block_hash = block_hash
        info.lora_id = lora_id
        self.cached[block_hash] = page_id
        self._emit([
            BlockStored(
                block_hashes=[block_hash], parent_block_hash=parent_hash,
                token_ids=list(token_ids), block_size=self.page_size,
                lora_id=lora_id, medium=self.medium,
            )
        ])

    def release(self, page_id: int) -> None:
        """Drop one reference; refcount-0 pages stay cached (evictable) or free."""
        info = self.pages.get(page_id)
        if info is None:
            return
        info.refs -= 1
        if info.refs > 0:
            return
        if info.block_hash is not None and self.enable_prefix_caching:
            self.lru[info.block_hash] = page_id
            self.lru.move_to_end(info.block_hash)
        else:
            del self.pages[page_id]
            self.free.append(page_id)

    def demote_lru(self, n: int) -> list[tuple[int, int]]:
        """Pop the n oldest evictable cached pages onto the free list and return
        their (block_hash, page_id) pairs — the offload connector's batched-drain
        entry (one D2H gather for the whole batch instead of per-page syncs in
        allocate()). The evict_hook is NOT called; the caller owns the copy-out,
        which is safe until the freed pages are reallocated AND rewritten."""
        pairs: list[tuple[int, int]] = []
        while self.lru and len(pairs) < n:
            h, pid = self.lru.popitem(last=False)
            pairs.append((h, pid))
            del self.cached[h]
            del self.pages[pid]
            self.free.append(pid)
        if pairs:
            self._emit([BlockRemoved(block_hashes=[h for h, _ in pairs], medium=self.medium)])
        return pairs

    def purge_lora(self, lora_id: str) -> int:
        """Drop cached blocks computed under an adapter (prompt memory reclaim at
        unload). Correctness does not depend on this: block hashes carry the
        generation-scoped lora_key, so stale KV can never match anyway — this
        just frees the pages early. Matches both bare names and "name@gen" keys."""
        removed: list[int] = []
        for h, pid in list(self.cached.items()):
            info = self.pages.get(pid)
            if info is None or info.lora_id is None or not (
                info.lora_id == lora_id or info.lora_id.startswith(lora_id + "@")
            ):
                continue
            del self.cached[h]
            if h in self.lru:  # evictable → page returns to the free list
                self.lru.pop(h)
                del self.pages[pid]
                self.free.append(pid)
            else:  # in use by a live sequence: keeps serving it, never re-matched
                info.block_hash = None
            removed.append(h)
        if removed:
            self._emit([BlockRemoved(block_hashes=removed, medium=self.medium)])
        return len(removed)

    def clear(self) -> None:
        self.free = deque(range(self.base_id, self.base_id + self.num_pages))
        self.pages.clear()
        self.cached.clear()
        self.lru.clear()
        self._emit([AllBlocksCleared()])


@dataclass
class Sequence:
    """One in-flight request's engine-side state."""

    request_id: str
    token_ids: list[int]  # prompt + generated
    prompt_len: int
    max_tokens: int
    sampling: "object" = None  # SamplingParams
    lora_id: Optional[str] = None
    # generation-scoped hash key (engine._lora_hash_key): "name@<load-ns>" when
    # LoRA serving is on, == lora_id otherwise. All block hashing uses THIS, so
    # KV computed under unloaded/reloaded weights can never prefix-match again —
    # in HBM, the CPU tier, or FS files surviving a restart.
    lora_key: Optional[str] = None
    pages: list[int] = field(default_factory=list)
    num_computed: int = 0  # tokens whose KV is resident
    num_cached_prompt: int = 0  # tokens reused from prefix cache
    slot: int = -1  # decode batch slot
    recompute: bool = False  # preempted, and not dispatched from position 0 since
    finished: bool = False
    finish_reason: Optional[str] = None
    block_hashes: list[int] = field(default_factory=list)  # chained hashes of committed blocks
    arrival_time: float = 0.0
    first_token_time: Optional[float] = None
    rank: int = 0  # owning DP rank scheduler (wide-EP; 0 in single-rank engines)
    # pod-state features frozen at arrival/admission — the predictor's training
    # rows (latency-predictor.md:58): what the EPP could have observed when it
    # routed this request, joined with the latencies the engine then delivered
    admit_features: Optional[dict] = None
    # multimodal: (content_hash, embeds [mm_tokens, hidden]) per media item, in
    # prompt order; placeholder occurrence j in token_ids draws row j % k of
    # item j // k. Hashes fold into every block key (kv-indexer.md mm extra
    # keys) so two prompts with identical tokens but different media never share
    # cache entries.
    mm_items: list = field(default_factory=list)
    # obs.tracing.SpanContext of the request span (engine.generate) when the
    # request arrived traced — engine step spans parent onto it
    trace_ctx: Optional[object] = None
    # Speculative decoding tallies (engine/spec.py): drafted/accepted feed the
    # per-request acceptance-rate summary observed at retirement.
    spec_drafted: int = 0
    spec_accepted: int = 0
    # Per-sequence draft arming: the prompt-lookup probe is O(context) host
    # work, so a row whose probe came up empty stays disarmed until fresh
    # tokens land for IT (decode/sample/verify). Per-sequence — one
    # non-repetitive stream must not disarm drafting for the whole batch.
    spec_armed: bool = True
    # Arm/disarm transitions over the sequence lifetime — the decision
    # ledger's thrash signal (obs/decisions.py): a high flip count means the
    # probe keeps oscillating between drafting and giving up.
    spec_flips: int = 0
    # Structured outputs (llmd_tpu/structured): the per-sequence automaton
    # cursor (StructuredState) when the request is grammar-constrained. The
    # cursor derives from token_ids, which preemption preserves, so recompute
    # resumes the automaton with no extra state handling.
    structured: Optional[object] = None
    # Static OpenAI logit_bias map (token id -> bias); rides the same device
    # bias-add rows the grammar mask uses.
    logit_bias: Optional[dict] = None

    @property
    def num_generated(self) -> int:
        return len(self.token_ids) - self.prompt_len

    def last_block_hash(self) -> Optional[int]:
        return self.block_hashes[-1] if self.block_hashes else None

    def maybe_commit_blocks(self, alloc: PageAllocator) -> None:
        """Hash+commit any newly completed pages (called after compute
        advances, or after a token lands that compute had run ahead of: a
        block is committed once it is both computed and known to the host)."""
        ps = alloc.page_size
        committed = len(self.block_hashes)
        done = min(self.num_computed, len(self.token_ids))
        if (committed + 1) * ps > done:
            return
        mm = self.mm_hashes()
        while (committed + 1) * ps <= done:
            start = committed * ps
            chunk = self.token_ids[start : start + ps]
            key = self.lora_key if self.lora_key is not None else self.lora_id
            h = hash_block_tokens(self.last_block_hash(), chunk, key, mm)
            alloc.commit_block(self.pages[committed], h, chunk, self.last_block_hash(), key)
            self.block_hashes.append(h)
            committed += 1

    def mm_hashes(self) -> list[bytes]:
        return [h for h, _ in self.mm_items]
