"""Continuous-batching scheduler: the per-request state machine.

Requests move WAITING → PREFILL → DECODE → DONE.  The device-side decode
step has STATIC shape — ``max_batch`` slots, an active mask — so
admission and eviction are pure host bookkeeping between decode bursts:
a fresh slot's token/length/page-table rows are rewritten and the next
burst's ``device_put`` ships the same-shaped arrays (zero retraces, the
recompile watch in ``serve_bench`` proves it over a whole trace).

Admission policy: FCFS with head-of-line blocking, and ALL pages a
request can ever need — ``ceil((prompt + max_new) / page_size)`` — are
granted at admit time.  Lazier per-token growth would pack more
requests in, but a request mid-decode could then hit an empty free list
and must be preempted (re-prefilled later); granting up front makes
admitted requests run to completion unconditionally, which is the right
trade at this repo's tier and keeps the engine's device loop free of
page-fault paths.  Eviction (page + slot release) happens at the sync
point where a request's emission count reaches ``max_new``.

A granted SLOT is also the grant of that slot's fixed-size state in a
block that keeps some (its ``"linear"`` layers:
``kv_pool.PoolBuffers.state/conv``, one entry a batch slot).  Nothing is
copied or zeroed on the host: the request's first prefill chunk starts
from zeros whatever the slot's last request left
(``engine._paged_block_forward``), and the engine counts the grant as a
``state_resets``.  The state needs no page, so ``pages_needed`` is the
full-attention layers' alone.

Where the pool has a second page class (``kv_pool``: the window layers'
rings), a request is granted from BOTH allocators or not at all: its whole
length in full-class pages as above, and ``min(total, ring rows)`` in
window-class pages (``Request.pages_window``; a request shorter than the
ring never wraps it).  FCFS and head-of-line blocking are unchanged: the
head request waits while EITHER class is short, and retirement frees both.

Timestamps are elapsed seconds on the engine's clock: ``t_submit`` is
the request's (virtual) arrival, ``t_first`` when its first token
resolved on the host (prefill is synchronous at admission, so TTFT is
measured at token resolution), ``t_done`` at the retiring sync point —
so per-token latency is measured at sync granularity, the price of a
burst's one read.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import numpy as np

from .kv_pool import PageAllocator

WAITING = "WAITING"
PREFILL = "PREFILL"
DECODE = "DECODE"
DONE = "DONE"


@dataclass
class Request:
    """One generation request plus its runtime state.  ``tokens`` holds
    the emitted ids (greedy continuation of ``prompt``); the first entry
    comes from the prefill's last-position logits."""
    rid: int
    prompt: np.ndarray          # (S0,) int32
    max_new_tokens: int
    #: virtual arrival time; None = "now" at submit.  An explicit None
    #: sentinel, NOT falsy-0.0 — the first request of every virtual
    #: trace legitimately arrives at 0.0 and must keep that timestamp.
    arrival_s: float | None = None
    #: distributed trace id, minted once at Router.submit (or by the
    #: single engine's submit).  Part of request IDENTITY, not runtime
    #: state: ``reset_for_replay`` preserves it, so every span/step
    #: event from a dead replica's attempt and its survivor replay
    #: joins into one swimlane.
    trace_id: str | None = None

    state: str = WAITING
    slot: int | None = None
    pages: list[int] | None = None
    #: radix-cache nodes this request holds refs on; a prefix of
    #: ``pages`` (same order) — those pages are TRIE-owned, only
    #: ``pages[len(cache_nodes):]`` go back to the allocator at retire
    cache_nodes: list = field(default_factory=list)
    #: the request's ring in the window page class, where the pool has
    #: one (all request-owned: nothing of a ring is shared)
    pages_window: list[int] | None = None
    prefill_pos: int = 0
    tokens: list[int] = field(default_factory=list)
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None
    t_done: float | None = None

    @property
    def n_prompt(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def ttft_s(self) -> float | None:
        if self.t_first is None or self.t_submit is None:
            return None
        return self.t_first - self.t_submit

    @property
    def per_token_s(self) -> float | None:
        """Mean decode latency per token AFTER the first (TTFT owns the
        first); sync-granular — see the module docstring."""
        if self.t_done is None or self.t_first is None:
            return None
        return (self.t_done - self.t_first) / max(len(self.tokens) - 1, 1)


class ContinuousBatcher:
    """Slot + page bookkeeping for the engine.  Owns the waiting queue,
    the ``max_batch`` slot table and the page allocator; knows nothing
    about devices."""

    def __init__(self, max_batch: int, allocator: PageAllocator,
                 page_size: int, *,
                 window_allocator: PageAllocator | None = None,
                 ring_pages: int = 0):
        self.max_batch = int(max_batch)
        self.allocator = allocator
        self.page_size = int(page_size)
        # the window page class (None: the pool has one class) and the
        # most pages of it a request holds, its ring
        self.window_allocator = window_allocator
        self.ring_pages = int(ring_pages)
        if window_allocator is not None and self.ring_pages < 1:
            raise ValueError("a window page class needs ring_pages >= 1")
        self.waiting: deque[Request] = deque()
        self.slots: list[Request | None] = [None] * self.max_batch
        self.admitted_total = 0
        self.completed_total = 0
        # live MetricsRegistry, late-assigned by the engine; None-safe
        self.metrics = None
        # RadixPrefixCache, late-assigned by the engine when prefix
        # caching is on; None = every page comes from the allocator
        self.prefix_cache = None

    # ---- queries ------------------------------------------------------
    def has_work(self) -> bool:
        return bool(self.waiting) or any(
            r is not None for r in self.slots)

    def slot_request(self, b: int) -> Request | None:
        return self.slots[b]

    def next_prefill(self) -> Request | None:
        """The oldest request still in PREFILL (chunked prefill drains
        FCFS — one long prompt can't starve, it just shares rounds)."""
        cands = [r for r in self.slots
                 if r is not None and r.state == PREFILL]
        return min(cands, key=lambda r: r.t_admit) if cands else None

    def pages_needed(self, req: Request, window: bool = False) -> int:
        """Pages of one class ``req`` is granted at admission: its whole
        length in the full class; in the ``window`` class no more than a
        ring."""
        total = req.n_prompt + req.max_new_tokens
        need = -(-total // self.page_size)
        return min(need, self.ring_pages) if window else need

    # ---- transitions --------------------------------------------------
    def submit(self, req: Request, now: float) -> None:
        req.state = WAITING
        # explicit None check: arrival_s == 0.0 is a real timestamp
        # (the head of every virtual trace), not "unset"
        req.t_submit = req.arrival_s if req.arrival_s is not None else now
        self.waiting.append(req)

    def admit(self, now: float) -> list[Request]:
        """FCFS: admit while a slot AND the full page grant are free.
        Head-of-line blocking is deliberate — skipping ahead would
        starve long requests under load.

        With a prefix cache attached, the grant counts only the
        NON-CACHED suffix: cached full-prompt pages are aliased (refs
        taken, never written — see ``kv_pool.RadixPrefixCache``) and
        prefill starts at the matched page boundary.  Under pool
        pressure the cache is asked to evict idle pages before the
        head request is declared blocked."""
        admitted = []
        cache = self.prefix_cache
        while self.waiting:
            free = [b for b, r in enumerate(self.slots) if r is None]
            if not free:
                break
            req = self.waiting[0]
            nodes = cache.match(req.prompt) if cache is not None else []
            need = self.pages_needed(req) - len(nodes)
            pages = self.allocator.alloc(need)
            if pages is None and cache is not None:
                # pin the matched prefix first: its refs-0 nodes are
                # legal LRU victims, and evicting a page this request
                # is about to alias would hand it a freed page
                cache.acquire(nodes)
                ev = cache.evict(need - self.allocator.free_pages)
                cache.release(nodes)
                if ev:
                    from ..telemetry.metrics import maybe_inc
                    maybe_inc(self.metrics,
                              "prefix_cache_evictions_total", ev)
                pages = self.allocator.alloc(need)
            if pages is None:
                break
            if self.window_allocator is not None:
                ring = self.window_allocator.alloc(
                    self.pages_needed(req, window=True))
                if ring is None:        # both grants or neither
                    self.allocator.free(pages)
                    break
                req.pages_window = ring
            self.waiting.popleft()
            req.pages = [n.page for n in nodes] + pages
            req.cache_nodes = list(nodes)
            req.prefill_pos = len(nodes) * self.page_size
            if cache is not None:
                cache.acquire(nodes)
                n_full = (req.n_prompt - 1) // self.page_size
                cache.note_lookup(len(nodes), n_full)
                from ..telemetry.metrics import maybe_inc
                maybe_inc(self.metrics, "prefix_cache_hit_pages_total",
                          len(nodes))
                maybe_inc(self.metrics,
                          "prefix_cache_lookup_pages_total", n_full)
            req.slot = free[0]
            req.state = PREFILL
            req.t_admit = now
            self.slots[req.slot] = req
            self.admitted_total += 1
            from ..telemetry.metrics import maybe_inc
            maybe_inc(self.metrics, "batcher_admitted_total")
            admitted.append(req)
        return admitted

    def retire(self, req: Request, now: float) -> None:
        """DONE: release the slot and every page (eviction between
        decode bursts — the device never sees it, only the next burst's
        rewritten host arrays do).  Double-retire (or retiring a
        request this batcher never admitted) is a real failover-churn
        hazard — rejected loudly, never a silent double-free."""
        if req.slot is None or self.slots[req.slot] is not req:
            raise ValueError(
                f"retire(rid={req.rid}): request is not resident in "
                f"this batcher (slot={req.slot}, state={req.state}) — "
                f"double retire or foreign request")
        self.slots[req.slot] = None
        self._release_pages(req)
        req.slot = None
        req.state = DONE
        req.t_done = now
        self.completed_total += 1
        from ..telemetry.metrics import maybe_inc
        maybe_inc(self.metrics, "batcher_completed_total")

    def _release_pages(self, req: Request) -> None:
        """Cached pages go back to the trie (deref, stay resident for
        the next prefix twin); only request-OWNED pages return to the
        allocator."""
        if req.cache_nodes:
            self.prefix_cache.release(req.cache_nodes)
        owned = req.pages[len(req.cache_nodes):]
        if owned:
            self.allocator.free(owned)
        if req.pages_window:
            self.window_allocator.free(req.pages_window)
        req.pages = req.pages_window = None
        req.cache_nodes = []

    def release_all(self) -> list[Request]:
        """Failover teardown: free every resident request's slot and
        pages and drain the waiting queue, returning all unfinished
        requests (resident first, in slot order, then waiting FCFS) so
        the fleet can replay them on a survivor.  Counters are NOT
        rewound — the survivor's ``admitted_total`` will count the
        re-admission, and the fleet aggregates by rid."""
        orphans: list[Request] = []
        for b, req in enumerate(self.slots):
            if req is None:
                continue
            self.slots[b] = None
            self._release_pages(req)
            reset_for_replay(req)
            orphans.append(req)
        while self.waiting:
            req = self.waiting.popleft()
            reset_for_replay(req)
            orphans.append(req)
        return orphans


def reset_for_replay(req: Request) -> None:
    """Rewind a request to its just-submitted state so a survivor
    replica can replay it from scratch.  Greedy decode is deterministic
    in (params, prompt), so a full replay reproduces the exact token
    stream an undisturbed run would have emitted — partial progress is
    deliberately discarded rather than migrated (KV pages died with the
    replica).  Identity (rid, prompt, max_new_tokens, arrival_s,
    t_submit, trace_id) is preserved; runtime state is cleared."""
    req.state = WAITING
    req.slot = None
    req.pages = req.pages_window = None
    req.cache_nodes = []
    req.prefill_pos = 0
    req.tokens = []
    req.t_admit = None
    req.t_first = None
    req.t_done = None
