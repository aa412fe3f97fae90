"""Request model + FIFO admission scheduler for the serving engine.

Admission is strictly first-come-first-served: a request is admitted only
when it is at the head of the queue, its arrival time has passed, and a
cache slot is free. Head-of-line order is the property the scheduler tests
pin down — later requests never jump an earlier one, even when the earlier
one needs a slot (or, paged, enough free pages) and they would fit
elsewhere. Page-aware admission peeks the head (``peek_ready``), sizes its
page demand against the pool, and only then pops — so a head blocked on
pages blocks the line exactly like a head blocked on a slot.

``PrefixIndex`` is the shared-prefix half of the paged cache: a radix-style
index (flattened trie — one entry per page-aligned token prefix) from
prompt prefixes to resident, refcounted pages. Prefill publishes each fully
covered prompt page; admission walks the index page by page and maps every
hit into the new slot's page table instead of recomputing it. Entries are
evicted LRU when admission runs short of fresh pages.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional, Sequence

from .errors import QueueFull


@dataclasses.dataclass
class Request:
    """One generation request.

    prompt: token ids (any int sequence / 1-D array), length >= 1.
    max_new_tokens: number of tokens to generate (>= 1); the first one comes
        from the final prefill logits, the rest from decode steps.
    arrival: engine-clock timestamp (steps) before which the request is
        invisible to admission.
    deadline: engine-clock timestamp at/after which the request is expired —
        shed from the queue, or cut short in flight at the next step
        boundary (partial tokens are returned with status "expired").
        None (default) = no deadline.
    priority: preemption class (higher = more important; default 0). FIFO
        admission order is NOT priority-aware — priority only selects
        preemption victims: when the pool can't cover the FIFO head, a
        strictly-lower-priority in-flight request may be preempted (pages
        released, request parked host-side) to make room.

    The engine fills in two host-clock stamps (``time.perf_counter()``
    seconds, the clock a client stamps its tokens with): ``t_submit`` when
    ``submit`` queues the request, ``t_admit`` when admission first gives
    it a slot (a preempted request resumes under its first stamp). Their
    difference is the request's queue wait. Neither is an argument, and
    ``dataclasses.replace`` starts a copy without them.
    """

    rid: int
    prompt: Sequence[int]
    max_new_tokens: int
    arrival: float = 0.0
    deadline: Optional[float] = None
    priority: int = 0
    t_submit: Optional[float] = dataclasses.field(
        default=None, init=False, compare=False, repr=False)
    t_admit: Optional[float] = dataclasses.field(
        default=None, init=False, compare=False, repr=False)

    def __post_init__(self):
        if len(self.prompt) < 1:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.rid}: max_new_tokens must be >= 1")
        if self.deadline is not None and self.deadline <= self.arrival:
            raise ValueError(
                f"request {self.rid}: deadline {self.deadline} is not after "
                f"arrival {self.arrival}"
            )


class FIFOScheduler:
    def __init__(self, max_queue: Optional[int] = None):
        """``max_queue`` bounds the admission queue: ``submit`` beyond it
        raises the retryable ``QueueFull`` (back-pressure) instead of
        growing host memory without limit. None (default) = unbounded."""
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        self._queue: collections.deque[Request] = collections.deque()
        # admission diagnostics (FIFO-order test anchor) — bounded so a
        # long-lived engine doesn't grow memory with every request served
        self.admitted_order: collections.deque[int] = collections.deque(
            maxlen=4096
        )

    def submit(self, request: Request) -> None:
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            raise QueueFull(
                f"request {request.rid}: queue is at max_queue="
                f"{self.max_queue} — retry after the engine drains"
            )
        self._queue.append(request)

    def pending(self) -> int:
        return len(self._queue)

    def peek_arrival(self) -> Optional[float]:
        """Arrival time of the queue head (None when empty)."""
        return self._queue[0].arrival if self._queue else None

    def peek_ready(self, now: float) -> Optional[Request]:
        """The head request iff it has arrived, WITHOUT admitting it — the
        paged engine peeks first to size the head's page demand against the
        pool, then commits with ``pop_ready``. FIFO means nothing behind a
        not-yet-arrived (or not-yet-fitting) head is considered."""
        if self._queue and self._queue[0].arrival <= now:
            return self._queue[0]
        return None

    def pop_ready(self, now: float) -> Optional[Request]:
        """Admit the head request iff it has arrived; FIFO means nothing
        behind a not-yet-arrived head is considered."""
        if self._queue and self._queue[0].arrival <= now:
            req = self._queue.popleft()
            self.admitted_order.append(req.rid)
            return req
        return None

    def drop_head(self) -> Optional[Request]:
        """Remove the head WITHOUT recording an admission — the engine sheds
        an expired or cancelled head here (it never ran)."""
        return self._queue.popleft() if self._queue else None

    def remove(self, rid: int) -> Optional[Request]:
        """Remove a queued request by id (client cancellation before
        admission). O(queue) scan — runs at cancel time, not per step."""
        for req in self._queue:
            if req.rid == rid:
                self._queue.remove(req)
                return req
        return None


class PrefixIndex:
    """Radix-style prompt-prefix → page index for copy-on-write prefix reuse.

    A flattened trie: the key for depth ``i`` is the FULL token prefix
    through page boundary ``i+1`` (``tuple(prompt[: (i + 1) * page_size])``),
    so a page's KV content is a pure function of its key (K/V at position j
    depend on every token <= j — keying by the whole prefix, not the page's
    own tokens, is what makes cross-request reuse sound). ``publish`` pins
    each indexed page with a pool refcount, so index entries stay resident
    until evicted; ``lookup`` walks hits page by page and stops at the first
    miss. Eviction is LRU over lookups/publishes, skipping pages the current
    admission is about to share.
    """

    def __init__(self, page_size: int):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = int(page_size)
        self._map: collections.OrderedDict[tuple, int] = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._map)

    def pages(self) -> list:
        """Page ids currently pinned by the index (one per entry; a page
        indexed under several keys appears once per key) — the external-pin
        census ``ServingEngine.check_invariants`` audits refcounts against."""
        return list(self._map.values())

    def lookup(self, prompt: Sequence[int]) -> list:
        """Resident pages covering the longest indexed page-aligned prefix
        of ``prompt`` (possibly empty). Touches every hit for LRU."""
        pg = self.page_size
        toks = tuple(int(t) for t in prompt)
        pages: list = []
        while (len(pages) + 1) * pg <= len(toks):
            key = toks[: (len(pages) + 1) * pg]
            page = self._map.get(key)
            if page is None:
                break
            self._map.move_to_end(key)
            pages.append(page)
        if pages:
            self.hits += 1
        else:
            self.misses += 1
        return pages

    def publish(self, prompt: Sequence[int], pool, slot: int) -> int:
        """Index every prompt page of ``slot`` that the prompt covers
        completely (partial last pages are not shareable — their tail will
        be/was written by this request). Called at prefill completion, so
        concurrent requests behind the donor can already share; published
        pages are never written again by their owner (pad and decode writes
        both land at positions >= len(prompt)). Pages already indexed under
        the same key are skipped (first donor wins). Returns the number of
        newly indexed pages."""
        pg = self.page_size
        toks = tuple(int(t) for t in prompt)
        added = 0
        for i in range(len(toks) // pg):
            key = toks[: (i + 1) * pg]
            if key in self._map:
                self._map.move_to_end(key)
                continue
            page = pool.slot_page(slot, i)
            pool.ref_page(page)
            self._map[key] = page
            added += 1
        return added

    def evict_lru(self, pool, protect=()) -> bool:
        """Drop the least-recently-used entry whose page is not in
        ``protect`` (pages the in-flight admission is mapping) and release
        its pool reference. Returns False when nothing is evictable."""
        protect = set(protect)
        for key, page in self._map.items():
            if page in protect:
                continue
            del self._map[key]
            pool.deref_page(page)
            self.evictions += 1
            return True
        return False

    def clear(self, pool) -> None:
        """Drop every entry and release its page reference."""
        while self._map:
            _, page = self._map.popitem(last=False)
            pool.deref_page(page)
