"""Host-side continuous-batching scheduler — admission by *actual*
occupancy.

The state machine the engine drives once per step::

    WAITING --admit (slot + first-chunk blocks)--> RUNNING
        RUNNING (prefilling: cache_len < prefill_target)
        RUNNING (decoding) --eos / budget / max_seq--> FINISHED
    RUNNING --pool pressure--> WAITING   (preempted: blocks freed,
                                          recompute-on-readmit)
    WAITING --drain--> CANCELLED
    submit() while draining --> REJECTED   (refused at the door)

PR 8 admitted by **worst-case reservation** — a request held
``blocks_for(prompt + max_new_tokens)`` from admission to finish, so
the pool ran far below real occupancy (most requests never reach their
horizon, and the reserved tail blocks sat idle).  This scheduler closes
that gap the way production engines do:

- **Admission** needs a free decode slot and blocks for the request's
  *first prefill chunk only* — after the prefix cache
  (:class:`~apex_tpu.serving.kv_cache.PrefixCache`) has been consulted:
  shared prompt-prefix blocks are refcount-incremented, not
  re-allocated or re-computed.  Fixed-size blocks keep this a pure
  counter check (fragmentation cannot strand capacity).
- **Growth is on demand**: a request crossing into a new block during
  prefill or decode allocates it then.  When the free list is empty the
  scheduler first **evicts** least-recently-used prefix-cache blocks
  (finished requests' cached KV — capacity held only as an
  optimization), and only then **preempts**: the *newest-admitted*
  victim frees every block (its cached full blocks are first indexed
  into the prefix cache, so its work is not lost) and returns to the
  front of the queue.  On readmission it *recomputes* — its prompt plus
  every token it already emitted replays through the ordinary chunked
  prefill path (the PR 10 fleet-replay mechanics, one process inward) —
  and typically hits its own just-cached blocks, so the recompute
  prefills only what eviction actually took.
- Victims are always strictly newer than the request growing, so the
  oldest running request can never be preempted: it finishes, frees
  its blocks, and everything behind it readmits — every admitted
  request terminates even at heavy pool oversubscription (pinned at 2x
  in ``tests/test_serving.py``).
- The submit-time guard keeps one hard reservation rule: a request
  whose worst case exceeds the WHOLE pool is rejected at the door (it
  could otherwise preempt the fleet forever and still never finish).
- ``admission="reserve"`` keeps the PR 8 worst-case policy as the A/B
  baseline (never A/B'd on the chip: ROADMAP W4, D4): no sharing, no
  growth, no preemption — admission is the whole horizon or nothing.

**Cache groups** (ISSUE 27): with more than one
:class:`~apex_tpu.serving.kv_cache.CacheGroup` a request holds one block
list per group (``Request.blocks`` for group 0, ``Request.more_blocks`` for
the rest), all indexed by the same logical block.  Admission, growth and
preemption count every group: a request is admitted or grown only when each
group's pool can serve it, and a victim gives back its blocks of every
group.  A window group also gives back, at each plan, the blocks that lie
wholly behind what the request's next query can read
(:meth:`Scheduler.free_behind_window`; their entries read
:data:`~apex_tpu.serving.kv_cache.FREED`), so it holds ``window +
chunk`` tokens a slot however long the history.  Prefix sharing is refused
at construction for such a cache: a shared prefix would have to outlive the
window that hands its blocks back.  :meth:`Scheduler.check` holds the
invariants of every group.

**Slots** are indices into the engine's fixed ``[max_batch]`` decode
arrays; a request keeps one slot from admission to finish or
preemption.  Churn rewrites the slot's row of the block-table/length
arrays — data, never shape, which is what the zero-recompile contract
rests on.

**Draining** (preemption of the whole engine): no further admissions;
RUNNING requests decode to completion and deliver their responses;
WAITING requests — including preempted ones, whose partial streams were
already delivered — are cancelled immediately (the submitter sees a
terminal state, not a hang).  A submit that arrives *during* the drain
is REJECTED, not cancelled: the two terminal states answer different
routing questions (see ``RequestState``), and the engine counts them
separately (``serving/requests_cancelled`` vs
``serving/requests_rejected``).
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import itertools
import time
from typing import Deque, List, Optional, Sequence

import numpy as np

from apex_tpu.serving.kv_cache import (
    FREED,
    BlockAllocator,
    KVCacheConfig,
    PrefixCache,
)
from apex_tpu.serving.sampling import SamplingParams

__all__ = ["Request", "RequestState", "Scheduler", "trace_fields"]


def trace_fields(req) -> dict:
    """Trace-context kwargs for a request's timeline events (ISSUE 15):
    ``{trace_id, attempt}`` when the request rides a fleet trace, empty
    otherwise — an untraced spill carries no null clutter and is byte-
    compatible with the pre-tracing schema."""
    if req.trace_id is None:
        return {}
    return {"trace_id": req.trace_id, "attempt": req.trace_attempt}


class RequestState(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"
    CANCELLED = "cancelled"
    # refused at the door (submitted into a drain window, or shed by the
    # fleet router on overload) — distinguishable from CANCELLED, which
    # means "accepted, then drained out of the queue": a router that
    # sees REJECTED re-routes the request to another replica, while a
    # CANCELLED request was an accepted casualty of this engine's drain
    REJECTED = "rejected"


@dataclasses.dataclass
class Request:
    """One generation request and its live serving state."""

    rid: int
    prompt: np.ndarray                  # int32 [prompt_len]
    max_new_tokens: int
    eos_id: Optional[int] = None
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)

    state: RequestState = RequestState.WAITING
    output_tokens: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)
    # the block lists of cache groups 1.. (same logical index as ``blocks``;
    # FREED where a window group handed a block back)
    more_blocks: List[List[int]] = dataclasses.field(default_factory=list)
    # per cache group, how many leading entries of its list are FREED (what
    # a window hands back is always a prefix)
    freed_prefix: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    cache_len: int = 0                  # tokens currently in the paged cache
    prefill_target: int = 0             # tokens the prefill must cover
    hit_blocks: int = 0                 # prefix-cache blocks shared (last admit)
    pc_blocks: int = 0                  # full blocks chain-hashed so far
    pc_hash: int = 0                    # chain hash after block pc_blocks-1
    preemptions: int = 0                # times evicted back to the queue
    admit_seq: int = -1                 # admission order (victim selection)
    spec_fails: int = 0                 # consecutive all-rejected proposals
    #                                     (speculative back-off; ISSUE 13)
    spec_quiet: int = 0                 # backed-off ticks since the last
    #                                     probe (re-arm cadence)
    # distributed-tracing context (ISSUE 15): the fleet-wide id this
    # request's timeline events carry, and which dispatch attempt this
    # engine-local incarnation is — None/0 outside a traced fleet (the
    # engine's events then stay rid-keyed and process-local, exactly
    # the pre-tracing shape)
    trace_id: Optional[str] = None
    trace_attempt: int = 0

    # wall-clock marks for the latency metrics (engine-stamped)
    t_submit: float = 0.0
    t_admit: Optional[float] = None     # the latest admission
    t_first_token: Optional[float] = None
    t_last_token: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.REJECTED)

    @property
    def prefilling(self) -> bool:
        """RUNNING but with prompt tokens still to land in the cache."""
        return (self.state is RequestState.RUNNING
                and self.cache_len < self.prefill_target)

    @property
    def last_token(self) -> int:
        if self.output_tokens:
            return self.output_tokens[-1]
        return int(self.prompt[-1])

    def sequence_tokens(self) -> List[int]:
        """Every token this request has: prompt + emitted stream (the
        readmission wire, and the content key of its cache blocks)."""
        return list(map(int, self.prompt)) + self.output_tokens

    def group_blocks(self) -> List[List[int]]:
        """The block list of every cache group, group 0 first."""
        return [self.blocks] + self.more_blocks


class Scheduler:
    """Slot + block bookkeeping for the continuous batch."""

    def __init__(self, cache: KVCacheConfig, max_batch: int, *,
                 chunk_tokens: Optional[int] = None,
                 admission: str = "occupancy",
                 prefix_caching: bool = True):
        if admission not in ("occupancy", "reserve"):
            raise ValueError(
                f"admission must be 'occupancy' or 'reserve', got "
                f"{admission!r}")
        self.cache = cache
        self.groups = cache.cache_groups
        if len(self.groups) > 1 or self.groups[0].window is not None:
            if prefix_caching:
                raise ValueError(
                    "prefix caching is not available with cache groups: a "
                    "window group hands back the blocks a shared prefix "
                    "would need; pass prefix_caching=False")
            if admission != "occupancy":
                raise ValueError(
                    "cache groups are admitted by occupancy only")
        self.max_batch = max_batch
        self.admission = admission
        self.chunk_tokens = chunk_tokens or cache.max_seq
        self.allocators = [BlockAllocator(g.n_blocks) for g in self.groups]
        self.allocator = self.allocators[0]
        self.window_blocks_freed = 0    # lifetime count (engine flushes)
        # reserve mode cannot share (a reservation is exclusive by
        # definition), so the cache only exists under occupancy admission
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.allocator, cache.block_size)
            if prefix_caching and admission == "occupancy" else None)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.waiting: Deque[Request] = collections.deque()
        self._ids = itertools.count()
        self._admit_seq = itertools.count()
        self.draining = False
        self.preemptions = 0            # lifetime count (engine flushes)

    # ------------------------------------------------------------- submit

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None) -> Request:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token list")
        if prompt.size >= self.cache.max_seq:
            raise ValueError(
                f"prompt of {prompt.size} tokens does not fit max_seq="
                f"{self.cache.max_seq} with room to generate")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        req = Request(rid=next(self._ids), prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      sampling=sampling or SamplingParams(),
                      t_submit=time.monotonic())
        need = self._worst_case_blocks(req)
        if need > self.allocator.n_blocks or not self._windows_fit():
            # the one reservation rule occupancy admission keeps: a
            # request the WHOLE pool cannot cover would either starve
            # the FIFO head forever (reserve mode) or preempt every
            # neighbour and still never finish (occupancy mode) —
            # reject it at the door instead
            raise ValueError(
                f"request needs {need} blocks worst-case "
                f"(prompt {prompt.size} + max_new_tokens "
                f"{max_new_tokens}) but the arena has only "
                f"{self.allocator.n_blocks}; raise n_blocks or lower "
                "max_new_tokens")
        if self.draining:
            # a submit that lands in the drain window is refused with a
            # typed terminal state, NOT accepted-then-cancelled: the
            # caller (a fleet router, a retrying client) must be able to
            # tell "this engine would never have run it" from "it was
            # queued and the drain killed it"
            req.state = RequestState.REJECTED
            return req
        self.waiting.append(req)
        return req

    # -------------------------------------------------------------- admit

    def _worst_case_blocks(self, req: Request) -> int:
        horizon = min(len(req.prompt) + req.max_new_tokens,
                      self.cache.max_seq)
        return self.cache.blocks_for(horizon)

    def _windows_fit(self) -> bool:
        """Whether each further group's pool covers one request's worst
        case: its whole horizon, or for a window group the window and one
        chunk wherever they lie against the block edges."""
        return all(
            g.blocks_spanned(self.chunk_tokens, self.cache.block_size,
                             self.cache.max_blocks_per_request)
            <= alloc.n_blocks
            for g, alloc in zip(self.groups[1:], self.allocators[1:]))

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _ensure_free(self, n: int) -> bool:
        """Raise ``n_free`` to ``n`` by evicting prefix-cache LRU blocks
        (capacity held only as an optimization — the whole deficit is
        swept in one pass); False when the cache runs out first.  With
        cache groups every group's pool must have ``n`` free."""
        deficit = n - self.allocator.n_free
        if deficit > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict_many(deficit)
        return all(a.n_free >= n for a in self.allocators)

    def admit(self) -> List[Request]:
        """Move WAITING requests into free slots while capacity lasts
        (FIFO — no request starves behind a later, smaller one).
        Returns the newly-admitted requests; the engine prefills them.

        Occupancy admission: consult the prefix cache (shared blocks
        are refcounted, their tokens never recomputed), then require
        blocks for the first prefill chunk only — evicting cached
        blocks to make room, but never preempting (running requests
        outrank arrivals).  Reserve admission (the PR 8 baseline):
        the whole worst-case horizon or nothing."""
        admitted: List[Request] = []
        if self.draining:
            return admitted
        free = self.free_slots()
        while self.waiting and free:
            req = self.waiting[0]
            wire = req.sequence_tokens()
            if self.admission == "reserve":
                need = self._worst_case_blocks(req)
                if not self.allocator.can_alloc(need):
                    break
                shared: List[int] = []
            else:
                shared = []
                if self.prefix_cache is not None:
                    # cap: always leave >= 1 token to recompute — the
                    # recompute emits the request's next sampled token,
                    # and it keeps every write on private blocks
                    shared = self.prefix_cache.lookup(
                        wire, req.rid,
                        max_blocks=(len(wire) - 1)
                        // self.cache.block_size)
                hit_len = len(shared) * self.cache.block_size
                chunk = min(len(wire) - hit_len, self.chunk_tokens)
                need = self.cache.blocks_for(hit_len + chunk) - len(shared)
                if not self._ensure_free(need):
                    # not even the first chunk fits: the FIFO head
                    # blocks (hand the shared refs back — the entries
                    # stay cached for the retry — and roll the hit
                    # count back: nothing was *served*, and a head
                    # stuck behind a full pool for N ticks must not
                    # inflate serving/prefix_cache_hits N times)
                    if shared:
                        self.allocator.free(shared, owner=req.rid)
                        self.prefix_cache.hits -= len(shared)
                    break
            self.waiting.popleft()
            req.blocks = shared + self.allocator.alloc(need, owner=req.rid)
            req.more_blocks = [a.alloc(need, owner=req.rid)
                               for a in self.allocators[1:]]
            req.freed_prefix = [0] * len(self.groups)
            req.hit_blocks = len(shared)
            req.pc_blocks = 0
            req.pc_hash = 0
            req.cache_len = len(shared) * self.cache.block_size
            req.prefill_target = len(wire)
            req.slot = free.pop(0)
            req.state = RequestState.RUNNING
            req.admit_seq = next(self._admit_seq)
            self.slots[req.slot] = req
            admitted.append(req)
        return admitted

    def admit_imported(self, prompt: Sequence[int], max_new_tokens: int,
                       eos_id: Optional[int] = None,
                       sampling: Optional[SamplingParams] = None, *,
                       cache_len: int, n_blocks: int) -> Request:
        """Admit a request whose KV for ``prompt[:cache_len]`` is about
        to be *imported* (KV-block migration, ISSUE 16) instead of
        computed here.

        Allocates blocks covering the whole prefill target (the
        imported run plus the remaining-tail blocks, so the chunked
        prefill of the uncovered tokens never scatters out of range),
        binds a slot immediately — the migrated payload is already
        committed to this host, parking it behind the FIFO would strand
        device memory — and returns the RUNNING request with
        ``cache_len`` pre-seeded.  The engine scatters the payload into
        ``req.blocks[:n_blocks]`` and the ordinary chunked-prefill path
        covers ``prompt[cache_len:]`` (for a migration that is exactly
        the last wire token — the same recompute-one-token shape as a
        prefix-cache hit), which is what makes the continued stream
        bitwise the failover-replay stream.  Raises ``ValueError`` /
        :class:`~apex_tpu.serving.kv_cache.OutOfBlocksError` when slot
        or pool capacity is missing (the caller degrades to
        re-prefill); a drain window returns a REJECTED request, exactly
        like :meth:`submit`."""
        from apex_tpu.serving.kv_cache import OutOfBlocksError

        if len(self.groups) > 1:
            raise NotImplementedError(
                "KV migration moves one cache group's blocks only")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token list")
        if prompt.size >= self.cache.max_seq:
            raise ValueError(
                f"imported prompt of {prompt.size} tokens does not fit "
                f"max_seq={self.cache.max_seq} with room to generate")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not 0 < cache_len < prompt.size:
            raise ValueError(
                f"imported cache_len {cache_len} must cover part of the "
                f"{prompt.size}-token prompt (>= 1 token recomputed)")
        if n_blocks != self.cache.blocks_for(cache_len):
            raise ValueError(
                f"imported run of {n_blocks} blocks does not cover "
                f"cache_len {cache_len} (block_size "
                f"{self.cache.block_size})")
        req = Request(rid=next(self._ids), prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      sampling=sampling or SamplingParams(),
                      t_submit=time.monotonic())
        if self._worst_case_blocks(req) > self.allocator.n_blocks:
            raise ValueError(
                "imported request exceeds the whole pool worst-case")
        if self.draining:
            req.state = RequestState.REJECTED
            return req
        free = self.free_slots()
        if not free:
            raise ValueError("no free decode slot for the imported "
                             "request")
        if self.admission == "reserve":
            need = self._worst_case_blocks(req)
        else:
            need = self.cache.blocks_for(prompt.size)
        if not self._ensure_free(need):
            raise OutOfBlocksError(
                f"imported request needs {need} blocks, only "
                f"{self.allocator.n_free} free after eviction")
        req.blocks = self.allocator.alloc(need, owner=req.rid)
        req.hit_blocks = 0
        req.pc_blocks = 0
        req.pc_hash = 0
        req.cache_len = int(cache_len)
        req.prefill_target = prompt.size
        req.slot = free[0]
        req.state = RequestState.RUNNING
        req.admit_seq = next(self._admit_seq)
        self.slots[req.slot] = req
        # NB the imported run is NOT indexed into the prefix cache here:
        # its content has not landed in the arena yet.  The engine calls
        # :meth:`note_imported` after the batched scatter.
        return req

    def note_imported(self, req: Request) -> None:
        """Index an imported request's landed run into the prefix cache
        (called by the engine after the batched scatter — indexing
        before the device put lands would let a same-tick hit share
        garbage blocks)."""
        self._index_into_cache(req)

    # ------------------------------------------------------------- growth

    def try_grow_to(self, req: Request, n_tokens: int, *,
                    preempt: bool = True) -> int:
        """Grow ``req.blocks`` toward covering ``n_tokens`` of cache,
        taking blocks on demand: free list first, then prefix-cache
        eviction, then (``preempt=True``) preemption of strictly
        *newer* requests.  Returns the token count the request's blocks
        now cover — a newer request with nothing left to preempt simply
        waits its turn (the engine skips its chunk/decode this tick),
        while the oldest running request always reaches its target
        (everything else is evictable or preemptable), which is what
        makes every admitted request terminate under oversubscription.

        ``preempt=False`` stops the ladder at eviction — the engine's
        *speculative* growth (blocks for drafted tokens, ISSUE 13) uses
        this: drafting is an optimization and must never pay for itself
        by throwing away a neighbour's computed KV."""
        target = self.cache.blocks_for(n_tokens)
        while len(req.blocks) < target:
            want = target - len(req.blocks)
            if self._ensure_free(1):
                # every group's list grows in step: as many blocks as each
                # pool has free
                take = min([want] + [a.n_free for a in self.allocators])
                req.blocks.extend(self.allocator.alloc(take, owner=req.rid))
                for held, alloc in zip(req.more_blocks,
                                       self.allocators[1:]):
                    held.extend(alloc.alloc(take, owner=req.rid))
                continue
            if not preempt:
                break
            victim = self._pick_victim(exclude=req)
            if victim is None:
                break
            self.preempt(victim)
        return len(req.blocks) * self.cache.block_size

    def free_behind_window(self, req: Request, query_pos: int) -> int:
        """Hand back the blocks of ``req``'s window groups that lie wholly
        behind what a query at ``query_pos`` can read (and so behind what
        any later query can).  Returns how many went back to their pools."""
        freed = 0
        for gi, (g, alloc, held) in enumerate(zip(
                self.groups, self.allocators, req.group_blocks())):
            if g.window is None:
                continue
            done = req.freed_prefix[gi]
            upto = min(g.first_needed_block(query_pos,
                                            self.cache.block_size),
                       len(held))
            if upto > done:
                alloc.free(held[done:upto], owner=req.rid)
                held[done:upto] = [FREED] * (upto - done)
                req.freed_prefix[gi] = upto
                freed += upto - done
        self.window_blocks_freed += freed
        return freed

    def _release_all(self, req: Request) -> None:
        """Give back every block ``req`` holds, in every group."""
        for alloc, held, done in zip(self.allocators, req.group_blocks(),
                                     req.freed_prefix or [0]):
            alloc.free(held[done:], owner=req.rid)
        req.blocks = []
        req.more_blocks = []
        req.freed_prefix = []

    def _pick_victim(self, exclude: Request) -> Optional[Request]:
        """Newest-admitted running request other than ``exclude`` —
        preempting strictly newer work is what guarantees the oldest
        request always completes (no preemption livelock)."""
        candidates = [r for r in self.slots
                      if r is not None and r is not exclude
                      and r.admit_seq > exclude.admit_seq]
        if not candidates:
            return None
        return max(candidates, key=lambda r: r.admit_seq)

    def preempt(self, req: Request) -> None:
        """Evict a RUNNING request back to the queue: its full cache
        blocks are first indexed into the prefix cache (the work
        already done is kept as *evictable* capacity, and the
        readmission usually hits it), every block ref is released, and
        the request returns to the FRONT of the queue to recompute —
        prompt + emitted tokens replay through the ordinary chunked
        prefill path on readmission."""
        if req.state is not RequestState.RUNNING:
            raise ValueError(f"preempt() on {req.state} request {req.rid}")
        from apex_tpu.observability import timeline

        self._index_into_cache(req)
        self._release_all(req)
        self.slots[req.slot] = None
        req.slot = None
        req.cache_len = 0
        req.prefill_target = 0
        req.state = RequestState.WAITING
        req.preemptions += 1
        self.preemptions += 1
        self.waiting.appendleft(req)
        timeline.emit("request_preempt", rid=req.rid,
                      tokens=len(req.output_tokens),
                      **trace_fields(req))

    def _index_into_cache(self, req: Request) -> None:
        if self.prefix_cache is None:
            return
        # content actually in the arena: the first cache_len tokens of
        # the stream (the last sampled token is emitted before it is
        # written, so it is NOT cache content yet).  The chain-hash
        # cursor rides the request, so each full block is hashed ONCE
        # per admission however many chunks the prompt takes.
        n_full = min(req.cache_len // self.cache.block_size,
                     len(req.blocks))
        if n_full <= req.pc_blocks:
            return
        req.pc_hash = self.prefix_cache.insert(
            req.sequence_tokens()[:req.cache_len], req.blocks,
            req.cache_len, start_block=req.pc_blocks,
            prev_hash=req.pc_hash)
        req.pc_blocks = n_full

    def note_prefilled(self, req: Request, n_tokens: int) -> None:
        """Account a prefill chunk landing in the arena; newly complete
        full blocks become shareable prefix-cache entries (a same-tick
        arrival with the same template already hits them)."""
        req.cache_len += n_tokens
        self._index_into_cache(req)

    # ------------------------------------------------------------- finish

    def finish(self, req: Request) -> None:
        """Release a RUNNING request's slot and blocks; its full blocks
        stay behind as prefix-cache entries (evictable capacity — a
        follow-up request extending this stream prefills almost
        nothing)."""
        if req.state is not RequestState.RUNNING:
            raise ValueError(f"finish() on {req.state} request {req.rid}")
        self._index_into_cache(req)
        self._release_all(req)
        self.slots[req.slot] = None
        req.slot = None
        req.state = RequestState.FINISHED
        req.t_last_token = time.monotonic()

    def running(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def drain(self) -> List[Request]:
        """Stop admissions and cancel the queue (including preempted
        requests — their partial streams were already delivered);
        running requests keep their slots (the engine decodes them to
        completion).  Returns the cancelled requests."""
        self.draining = True
        cancelled = list(self.waiting)
        self.waiting.clear()
        for req in cancelled:
            req.state = RequestState.CANCELLED
        return cancelled

    @property
    def idle(self) -> bool:
        return not self.waiting and all(r is None for r in self.slots)

    def kv_occupancy(self) -> float:
        """Fraction of the pool holding live or cached KV (the number
        worst-case reservation kept artificially low); with cache groups,
        of the fullest group's pool."""
        return max(1.0 - a.n_free / a.n_blocks for a in self.allocators)

    def window_blocks_held(self) -> int:
        """Blocks the running requests hold in window groups."""
        return sum(len(held) - done
                   for req in self.running()
                   for g, held, done in zip(self.groups, req.group_blocks(),
                                            req.freed_prefix)
                   if g.window is not None)

    def check(self) -> None:
        """The invariants of every cache group: each pool's free and held
        blocks partition it, every block a running request lists is held
        by it and by it alone among the requests' lists, the lists of a
        request are equally long, and a window group still holds every
        block the request's next query can read."""
        for alloc in self.allocators:
            alloc.check()
        bs = self.cache.block_size
        for gi, (g, alloc) in enumerate(zip(self.groups, self.allocators)):
            seen = {}
            for req in self.running():
                held = req.group_blocks()[gi]
                if len(held) != len(req.blocks):
                    raise AssertionError(
                        f"request {req.rid}: group {gi} lists {len(held)} "
                        f"blocks, group 0 {len(req.blocks)}")
                done = req.freed_prefix[gi] if req.freed_prefix else 0
                if any((b == FREED) != (i < done)
                       for i, b in enumerate(held)):
                    raise AssertionError(
                        f"request {req.rid}: group {gi}'s freed entries are "
                        f"not its first {done}")
                first = g.first_needed_block(req.cache_len, bs)
                need = range(first, self.cache.blocks_for(req.cache_len))
                lost = [i for i in need if held[i] == FREED]
                if lost:
                    raise AssertionError(
                        f"request {req.rid}: group {gi} gave back blocks "
                        f"{lost} that position {req.cache_len} still reads")
                for b in held:
                    if b == FREED:
                        continue
                    if req.rid not in alloc._holders.get(b, ()):
                        raise AssertionError(
                            f"request {req.rid} lists block {b} of group "
                            f"{gi} and does not hold it")
                    if self.prefix_cache is None and seen.setdefault(
                            b, req.rid) != req.rid:
                        raise AssertionError(
                            f"block {b} of group {gi} is listed by "
                            f"requests {seen[b]} and {req.rid}")
