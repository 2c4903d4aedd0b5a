"""Continuous-batching admission policy, extracted model-free.

The engine loop (core.py) is thin glue around this scheduler: every tick
it asks for ``admissions()`` (waiting requests matched to free slots,
with the prefill bucket and any prefix-cache reuse already decided) and
for the decode roster of active requests. Keeping the policy here —
with zero jax imports — makes admission behaviour (FIFO fairness, slot
recycling between device chunks, bucketed prefill, per-request token
accounting) unit-testable without compiling a model.

Orca-style continuous batching (Yu et al., OSDI '22): admission happens
between device chunks, finished requests free their slot immediately,
and the decode roster is rebuilt per chunk so new requests join without
head-of-line blocking on the longest generation.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import time
from concurrent.futures import Future
from typing import Any, Deque, Iterator, List, Optional

from ray_tpu.serve.engine.kv_manager import KVCacheManager


@dataclasses.dataclass
class EngineRequest:
    """One generation request plus its engine-side state.

    (serve/llm.py re-exports this as ``GenerationRequest`` for
    compatibility with the pre-subsystem engine.)
    """
    prompt_ids: List[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    future: Future = dataclasses.field(default_factory=Future)
    # Streaming consumers read tokens from here as they decode; a ("done",
    # None) / ("error", e) record terminates the stream.
    stream_queue: Optional[Any] = None
    # engine state
    slot: int = -1
    generated: List[int] = dataclasses.field(default_factory=list)
    length: int = 0        # tokens currently in the KV cache for this slot
    cached_len: int = 0    # prompt prefix served from the prefix cache
    arrival_t: float = 0.0
    first_token_t: float = 0.0
    # When the engine thread put the first token on ``stream_queue``
    # (0.0: not yet, or not streamed); generate_stream reads it on the
    # consumer's thread for the serve front's share of TTFT.
    first_put_t: float = 0.0
    # Speculative-decoding state (None when the engine runs spec-off):
    # the adaptive draft allowance + lifetime drafted/accepted counters
    # (drafter.SpecControl), attached by the engine at request creation.
    spec: Optional[Any] = None
    # Distributed tracing: the caller's wire span context, captured at
    # request creation on the CALLER's thread (the engine thread has no
    # ContextVar view of it). None when tracing is off — every engine
    # span emit gates on this, so the untraced decode path allocates no
    # span state.
    trace_ctx: Optional[Any] = None
    # Disaggregated serving: True on a PREFILL-role engine's requests —
    # after the final prefill chunk the engine exports the slot's KV
    # pages and resolves the future with a handoff payload instead of
    # joining the decode roster (core._advance_prefill).
    handoff: bool = False
    # Per-tenant QoS: tenant attribution (stats only at engine tier)
    # and the strict priority class — admission serves higher classes
    # first, and a starved higher-priority arrival may PREEMPT a
    # lower-priority active request (preempt.Preemption.park parks it; its
    # KV rows stay prefix-resident and it resumes as a continuation).
    tenant: str = ""
    priority: int = 0

    def remaining(self) -> int:
        """Token budget left (per-request accounting)."""
        return max(0, self.max_new_tokens - len(self.generated))


@dataclasses.dataclass
class Admission:
    """One admission decision: prefill ``request.prompt_ids[cached_len:]``
    into ``slot`` starting at row offset ``cached_len``, as the
    ``chunks`` plan — a list of (real_tokens, padded_bucket) pieces the
    engine dispatches one per tick (Sarathi-style chunked prefill;
    a single entry when chunking is off or the suffix fits one chunk).
    ``bucket`` remains the one-shot bucket for the whole suffix
    (back-compat surface for callers that predate chunking)."""
    request: EngineRequest
    slot: int
    cached_len: int
    bucket: int
    chunks: List[tuple] = dataclasses.field(default_factory=list)


def bucket_for(n: int, buckets: List[int]) -> int:
    """Smallest configured bucket >= n (static prefill shapes: XLA
    compiles once per bucket, not once per prompt length)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest bucket "
                     f"{buckets[-1]}")


class Scheduler:
    """FIFO admission over a slot pool with prefix-aware placement."""

    def __init__(self, kv: KVCacheManager, *, max_len: int,
                 prompt_buckets: List[int], prefill_chunk: int = 0):
        self.kv = kv
        self.max_len = max_len
        self.buckets = sorted(set(
            [b for b in prompt_buckets if b <= max_len] + [max_len]))
        # Chunked prefill (0 = off): long suffixes split into pieces of
        # this many REAL tokens, dispatched one per engine tick so a
        # long prompt stops stalling the whole roster's TPOT. Snapped
        # DOWN to the largest configured bucket <= the request (up to
        # the smallest bucket when none is) so intermediate chunks are
        # unpadded (one static prefill shape, no new programs) —
        # snapping up would let sparse buckets balloon the chunk back
        # into the one-shot stall the knob exists to bound.
        if prefill_chunk:
            le = [b for b in self.buckets if b <= prefill_chunk]
            self.prefill_chunk = le[-1] if le else self.buckets[0]
        else:
            self.prefill_chunk = 0
        self._waiting: Deque[EngineRequest] = collections.deque()
        self.active: List[EngineRequest] = []
        # Requests that let go of their slot ahead of the retire that
        # finishes them (``hand_over``): on no roster, holding no slot,
        # still owed the tokens of the decode chunk in flight.
        self.ending: List[EngineRequest] = []
        self.peak_active = 0

    # ------------------------------------------------------------- intake

    def submit(self, req: EngineRequest) -> None:
        req.arrival_t = req.arrival_t or time.perf_counter()
        self._waiting.append(req)

    def drain_into(self, q: "queue.Queue[EngineRequest]") -> None:
        """Pull every request currently in ``q`` into the waiting line
        (the engine's thread-safe mailbox -> scheduler handoff)."""
        while True:
            try:
                self.submit(q.get_nowait())
            except queue.Empty:
                return

    def queue_depth(self) -> int:
        return len(self._waiting)

    def max_waiting_priority(self) -> Optional[int]:
        """Highest priority class among waiting requests (None when the
        line is empty) — core's preemption trigger reads it."""
        return max((r.priority for r in self._waiting), default=None)

    def _pop_next(self) -> EngineRequest:
        """Next admission: strict priority classes, FIFO within a class
        (all-equal priorities — the default — is exactly FIFO)."""
        best_i, best_p = 0, self._waiting[0].priority
        for i, r in enumerate(self._waiting):
            if r.priority > best_p:
                best_i, best_p = i, r.priority
        if best_i == 0:
            return self._waiting.popleft()
        r = self._waiting[best_i]
        del self._waiting[best_i]
        return r

    # ---------------------------------------------------------- admission

    def prefill_plan(self, suffix: int) -> List[tuple]:
        """Split a ``suffix``-token prefill into (real_tokens, bucket)
        chunks. Chunking off (or suffix within one chunk): a single
        bucket-padded piece — today's behavior exactly. On: full
        ``prefill_chunk``-token pieces (bucket == length, unpadded)
        with a bucketed tail; ONLY the final chunk's logits carry the
        first generated token, so intermediate chunks are dispatched
        without a host fetch."""
        c = self.prefill_chunk
        if not c or suffix <= c:
            return [(suffix, bucket_for(suffix, self.buckets))]
        out: List[tuple] = []
        rest = suffix
        while rest > c:
            out.append((c, c))
            rest -= c
        out.append((rest, bucket_for(rest, self.buckets)))
        return out

    def _prefill_rows(self, suffix: int) -> int:
        """Cache rows a suffix prefill writes: real tokens for every
        full chunk plus the final chunk's padded bucket."""
        plan = self.prefill_plan(suffix)
        return sum(n for n, _ in plan[:-1]) + plan[-1][1]

    def admissions(self) -> Iterator[Admission]:
        """Match waiting requests to free slots, FIFO. Stops at slot
        exhaustion — later arrivals wait for a recycled slot (admitted
        between device chunks, never mid-chunk)."""
        while self._waiting and self.kv.free_slots():
            req = self._pop_next()
            plen = len(req.prompt_ids)
            # Reuse depths whose bucket-padded suffix prefill would write
            # past max_len are vetoed: the padded chunk lands at rows
            # [cached, cached + bucket), and a clamped device write would
            # silently shift the suffix KV onto the wrong rows. (Chunked
            # prefill pads only the FINAL chunk, so its row bound is
            # usually tighter than the one-shot bucket.)
            self.kv.current_request = req
            try:
                got = self.kv.acquire(
                    req.prompt_ids,
                    fit=lambda c: (c + self._prefill_rows(plen - c)
                                   <= self.max_len))
            finally:
                self.kv.current_request = None
            if got is None:  # raced to exhaustion
                self._waiting.appendleft(req)
                return
            slot, cached_len = got
            req.slot, req.cached_len = slot, cached_len
            suffix = plen - cached_len
            yield Admission(req, slot, cached_len,
                            bucket_for(suffix, self.buckets),
                            chunks=self.prefill_plan(suffix))

    def activate(self, req: EngineRequest) -> None:
        """Prefill succeeded: request joins the decode roster."""
        req.length = len(req.prompt_ids)
        self.active.append(req)
        self.peak_active = max(self.peak_active, len(self.active))

    def abort_admission(self, req: EngineRequest,
                        resident=()) -> None:
        """Prefill failed: recycle the slot. ``resident`` may carry the
        PRE-ACQUIRE reused prefix (rows a previous, confirmed
        generation wrote and this request's prefill never touched —
        writes start at cached_len) so an abort doesn't evict a still-
        valid hot prefix; rows this request dispatched are in an
        unknown state and are never seeded."""
        self.kv.release(req.slot, resident_tokens=resident)
        req.slot = -1

    # ------------------------------------------------------------- decode

    def is_finished(self, req: EngineRequest, last_tok: int) -> bool:
        return (len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and last_tok == req.eos_id)
                or req.length + 1 >= self.max_len)

    def ends_within(self, req: EngineRequest, k: int) -> bool:
        """``is_finished`` foreseen: the request's next ``k`` tokens are
        sure to end it, by its budget or its row cap — the two clauses
        above that the host knows ahead (an EOS can only end it
        sooner). A decode chunk of ``k`` steps in flight for ``req``
        leaves its slot frozen, whatever it samples (the scan applies
        the same rules, ``decode_loop.decode_chunk``)."""
        return req.remaining() <= k or req.length + k + 1 >= self.max_len

    def _vacate(self, req: EngineRequest) -> None:
        """``req``'s slot returns to the pool with its CONFIRMED rows
        resident: rows [0, length) hold KV for prompt + generated[:-1]
        as the host knows them (the last generated token never went
        back through the model, and rows a chunk still in flight is
        writing past them are never seeded)."""
        if req in self.active:
            self.active.remove(req)
        resident = list(req.prompt_ids) + list(req.generated[:-1])
        self.kv.release(req.slot, resident_tokens=resident)
        req.slot = -1

    def finish(self, req: EngineRequest) -> None:
        """Retire a request; its slot returns to the pool with its
        resident tokens recorded for prefix reuse. One that handed its
        slot over ahead (``ending``) has nothing left to return."""
        if req in self.ending:
            self.ending.remove(req)
        else:
            self._vacate(req)

    def preempt(self, req: EngineRequest) -> None:
        """Park an active request (priority preemption): the slot
        returns to the pool with exactly what finish() would seed, so
        the resume continuation's re-prefill is a prefix-cache hit (or,
        once those rows are evicted and spilled, a fleet-tier pull)."""
        self._vacate(req)

    def hand_over(self, req: EngineRequest) -> None:
        """An active request that ``ends_within`` the decode chunk in
        flight lets go of its slot NOW, a tick ahead of the retire that
        finishes it: the slot is the next waiter's to prefill behind
        that chunk, and the request waits on ``ending`` for the chunk's
        tokens (``core._retire_chunk`` delivers them by the chunk's own
        record of who it was dispatched with)."""
        self._vacate(req)
        self.ending.append(req)

    def fail_active(self) -> List[EngineRequest]:
        """Device failure: retire the whole roster (slots recycled, no
        prefix reuse) and hand the requests back for error delivery —
        those the chunk in flight still owed tokens (``ending``) among
        them."""
        failed = list(self.active)
        for req in failed:
            self.active.remove(req)
            self.kv.release(req.slot, resident_tokens=())
            req.slot = -1
        failed += self.ending
        self.ending = []
        return failed
