"""InferenceEngine: the orchestration loop tying the subsystem together.

One background thread runs the Orca-style tick: drain the mailbox into
the scheduler, admit waiting requests into free slots (prefix-aware,
bucket-padded prefill — long prompts optionally split into
``prefill_chunk``-token pieces advanced one per tick), then dispatch
ONE device-resident decode chunk for the whole roster and fetch K
tokens in a single host sync (decode_loop.py; with ``multi_step`` the
fetch lands the PREVIOUS chunk while the next one executes). Requests
finish mid-chunk on the on-device EOS/budget mask; the host discards
the frozen overshoot, recycles the slot into the prefix cache
(kv_manager.py), and streams tokens to waiting consumers.

``serve/llm.py`` keeps the public surface (``LLMEngine.generate`` /
``generate_stream`` / ``build_llm_deployment``) as a facade over this
class.

This module is the tick and nothing else: the fleet KV tier
(``self.fleet``, kv_fleet.py), the prefill/decode roles
(``self.handoff``, handoff.py), priority preemption
(``self.preemption``, preempt.py) and speculation (``self.speculation``,
drafter.py) are one object each, called at the places and through the
device surface that engine/README.md lists.
"""

from __future__ import annotations

import functools
import queue
import threading
import time
import zlib
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import numpy as np

from ray_tpu.devtools import jax_debug
from ray_tpu.devtools import res_debug as _resdbg
from ray_tpu.serve.engine.decode_loop import (DecodeLoop, check_offers,
                                              serving_params)
from ray_tpu.serve.engine.drafter import Speculation
from ray_tpu.serve.engine.handoff import KVHandoff
from ray_tpu.serve.engine.kv_manager import KVCacheManager
from ray_tpu.serve.engine.metrics import (DeviceQueue, EngineMetrics,
                                          TickClock)
from ray_tpu.serve.engine.preempt import Preemption
from ray_tpu.serve.engine.scheduler import EngineRequest, Scheduler
from ray_tpu.util import compile_cache as _compile_cache
from ray_tpu.util import flight_recorder as _flight
from ray_tpu.util import tracing as _tracing


# The listening wait (_listen) looks at the chunk in flight at least
# this often: what a wrong estimate of that chunk's end can cost.
_LISTEN_SLICE_S = 0.002


# Two waiting prompts are prefilled in ONE program (`_partner`) while
# that program holds at most this many rows: twice the larger of their
# two buckets. Up to here a tick prefill costs little more than the
# stream of the weights, whatever its rows; past it the rows' arithmetic
# shows. The bare program on a v5e over 7.0 GiB of bf16 weights (my chip
# run, PR 53; PERF.md section 5), [1, Pb] | [2, Pb] | two of [1, Pb]:
# 128: 16.7 | 16.2 | 33.3 ms; 256: 18.0 | 26.2 | 35.9; 512: 25.6 | 50.3 |
# 51.1; 1,024: 49.5. A matrix takes as long to multiply as to stream at
# 197e12 / 819e9 = 240 rows, and a program this short hides neither
# under the other: 512 rows in all is where the table bends. A pair of
# 512s would save 1 ms of 51 and keep the first of the two a whole
# program longer from its token. The chip's and the weights' dtype's,
# not a model's.
_PAIR_ROWS = 512


class _PrefillJob:
    """One admission's prefill progress: ``idx`` chunks of ``adm.chunks``
    dispatched, next chunk writing at row ``pos``; ``counters`` holds
    what the chunks dispatched so far counted, still on the device (the
    final chunk's one fetch brings them all). ``t0`` is the last
    chunk's dispatch stamp; ``token`` the first generated token, on the
    device, once the FINAL chunk is dispatched and until
    ``_land_prefill`` fetches it. Engine-thread-only."""

    __slots__ = ("adm", "pos", "idx", "t_pf0", "t0", "counters", "token",
                 "programs", "ahead")

    def __init__(self, adm, pos: int):
        self.adm = adm
        self.pos = pos
        self.idx = 0
        self.t_pf0 = 0.0
        self.t0 = 0.0
        self.counters: list = []
        self.token = None
        # The chunks dispatched so far as the device's queue holds them
        # (`DeviceQueue.put`), and how many (chunks, prefills) the
        # first of them queued behind.
        self.programs: list = []
        self.ahead = (0, 0)


def _timed_init(init):
    """The constructor under the compile account's ``engine.init``
    phase (`util/compile_cache.py`): its wall seconds less the compiles
    booked inside it, kept for ``stats()["engine_init_s"]``."""

    @functools.wraps(init)
    def wrapped(self, *args, **kwargs):
        with _compile_cache.phase("engine.init") as whole:
            init(self, *args, **kwargs)
        self._init_s = whole.own_s

    return wrapped


class InferenceEngine:
    """Slot-based continuous-batching engine with a device-resident
    decode loop and prefix caching.

    ``decode_chunk`` is K decode steps per host sync (a per-token fetch
    puts the host round-trip on every token); ``prefix_block`` the
    prefix-cache block granularity. ``spec_draft_len`` > 0 builds the
    speculative tick (``drafter.Speculation``), ``quantize="int8"``
    serves weight-only int8 weights (``models/quant.py``), ``role`` and
    ``kv_fleet_*`` the page mechanisms: engine/README.md has a section
    each, and each is served for a family that offers it.

    ``prefill_chunk`` > 0 splits long prompt suffixes into chunks of
    that many real tokens and dispatches ONE chunk per engine tick,
    interleaved with the roster's decode chunks (``_prefill_tick``;
    Sarathi-style, Agrawal et al. 2024). Only the final chunk's token is
    fetched, and greedy output is token-identical to the unchunked path.

    ``multi_step`` (default on, plain-decode path only) double-buffers
    decode dispatch: each tick enqueues chunk N+1 BEFORE fetching chunk
    N's tokens, WHATEVER the roster did in between (``_pipelined_tick``;
    the roster's decode state is merged on the device, slot by slot:
    ``_dispatch_chunk``), so chunk N's fetch and delivery and the
    admissions' first-token fetches run under chunk N+1's device time.
    Exactly one host sync per FETCHED chunk and one per admission either
    way (the witness budget is unchanged). A request that must end
    inside the chunk in flight hands its slot to the next waiter before
    that chunk is fetched (``_hand_over``). Disabled automatically while
    speculation drafts (drafts are proposed from host-visible tokens,
    which an in-flight chunk would lag by one dispatch);
    ``multi_step=False`` and the speculative engine dispatch, fetch and
    deliver a chunk in one tick, and fetch a first token where its
    prefill is dispatched.
    """

    @_timed_init
    def __init__(self, cfg=None, params=None, *, max_batch: int = 4,
                 max_len: int = 512,
                 prompt_buckets: Optional[List[int]] = None,
                 decode_chunk: int = 8,
                 prefix_block: int = 16,
                 spec_draft_len: int = 0,
                 spec_ngram_max: int = 3,
                 spec_adaptive: bool = True,
                 spec_chunk: int = 0,
                 quantize: Optional[str] = None,
                 prefill_chunk: int = 0,
                 multi_step: bool = True,
                 role: str = "colocated",
                 seed: int = 0,
                 kv_fleet_min_prefix_blocks: Any = None,
                 kv_fleet_store: Any = None,
                 name: Optional[str] = None):
        import jax

        from ray_tpu.models import llama

        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        self.role = role
        self._jax = jax
        self.cfg = cfg or llama.tiny_config(max_seq_len=max_len)
        # The model seam: the configuration's own module provides the
        # cache, the prefill and the decode step (engine/README.md).
        self.model = self.cfg.model
        # Fetched counter -> the attribute its request's span carries.
        self._span_attr_names = getattr(self.model, "SPAN_ATTRS", {})
        # Fleet KV tier gate (kv_fleet.py): None defers to the config
        # knob; -1 = off.
        gate = kv_fleet_min_prefix_blocks
        if gate is None:
            from ray_tpu.core.config import GLOBAL_CONFIG as _cfg

            gate = _cfg.serve_kv_fleet_min_prefix_blocks
        fleet_on = not (isinstance(gate, int) and gate < 0)
        self.spec_draft_len = max(0, int(spec_draft_len))
        # An optional mechanism serves a family that offers it (the seam's
        # ENGINE_OFFERS) or is refused here, by name, before a weight is made.
        check_offers(self.model, quantize=quantize is not None,
                     spec_draft_len=self.spec_draft_len > 0,
                     role=role != "colocated", kv_fleet=fleet_on)
        self.quantize = quantize
        with _compile_cache.phase("engine.weights"):
            self.params = (params if params is not None
                           else self.model.init_params(
                               self.cfg, jax.random.PRNGKey(seed)))
            # The layout the family's programs read, made ONCE from the
            # published tree; a family without one serves the tree it
            # was given.
            self.params = serving_params(self.cfg, self.params)
            if quantize is not None:
                # Weight-only int8 (models/quant.py): decode/verify
                # stream half the weight bytes per step; every engine
                # program (prefill, decode_chunk, verify_chunk) reads
                # the same quantized pytree through forward_with_cache
                # unchanged.
                from ray_tpu.models.quant import (quantize_params,
                                                  quantized_weight_bytes)

                self.params = quantize_params(self.params, dtype=quantize)
                self._weight_bytes = quantized_weight_bytes(self.params)
        self.max_batch = max_batch
        self.max_len = min(max_len, self.cfg.max_seq_len)
        self.decode_chunk = max(1, int(decode_chunk))
        self.buckets = prompt_buckets or [32, 64, 128]
        # KV-page export/install (the roles, the fleet tier) moves
        # whole pages of ``prefix_block`` rows.
        pages = role != "colocated" or fleet_on
        with _compile_cache.phase("engine.decode_loop"):
            self.loop = DecodeLoop(
                self.cfg, max_len=self.max_len, chunk=self.decode_chunk,
                spec_window=self.spec_draft_len + 1, spec_chunk=spec_chunk,
                prefill_budget=len(self.buckets),
                kv_page=prefix_block if pages else 0)
        # Verify windows span spec_draft_len+1 rows; the scratch strip
        # past max_len absorbs parked/overrun writes so they can never
        # clamp back onto resident rows (decode_loop docstring). Row
        # accounting everywhere else still uses the logical max_len.
        cache_rows = self.max_len + self.loop.scratch_rows
        if pages:
            # Pad the allocation so the tail page of a max-length
            # prompt never needs the transfer programs' defensive clamp
            # (a clamped start on ONE side of a prefill→decode pair
            # whose scratch strips differ would land rows at the wrong
            # offset). The fleet spill/pull tier moves the same pages,
            # so a fleet-enabled colocated engine pads identically.
            cache_rows = -(-cache_rows // prefix_block) * prefix_block
        # ONE cache buffer for the engine's life: every tick program
        # takes it donated and hands it back aliased (decode_loop's
        # header), so ``self.cache`` is rebound at each dispatch and a
        # donated program that raises costs the buffer (_recover_cache).
        self._cache_rows = cache_rows
        with _compile_cache.phase("engine.cache"):
            self.cache = self.model.init_kv_cache(self.cfg, max_batch,
                                                  cache_rows)
        # Of every layer together: what a token a slot holds costs in
        # rows, and (a family with SLOT_STATE_KEYS) what a slot holds
        # besides, whatever its length.
        state_keys = getattr(self.model, "SLOT_STATE_KEYS", ())
        self._kv_bytes_per_token = sum(
            a.nbytes for k, a in self.cache.items()
            if k not in state_keys) // (max_batch * cache_rows)
        self._state_bytes_per_slot = sum(
            self.cache[k].nbytes for k in state_keys) // max_batch
        self._cache_rebuilds = 0

        # Such a slot's rows cannot be resumed from without the state
        # at their end: no prefix is reused (the manager counts what it
        # would have).
        self.kv = KVCacheManager(max_batch, self.max_len,
                                 block_size=prefix_block,
                                 reuse_prefix=not state_keys)
        self.scheduler = Scheduler(self.kv, max_len=self.max_len,
                                   prompt_buckets=self.buckets,
                                   prefill_chunk=prefill_chunk)
        self.prefill_chunk = self.scheduler.prefill_chunk
        self.multi_step = bool(multi_step)
        self.metrics = EngineMetrics(
            name, getattr(self.model, "COUNTER_MAXES", ()))
        # The engine thread's phase clock (engine.tick.* counters and
        # spans); created here, used by that thread alone.
        self._tick = TickClock(self.metrics, jax.profiler.TraceAnnotation)
        self._devq = DeviceQueue(self.metrics, self._tick)

        # The four mechanisms, each one object built where its keyword
        # asks for it (preemption has none and is always built).
        self.speculation = (Speculation(self, spec_ngram_max, spec_adaptive)
                            if self.spec_draft_len else None)
        # The pipelined chunk schedule (_pipelined_tick) is the
        # drafter-free engine's; drafts need host-visible tokens.
        self._pipelined = self.multi_step and self.speculation is None
        self.preemption = Preemption(self)
        self.handoff = KVHandoff(self) if role != "colocated" else None
        self.fleet = None
        if fleet_on:
            from ray_tpu.serve.engine.kv_fleet import FleetTier

            self.fleet = FleetTier(self, gate, kv_fleet_store, seed)

        # Chunked-prefill jobs in flight (admitted requests whose
        # suffix is still materializing, one chunk per tick) and the
        # multi-step tick's in-flight decode chunk (dispatched, not yet
        # fetched). Engine-thread-only state; bounded by max_batch and
        # one chunk respectively.
        self._prefilling: List[_PrefillJob] = []
        self._buckets_met: set = set()   # `_compile_bucket`
        self._inflight: Optional[Dict[str, Any]] = None
        self._last_retire_t = 0.0  # TPOT cadence anchor (see _retire_chunk)
        # What the listening wait (_listen) derives its deadline from,
        # all measured by the tick itself: the device's queue
        # (``_devq``: when the chunk in flight began, the device
        # seconds of the last few chunks whose ends were both seen) and
        # the host seconds of the last few carried dispatches.
        self._dispatch_s: Deque[float] = deque(maxlen=8)
        self._queue: "queue.Queue[EngineRequest]" = queue.Queue()
        self._shutdown = False
        self._thread = _resdbg.track_thread(
            threading.Thread(target=self._engine_loop, daemon=True,
                             name="llm-engine"), owner=self)
        self._thread.start()

    # ------------------------------------------------------------- public

    def generate(self, prompt_ids: List[int], max_new_tokens: int = 32,
                 eos_id: Optional[int] = None,
                 timeout: float = 300.0, tenant: str = "",
                 priority: int = 0) -> Dict[str, Any]:
        """Blocking generation (replicas call this per request; batching
        happens inside the engine across concurrent callers).
        ``priority`` selects the admission class (higher first; a
        starved higher class may preempt lower-priority actives)."""
        req = self._make_request(prompt_ids, max_new_tokens, eos_id,
                                 tenant=tenant, priority=priority)
        self._queue.put(req)
        return req.future.result(timeout=timeout)

    def generate_stream(self, prompt_ids: List[int],
                        max_new_tokens: int = 32,
                        eos_id: Optional[int] = None,
                        timeout: float = 300.0, tenant: str = "",
                        priority: int = 0):
        """Token-streaming generation: yields token ids as the engine
        decodes them. Tokens within one request always arrive in decode
        order (the engine thread is the only producer per stream)."""
        req = self._make_request(prompt_ids, max_new_tokens, eos_id,
                                 stream=True, tenant=tenant,
                                 priority=priority)
        self._queue.put(req)
        first = True
        while True:
            kind, val = req.stream_queue.get(timeout=timeout)
            if first and req.first_put_t:
                # The serve front's own share of TTFT: how long the
                # first token lay on the stream queue.
                self.metrics.record_first_deliver(
                    time.perf_counter() - req.first_put_t)
            first = False
            if kind == "token":
                yield val
            elif kind == "done":
                return
            else:
                raise val

    def prefill_remote(self, prompt_ids: List[int],
                       max_new_tokens: int = 32,
                       eos_id: Optional[int] = None,
                       timeout: float = 300.0, tenant: str = "",
                       priority: int = 0) -> Dict[str, Any]:
        """Prefill-role entry (disaggregated serving): run admission +
        (chunked) prefill for ``prompt_ids`` and return a KV HANDOFF
        payload — the slot's hash-chained KV pages plus the first
        generated token — instead of decoding. The caller streams the
        payload over a DAG channel to a decode-role engine's
        ``install_remote``. A request that FINISHES at its first token
        (budget 1 / immediate EOS) returns a completed result with no
        handoff (``kv_handoff`` absent)."""
        if self.role != "prefill":
            raise RuntimeError("prefill_remote requires role='prefill'")
        req = self._make_request(prompt_ids, max_new_tokens, eos_id,
                                 handoff=True, tenant=tenant,
                                 priority=priority)
        self._queue.put(req)
        return req.future.result(timeout=timeout)

    def install_async(self, payload: Dict[str, Any]) -> EngineRequest:
        """Decode-role entry: queue one prefill handoff for
        installation. Returns the EngineRequest; its future resolves
        with the standard generation result once decode finishes."""
        if self.role != "decode":
            raise RuntimeError("install_async requires role='decode'")
        if payload.get("page") != self.kv.block_size:
            raise ValueError(
                f"KV page size mismatch: payload {payload.get('page')} "
                f"vs engine block {self.kv.block_size}")
        req = self._make_request(payload["prompt_ids"],
                                 payload["max_new_tokens"],
                                 payload.get("eos_id"),
                                 stream=bool(payload.get("stream")),
                                 tenant=str(payload.get("tenant") or ""),
                                 priority=int(payload.get("priority", 0)))
        # The handoff's first token was generated at prefill time and
        # already delivered to the caller there — record it for result
        # accounting but never push it onto the stream queue (disagg
        # stream frames start at absolute index 1).
        req.generated.append(int(payload["first_token"]))
        self.handoff.arrivals.put((req, payload))
        return req

    def install_remote(self, payload: Dict[str, Any],
                       timeout: float = 300.0) -> Dict[str, Any]:
        """Blocking install + decode of one prefill handoff."""
        return self.install_async(payload).future.result(timeout=timeout)

    def _make_request(self, prompt_ids, max_new_tokens, eos_id,
                      stream: bool = False,
                      handoff: bool = False, tenant: str = "",
                      priority: int = 0) -> EngineRequest:
        req = EngineRequest(list(prompt_ids), max_new_tokens, eos_id,
                            stream_queue=queue.Queue() if stream else None,
                            arrival_t=time.perf_counter(),
                            handoff=handoff, tenant=tenant,
                            priority=priority)
        if _tracing.enabled():
            # Captured on the CALLER's thread (replica request context /
            # driver span); the engine thread parents its queued/prefill/
            # decode-chunk spans to it. Stays None when tracing is off,
            # which gates every engine-side span emit.
            req.trace_ctx = _tracing.current()
        if not req.prompt_ids:
            raise ValueError("empty prompt")
        if not all(isinstance(t, (int, np.integer))
                   and 0 <= t < self.cfg.vocab_size
                   for t in req.prompt_ids):
            raise ValueError("prompt_ids must be ints in [0, vocab_size)")
        if len(req.prompt_ids) + max_new_tokens > self.max_len:
            raise ValueError("prompt + max_new_tokens exceeds max_len")
        if self.speculation is not None:
            req.spec = self.speculation.control()
        return req

    def stats(self) -> Dict[str, Any]:
        out = {"active": len(self.scheduler.active),
               "free_slots": self.kv.free_slots(),
               "quantize": self.quantize,
               "role": self.role,
               "prefilling": len(self._prefilling),
               "installs_waiting": (len(self.handoff.waiting)
                                    if self.handoff is not None else 0),
               "waiting": (self._queue.qsize()
                           + self.scheduler.queue_depth()),
               **self.preemption.stats(),
               "cache_rebuilds": self._cache_rebuilds,
               "kv_bytes_per_token": self._kv_bytes_per_token}
        if self._state_bytes_per_slot:
            out["state_bytes_per_slot"] = self._state_bytes_per_slot
        if self.quantize is not None:
            out["weight_bytes"], out["weight_bytes_f32"] = \
                self._weight_bytes
        programs = self.loop.program_counts()
        if programs:  # RTPU_DEBUG_JAX recompile witness is on
            out["compiled_programs"] = programs
        out.update(self.kv.stats())
        out.update(self.metrics.snapshot())
        # Set-up (engine/README.md "Set-up and compilation"): this
        # engine's constructor, and the PROCESS's compile account where
        # an entry script turned it on.
        out["engine_init_s"] = self._init_s
        account = _compile_cache.account()
        if account is not None:
            t = account.totals()
            out.update(compile_requests=t["requests"],
                       compile_hits=t["hits"],
                       compile_trace_s=t["trace_s"],
                       compile_lower_s=t["lower_s"],
                       compile_backend_s=t["compile_s"],
                       compile_cache_load_s=t["cache_load_s"])
        if self.fleet is not None:
            out.update(self.fleet.stats())
        return out

    def load_snapshot(self) -> Dict[str, Any]:
        """Compact load view for the serve routing/autoscaling path
        (replica.py forwards it; the controller aggregates it and the
        router scores on it). Cheap host-side reads only — safe to call
        from an RPC thread while the engine thread ticks."""
        from ray_tpu.core.config import GLOBAL_CONFIG as cfg

        m = self.metrics.snapshot()
        snap = {
            "role": self.role,
            "waiting": (self._queue.qsize() + self.scheduler.queue_depth()
                        + (len(self.handoff.waiting)
                           + self.handoff.arrivals.qsize()
                           if self.handoff is not None else 0)),
            "active": len(self.scheduler.active),
            # Admitted but still materializing their prompt (chunked
            # prefill): they hold slots and will decode — surfaced
            # separately so routers that predate the key see unchanged
            # waiting/active semantics.
            "prefilling": len(self._prefilling),
            # Parked (preempted) requests will re-admit: queue pressure
            # the router should see even though they hold no slot.
            "parked": len(self.preemption.parked),
            "slots": self.max_batch,
            "free_slots": self.kv.free_slots(),
            "kv_free_blocks": self.kv.free_blocks(),
            "kv_total_blocks": self.kv.total_blocks(),
            "decode_utilization": m["decode_utilization"],
            "ewma_ttft_ms": m["ttft_ms_ewma"],
            "prefix_block_size": self.kv.block_size,
            "prefix_hashes": self.kv.resident_hashes(
                cfg.serve_snapshot_prefix_hashes),
        }
        if self.fleet is not None:
            snap.update(self.fleet.snapshot())
        return snap

    def close(self) -> None:
        self._shutdown = True
        # Join the engine thread: a daemon thread still inside a jitted
        # program at interpreter teardown aborts the process (C++
        # `terminate called without an active exception`). Worst case is
        # one tick (bounded by one device chunk / prefill compile).
        if (self._thread.is_alive()
                and self._thread is not threading.current_thread()):
            self._thread.join(timeout=60.0)
        # RTPU_DEBUG_RES balance assertion: no in-flight KV speculation
        # reservation may outlive the engine (commit_speculation or the
        # slot's release settles each one), and the engine thread must
        # have exited by the join above. Reports, never raises; witness
        # off = one env read.
        _resdbg.check_balanced("engine.close", kinds=("kv_spec",),
                               owner=self.kv)
        self.preemption.close()
        if self.fleet is not None:
            self.fleet.close()
        if self._thread is not threading.current_thread():
            _resdbg.check_balanced("engine.close", kinds=("thread",),
                                   owner=self)

    # ------------------------------------------------------------- engine

    def _fetch(self, tree, tag: str = "decode"):
        """The ONLY device->host sync on the engine's hot path —
        counted twice over: metrics.host_syncs (per decode chunk) and
        the RTPU_DEBUG_JAX witness (per tag), so the one-sync-per-chunk
        invariant is assertable, not aspirational."""
        jax_debug.note_host_sync(f"engine.{tag}")
        waits = not all(a.is_ready() for a in self._jax.tree.leaves(tree))
        out = self._jax.device_get(tree)  # rtpu-lint: disable=host-sync-in-hot-path — this IS the counted sync
        # A fetch that had to wait returns when the device ends that
        # program and begins the next in its queue: the one stamp of
        # the device's own clock the host gets (see DeviceQueue).
        self._devq.fetched(time.perf_counter() if waits else None)
        return out

    def _put(self, value):
        """Explicit host->device placement for dispatch inputs: under
        the RTPU_DEBUG_JAX transfer guard every implicit transfer
        raises, so the engine never grows a hidden one."""
        return self._jax.device_put(value)

    @staticmethod
    def _span(name: str, t0: float, t1: float, req: EngineRequest,
              attrs: Dict[str, Any]) -> None:
        """One per-REQUEST span over two `perf_counter` stamps, under
        the request's trace (callers gate on ``req.trace_ctx``)."""
        _tracing.emit_span(name, _tracing.wall(t0), _tracing.wall(t1),
                           parent=req.trace_ctx, attrs=attrs)

    def _admit(self) -> None:
        """Match waiting requests to free slots; each admission becomes
        a prefill job (one chunk per tick — a single chunk when
        ``prefill_chunk`` is off, so unchunked admissions still prefill
        fully on their admission tick). Slots whose holders are sure to
        end inside the chunk in flight are on offer too
        (``_hand_over``)."""
        self.scheduler.drain_into(self._queue)
        if self.preemption.parked:
            self.preemption.resume()
        lent = self._hand_over()
        first = len(self._prefilling)
        self._run_admissions()
        for job in self._prefilling[first:]:
            if job.adm.slot in lent:
                self.metrics.record_ahead()
        if self.scheduler.queue_depth() and not self.kv.free_slots():
            # Slot-starved with waiters present: a strictly higher
            # priority class may preempt the lowest-priority active.
            if self.preemption.park():
                self._run_admissions()

    def _hand_over(self) -> frozenset:
        """The early hand-over. A decode chunk is in flight, somebody
        waits and the free pool cannot seat them: an active request of
        that chunk's roster that ``scheduler.ends_within`` it — by the
        budget or the row cap the host knew when it dispatched the
        chunk, whatever the chunk samples — lets go of its slot NOW,
        one for each waiter still unseated, where it would have at the
        chunk's retire, after the next chunk had gone out with the slot
        frozen in it. The ordinary admission seats the waiter (FIFO and
        priority classes as ever), this tick's prefill writes the
        slot's rows (and resets a state family's slot) and its job
        joins the next chunk: the device's order is still chunk N, the
        prefills, chunk N+1, so the old request's last step has run by
        then. What chunk N still owes the old request it gets at the
        retire (``scheduler.ending``). An EOS ahead of the budget is
        nobody's to foresee and is seen at the retire as ever; so is
        every finish on a schedule with no chunk in flight at the top
        of a tick (serial, speculative). Parked requests resume ahead
        of the line, so while any is parked nothing is lent to it (and
        the decode role's installs are not in it). Returns the slots
        lent."""
        rec = self._inflight
        if rec is None or self.preemption.parked:
            return frozenset()
        need = self.scheduler.queue_depth() - self.kv.free_slots()
        if need <= 0:
            return frozenset()
        k, held = self.loop.chunk, rec["held"]
        lent = [r for r in self.scheduler.active
                if held.get(r.slot) is r
                and self.scheduler.ends_within(r, k)][:need]
        slots = frozenset(r.slot for r in lent)
        for req in lent:
            self.scheduler.hand_over(req)
        return slots

    def _run_admissions(self) -> None:
        for adm in self.scheduler.admissions():
            if (self.fleet is not None
                    and adm.cached_len < len(adm.request.prompt_ids) - 1):
                try:
                    self.fleet.extend(adm)
                except Exception as e:  # noqa: BLE001 — a failed pull is a skipped optimization; recompute covers it
                    # A failed pull/install is a skipped optimization:
                    # rows it may have touched sit past cached_len and
                    # the suffix prefill overwrites them — unless the
                    # donated install took the cache with it, and with
                    # it the reused prefix this admission counts on.
                    if self._recover_cache(e):
                        self.scheduler.abort_admission(adm.request)
                        self._deliver_error([adm.request], e)
                        continue
            self._prefilling.append(_PrefillJob(adm, pos=adm.cached_len))

    def export_pages(self, slot: int, block_starts: List[int],
                     tag: str = "kv_export"):
        """THE KV page export path — the disagg handoff (handoff.py
        ``finish``) and the spill tier (kv_fleet.py ``spill_evicted``)
        both go through here, so they cannot drift: one jitted program
        per page, ONE counted host sync for the whole batch, and the
        padded-tail invariant stated once — the cache allocation is
        padded to a page multiple whenever the transfer programs are
        built, so export_page's defensive clamp (start <= S - P) never
        fires and every page lands at the exact offset install_page
        will write it back to. Returns host (pages_k, pages_v, crcs);
        each CRC covers the page BYTES (chain hashes cover only token
        identity)."""
        pages_dev = [self.loop.export_page(self.cache,
                                           self._put(np.int32(slot)),
                                           self._put(np.int32(s)))
                     for s in block_starts]
        pages = self._fetch(pages_dev, tag=tag)
        pages_k = [np.ascontiguousarray(k) for k, _v in pages]
        pages_v = [np.ascontiguousarray(v) for _k, v in pages]
        crcs = [zlib.crc32(k.tobytes()) ^ zlib.crc32(v.tobytes())
                for k, v in zip(pages_k, pages_v)]
        return pages_k, pages_v, crcs

    def _prefill_tick(self) -> List[_PrefillJob]:
        """Advance EVERY in-progress prefill by one chunk. Intermediate
        chunks are dispatch-only (no host fetch — their token is
        never needed); the decode tick that follows interleaves with
        their device execution, which is what keeps co-batched TPOT
        flat while a long prompt materializes.

        On the pipelined schedule a FINAL chunk is dispatch-only too:
        its token stays on the device and the jobs are handed back, for
        ``_pipelined_tick`` to put into the chunk it dispatches and to
        land (``_land_prefill``) after it — the device is never left
        waiting for the host to read a first token. Every other
        schedule lands each where it is dispatched and returns
        nothing."""
        landing: List[_PrefillJob] = []
        jobs = list(self._prefilling)
        for i, job in enumerate(jobs):
            if job not in self._prefilling or job.idx == len(job.adm.chunks):
                continue  # failed with the cache an earlier job lost,
                #           or gone out as an earlier job's partner
            partner = self._partner(job, jobs[i + 1:])
            if not self._dispatch_prefill(job, partner):
                continue
            for done in (job, partner) if partner else (job,):
                if self._pipelined:
                    landing.append(done)
                else:
                    self._land_prefill(done)
        return landing

    def _partner(self, job: "_PrefillJob",
                 behind: List[_PrefillJob]) -> Optional[_PrefillJob]:
        """The job that shares ``job``'s prefill program, or None: the
        first of those waiting ``behind`` it (same bucket before the
        neighbouring one) such that both are at the ONLY chunk of their
        plans, the program for both holds at most `_PAIR_ROWS` rows,
        and each prompt, padded to the pair's bucket, still ends within
        its slot's rows — the scheduler's plan promised that for the
        job's own bucket, and an overrun is clamped backwards over rows
        that are resident (`llama._block`). Only where the
        configuration's model module offers the forward for two prompts
        in one call (``loop.prefill_pair``); what the engine can see,
        not an option."""
        if self.loop.prefill_pair is None or len(job.adm.chunks) != 1:
            return None
        bucket = job.adm.chunks[0][1]

        def pairs(other: _PrefillJob) -> bool:
            if (other not in self._prefilling or other.idx
                    or len(other.adm.chunks) != 1):
                return False
            both = max(bucket, other.adm.chunks[0][1])
            return (2 * both <= _PAIR_ROWS
                    and max(job.pos, other.pos) + both <= self.max_len)

        admitted = [other for other in behind if pairs(other)]
        same = [o for o in admitted if o.adm.chunks[0][1] == bucket]
        return (same or admitted or [None])[0]

    def _dispatch_prefill(self, job: "_PrefillJob",
                          partner: Optional[_PrefillJob] = None) -> bool:
        """Dispatch one prefill chunk, no host sync. True when it was
        the job's FINAL chunk: ``job.token`` is then the first
        generated token, on the device, and the job stays in
        ``_prefilling`` until ``_land_prefill`` has fetched it. A chunk
        that raises aborts its admission alone.

        With a ``partner`` (`_partner`: each at its plan's only chunk)
        the two prompts go out as ONE program over ``[2, bucket]``, the
        shorter padded to the larger bucket: one entry of the device's
        queue that both jobs hold, one dispatch counted, a token each;
        neither queued behind the other. One that raises aborts both
        admissions and nobody else."""
        jobs = (job,) if partner is None else (job, partner)
        plans = [j.adm.chunks[j.idx] for j in jobs]
        bucket = max(b for _, b in plans)
        real = sum(n for n, _ in plans)
        final = job.idx == len(job.adm.chunks) - 1
        try:
            with self._tick.phase("prefill_dispatch", slot=job.adm.slot,
                                  bucket=bucket, tokens=real,
                                  rows=len(jobs)):
                t0, ahead = self._tick.now, self._devq.ahead()
                for j in jobs:
                    j.t0 = t0
                    if j.idx == 0:
                        j.t_pf0, j.ahead = t0, ahead
                        self.metrics.record_first_dispatch(*ahead)
                padded = np.zeros((len(jobs), bucket), np.int32)
                for i, (j, (n, b)) in enumerate(zip(jobs, plans)):
                    padded[i, :n] = j.adm.request.prompt_ids[j.pos:j.pos + n]
                    self._compile_bucket(b)
                # The head reads ONE row a prompt, the last real
                # token's, and its argmax is what comes back.
                if partner is None:
                    token, self.cache, *counters = self.loop.prefill_inplace(
                        self.params, self.cache, self._put(padded),
                        self._put(np.int32(job.adm.slot)),
                        self._put(np.int32(job.pos)),
                        self._put(np.int32(plans[0][0] - 1)))
                    tokens = (token,)
                else:
                    tokens, self.cache, *counters = self.loop.prefill_pair(
                        self.params, self.cache, self._put(padded),
                        *(self._put(np.array(v, np.int32)) for v in (
                            [j.adm.slot for j in jobs],
                            [j.pos for j in jobs],
                            [n - 1 for n, _ in plans])))
                # Every chunk's counters ride the final chunk's fetch (a
                # state family resets its slot in the FIRST chunk); a
                # pair's are the program's, counted once.
                job.counters.extend(counters)
                program = self._devq.put(
                    "prefill", t0, tokens[0].is_ready, tokens=real,
                    bucket=bucket, rows=len(jobs))
                self.metrics.record_prefill_chunk(real)
                if partner is not None:
                    self.metrics.record_prefill_pair()
                for j, (n, _) in zip(jobs, plans):
                    j.programs.append(program)
                    # Per-chunk prefix commit: block occupancy and the
                    # slot's resident chain track the materialized
                    # prefix as chunks land, not the whole prompt
                    # up-front.
                    self.kv.commit_prefill(
                        j.adm.slot, j.adm.request.prompt_ids[:j.pos + n])
        except BaseException as e:  # noqa: BLE001 — one bad request
            # must not kill the engine thread (every later request
            # would hang on a dead engine).
            for j in jobs:
                if j in self._prefilling:  # (not failed with the cache)
                    self._abort_prefill(j, e)
            return False
        for j, (n, _), token in zip(jobs, plans, tokens):
            if final:
                j.token = token
            else:
                self._prefill_span(j, j.idx, self._tick.now, ())
            j.idx += 1
            j.pos += n
        return final

    def _compile_bucket(self, bucket: int) -> None:
        """A bucket's tick prefills are compiled where the bucket is
        first met, BOTH of them where two of its prompts may pair: from
        the arguments' shapes, nothing run and nothing donated, and the
        dispatches that follow find the programs compiled. A pair may
        be the first to need either program in the middle of a timed
        window, and the first job of a small bucket may go out in its
        partner's larger one; so set-up's one request a bucket pays for
        all of them (`util/compile_cache.py` counts them as it counts
        every compile). A family without the paired program compiles
        where it always did, at the first dispatch."""
        if bucket in self._buckets_met:
            return
        self._buckets_met.add(bucket)  # rtpu-lint: disable=unbounded-registry-growth — one entry a configured prompt bucket, at most
        if self.loop.prefill_pair is None or 2 * bucket > _PAIR_ROWS:
            return
        for program, rows, index in (
                (self.loop.prefill_inplace, 1, np.int32(0)),
                (self.loop.prefill_pair, 2, np.zeros(2, np.int32))):
            index = self._put(index)
            program.lower(
                self.params, self.cache,
                self._put(np.zeros((rows, bucket), np.int32)),
                index, index, index).compile()

    def _abort_prefill(self, job: "_PrefillJob", e: BaseException) -> None:
        """A prefill chunk (or the fetch of its token) failed: this
        admission fails, alone unless the cache went with it. Seed only
        the PRE-ACQUIRE reused prefix: rows this job dispatched are
        unconfirmed."""
        req = job.adm.request
        if job in self._prefilling:
            self._prefilling.remove(job)
        self.scheduler.abort_admission(
            req, resident=req.prompt_ids[:job.adm.cached_len])
        self._recover_cache(e)
        self._deliver_error([req], e)

    def _prefill_span(self, job: "_PrefillJob", idx: int, t1: float,
                      counters) -> None:
        """One span per CHUNK (chunk/chunks attrs), so TTFT
        decomposition stays accurate under chunked prefill — the gaps
        between chunk spans are the interleaved decode ticks. The final
        chunk's ends with its fetch and carries the counters."""
        req = job.adm.request
        if req.trace_ctx is None:
            return
        n, program = job.adm.chunks[idx][0], job.programs[idx]
        # The bucket and the rows (2: a pair's) of the program that ran.
        attrs = {"prefill_tokens": n, "cached_tokens": job.adm.cached_len,
                 "bucket": program.attrs["bucket"], "slot": job.adm.slot,
                 "rows": program.attrs["rows"],
                 "chunk": idx, "chunks": len(job.adm.chunks),
                 "ahead_chunks": job.ahead[0],
                 "ahead_prefills": job.ahead[1],
                 **self._span_attrs(counters)}
        split = program.split()
        if split is not None:
            attrs["behind_s"], attrs["own_s"] = split
        self._span("engine.prefill", job.t0, t1, req, attrs)

    def _land_prefill(self, job: "_PrefillJob") -> None:
        """The ONE counted prefill sync of an admission, and what its
        first token sets off: TTFT bookkeeping, the token onto the
        stream, the request into the decode roster (or its handoff).
        On the pipelined schedule this runs AFTER the chunk the request
        joins is dispatched (the device took the token from the
        prefill's own output), so the wait here is under device work."""
        if job not in self._prefilling:
            return  # failed with the cache since its dispatch
        req, slot = job.adm.request, job.adm.slot
        cached = job.adm.cached_len
        try:
            # Intermediate chunks fetch nothing (np.asarray on a device
            # array here was the jax-lint rule's first in-tree catch: an
            # uncounted implicit sync). This waits out whatever the
            # device had queued before the prefill, then copies 4 bytes
            # (and the family's counters, where the program returns
            # those).
            with self._tick.phase("prefill_fetch", slot=slot,
                                  bucket=job.adm.chunks[-1][1]) as attrs:
                fetched = self._fetch((job.token, *job.counters),
                                      tag="prefill")
                token, *counters = fetched
                attrs["bytes"] = sum(
                    a.nbytes for a in self._jax.tree.leaves(fetched))
                self.metrics.record_prefill_fetch(attrs["bytes"])
        except BaseException as e:  # noqa: BLE001 — one bad request
            self._abort_prefill(job, e)
            return
        self._prefilling.remove(job)
        t1 = self._tick.now  # the first token is on the host
        self._devq.seen(job.programs[-1], t1)
        splits = [p.split() for p in job.programs]
        if all(splits):
            self.metrics.record_prefill_split(
                sum(s[0] for s in splits), sum(s[1] for s in splits))
        self._prefill_span(job, len(job.adm.chunks) - 1, t1, counters)
        with self._tick.phase("prefill_deliver", slot=slot):
            # First generated token: from the LAST REAL prompt pos (row
            # n-1 of the final chunk), chosen on the device.
            first = int(token[0])
            self.metrics.record_model_counters(counters)
            req.first_token_t = t1
            queue_s = max(0.0, job.t_pf0 - req.arrival_t)
            prefill_s = max(0.0, t1 - job.t_pf0)
            if self.fleet is not None:
                self.fleet.note_prefill_cost(prefill_s,
                                             len(req.prompt_ids) - cached)
            if req.trace_ctx is not None:
                # The request's wait, on its real stamps: arrival to
                # the first chunk's dispatch.
                self._span("engine.queued", req.arrival_t, job.t_pf0, req,
                           {"prompt_len": len(req.prompt_ids)})
            self.metrics.record_admit(queue_s, prefill_s,
                                      len(req.prompt_ids) - cached, cached)
            req.generated.append(first)
            if req.stream_queue is not None:
                req.first_put_t = time.perf_counter()
                req.stream_queue.put(("token", first))
            if req.handoff:
                self.handoff.finish(req)
                return
            self.scheduler.activate(req)
            self._maybe_finish(req, first)

    def _maybe_finish(self, req: EngineRequest, last_tok: int) -> bool:
        done = self.scheduler.is_finished(req, last_tok)
        if done:
            self.scheduler.finish(req)
            if not req.future.done():
                req.future.set_result({
                    "token_ids": req.generated,
                    "num_generated": len(req.generated),
                    "cached_prefix_len": req.cached_len,
                })
            if req.stream_queue is not None:
                req.stream_queue.put(("done", None))
            if req.trace_ctx is not None:
                # Ship this request's engine spans now: a sub-64-span
                # buffer would otherwise hold them past the caller's
                # trace query (one small frame per finished request).
                _tracing.flush()
        return done

    def _span_attrs(self, counters) -> Dict[str, int]:
        """The named counters of one fetch (a chunked prefill's holds a
        dict a chunk: summed) as span attributes."""
        out: Dict[str, int] = {}
        for fetched in counters:
            for name, value in fetched.items():
                attr = self._span_attr_names.get(name)
                if attr is not None:
                    out[attr] = out.get(attr, 0) + int(value)
        return out

    def _roster_arrays(self, active, joining=()):
        """Per-slot host inputs for a chunk dispatch (plain or spec):
        ``active`` requests with what the host knows of them, and
        ``joining`` prefill jobs whose first token is still on the
        device — their row and ``done`` are the device's to fill
        (``loop.roster_join``), the rest is known here."""
        tokens = np.zeros((self.max_batch, 1), np.int32)
        # The scan's static shape steps EVERY slot, so inactive slots
        # still write one KV row per step. Park those writes on the LAST
        # row: resident prefixes never extend past max_len-2 (a request
        # needs >= 1 suffix + 1 generated token), so the last row is
        # never prefix-cache-reused — row 0 of a freed slot is. (The
        # verify program ignores this and parks in the scratch strip.)
        lengths = np.full((self.max_batch,), self.max_len - 1, np.int32)
        remaining = np.zeros((self.max_batch,), np.int32)
        eos_ids = np.full((self.max_batch,), -1, np.int32)
        done = np.ones((self.max_batch,), bool)  # inactive slots frozen
        for req in active:
            tokens[req.slot, 0] = req.generated[-1]
            lengths[req.slot] = req.length
            remaining[req.slot] = req.remaining()
            if req.eos_id is not None:
                eos_ids[req.slot] = req.eos_id
            done[req.slot] = False
        for job in joining:
            req = job.adm.request
            lengths[req.slot] = len(req.prompt_ids)
            remaining[req.slot] = req.max_new_tokens - 1
            if req.eos_id is not None:
                eos_ids[req.slot] = req.eos_id
        return tokens, lengths, remaining, eos_ids, done

    @staticmethod
    def _deliver_error(reqs, e: BaseException) -> None:
        for req in reqs:
            if not req.future.done():
                req.future.set_exception(e)
            if req.stream_queue is not None:
                req.stream_queue.put(("error", e))

    def _fail_roster(self, e: BaseException, joining=()) -> None:
        """A chunk failed: everyone it was (or would have been)
        dispatched with fails — the roster, and the ``joining`` jobs
        whose first token it took from the device."""
        failed = self.scheduler.fail_active()
        for job in joining:
            if job in self._prefilling:
                self._prefilling.remove(job)
                self.scheduler.abort_admission(job.adm.request)
                failed.append(job.adm.request)
        self._recover_cache(e)
        self._deliver_error(failed, e)

    def _recover_cache(self, e: BaseException) -> bool:
        """Every handler of a failed cache-writing dispatch (or of the
        fetch of its results) ends here. The programs take the cache
        DONATED: one that raised has deleted ``self.cache``, one that
        failed on the device has left a result that raises when read,
        and either way every later request would die on it. So if the
        cache cannot be waited for (a failure path may sync), allocate
        a new one and fail whoever had rows in the old: the roster,
        the prefills under way, and — in the KV manager — every
        resident prefix (a parked session resumes by prefilling again).
        Waiting requests are untouched. True if the cache was rebuilt."""
        try:
            self._jax.block_until_ready(self.cache)
            return False
        except Exception:  # rtpu-lint: disable=swallowed-exception — the probe's failure IS the signal; ``e`` is what the callers deliver
            pass
        # State first, errors last: whoever sees an error delivered
        # here sees the rebuilt engine behind it.
        self._inflight = None
        lost = self.scheduler.fail_active()
        for job in self._prefilling:
            if job.adm.request.slot >= 0:  # the failing job's is released
                self.scheduler.abort_admission(job.adm.request)
                lost.append(job.adm.request)
        self._prefilling = []
        self.kv.forget_resident()
        self.cache = self.model.init_kv_cache(self.cfg, self.max_batch,
                                              self._cache_rows)
        self._cache_rebuilds += 1
        self._deliver_error(lost, e)
        return True

    def _decode_tick(self, landing: List[_PrefillJob]) -> None:
        """One device chunk for the whole roster + ONE host fetch.

        With speculation enabled, ticks where prompt lookup proposed at
        least one draft dispatch the multi-token verify program; ticks
        with nothing to verify fall through to the plain chunk — so a
        workload on which lookup never bites costs nothing over
        speculation-off. Multi-step double-buffering applies only to
        the drafter-free engine: drafts are proposed from host-visible
        tokens, which an in-flight chunk would lag by one dispatch
        (``landing`` is empty off that schedule: ``_prefill_tick``).
        """
        if self.speculation is not None:
            with self._tick.phase("decode_dispatch", drafting=True):
                drafts = self.speculation.drafts()
            if drafts:
                self.speculation.tick(drafts)
                return
        if self._pipelined:
            self._pipelined_tick(landing)
        else:
            self._plain_tick()

    def _plain_tick(self) -> None:
        """Dispatch one chunk and fetch it in the same tick (the
        pre-multi-step schedule; also the spec engine's zero-draft
        path)."""
        rec = self._dispatch_chunk()
        if rec is not None:
            self._retire_chunk(rec)

    def _land_inflight(self) -> bool:
        """Land the decode chunk in flight, if one is, NOW: a mechanism
        that recycles a slot outside the retire (preemption) calls this
        first, so the chunk's tokens reach whoever it was dispatched with
        while they hold their slots. False on device failure."""
        prev, self._inflight = self._inflight, None
        return prev is None or self._retire_chunk(prev)

    def _pipelined_tick(self, landing: List[_PrefillJob]) -> None:
        """Multi-step schedule: enqueue chunk N+1 BEFORE fetching chunk
        N, whatever the roster did since N was dispatched — the roster's
        decode state lives on the device across the change
        (``_dispatch_chunk``), so the tick's host work (chunk N's fetch
        and delivery, the admissions' first tokens) runs under chunk
        N+1's device time instead of ahead of it. Device-side freezing
        keeps the chunk in flight correct across finishes (a slot the
        host retires was already done on device: its carried mask emits
        nothing, so the trailing chunk of a burst delivers zero tokens
        and is dropped unfetched). The order below is the device's own:
        chunk N, the tick's prefills, chunk N+1 — so each fetch finds
        its program done or running, never queued behind a later one.

        Chunk N+1 goes out LATE in chunk N's time where the thread has
        something better to do than sit in chunk N's fetch: ``_listen``
        waits on the mailbox first, and an arrival heard there has its
        prefill on the device behind chunk N alone (its job joins
        ``landing``)."""
        listened = self._listen(landing)
        prev, self._inflight = self._inflight, None
        joining = [j for j in landing if not j.adm.request.handoff]
        if self._roster_outlives_chunk(prev, joining):
            # Bound before the retire: a device failure found there
            # drops it with the cache (_recover_cache).
            self._inflight = self._dispatch_chunk(prev, joining,
                                                  listened)
        if prev is not None:
            self._retire_chunk(prev)
        for job in landing:
            self._land_prefill(job)

    def _admits_at_once(self) -> bool:
        """An arrival would be admitted the moment it is heard: nobody
        waits ahead of it (FIFO holds) and a slot is free. The decode
        role's arrivals come by another queue."""
        return (self.kv.free_slots() > 0 and not self.preemption.parked
                and not self.scheduler.queue_depth()
                and self.role != "decode")

    def _listen_deadline(self, rec: Dict[str, Any]) -> Optional[float]:
        """When the wait for arrivals must end for chunk N+1 to reach
        the device before chunk N (``rec``) leaves it: N's start, plus
        the SHORTEST of the last chunks' device times, less a margin of
        their scatter (longest less shortest) and the LONGEST of the
        last carried dispatches' host times. All measured by the tick
        (the device's queue, ``_dispatch_chunk``); None when any of it
        is unknown — the last fetch found its result ready, no chunk
        has been timed, a program no fetch stamps lay ahead of ``rec``
        — and the tick then keeps the order it always had."""
        began, owns = rec["program"].start, self._devq.chunk_owns
        if (began is None or not rec["carried"] or not owns
                or not self._dispatch_s):
            return None
        shortest = min(owns)
        margin = max(owns) - shortest + max(self._dispatch_s)
        return began + shortest - margin

    @staticmethod
    def _chunk_done(rec: Dict[str, Any]) -> bool:
        """Chunk ``rec`` has left the device. No sync, no transfer: the
        ``_fetch`` count and the transfer guard see nothing."""
        return rec["outs"][0].is_ready()

    def _listen(self, landing: List[_PrefillJob]) -> bool:
        """While the chunk in flight runs and an arrival could be
        admitted at once, the thread waits on its MAILBOX, not in that
        chunk's fetch: an arrival heard here is admitted and its first
        prefill chunk dispatched now, behind the chunk in flight alone
        (a final chunk's job joins ``landing``, as if the top of the
        tick had dispatched it), where it would have waited for the top
        of the next tick and then behind a chunk that had only begun.
        At the deadline the tick goes on: the device's order is still
        chunk N, the prefills, chunk N+1.

        A wrong estimate costs one slice: the wait does not begin, and
        ends, when the chunk is done (``_chunk_done``). Booked as
        ``decode_fetch`` — the thread waits for the device here as it
        does there — with the admissions and dispatches heard inside it
        under their own phases. True if the thread listened at all."""
        rec = self._inflight
        if rec is None or not self._admits_at_once():
            return False
        deadline = self._listen_deadline(rec)
        now = time.perf_counter()
        if deadline is None or deadline <= now or self._chunk_done(rec):
            return False
        with self._tick.phase("decode_fetch", listening=True) as attrs:
            attrs["heard"] = 0
            self._devq.busy_at(now)
            while (self._inflight is rec and not self._shutdown
                   and not self._chunk_done(rec)):
                now = time.perf_counter()
                self._devq.busy_at(now)
                left = deadline - now
                if left <= 0:
                    break
                try:
                    req = self._queue.get(
                        timeout=min(left, _LISTEN_SLICE_S))
                except queue.Empty:
                    continue
                attrs["heard"] += self._hear(req, landing)
                if not self._admits_at_once():
                    break
        return True

    def _hear(self, req: EngineRequest, landing: List[_PrefillJob]) -> int:
        """Admit one arrival straight from the mailbox (the waiting
        line was empty, so FIFO holds) and dispatch its first prefill
        chunk. Returns the admissions made (0 if the slot raced away)."""
        self.scheduler.submit(req)
        first = len(self._prefilling)
        with self._tick.phase("admit", listening=True):
            self._run_admissions()
        jobs = self._prefilling[first:]
        for job in jobs:
            self.metrics.record_heard()
            if self._dispatch_prefill(job):
                landing.append(job)
        return len(jobs)

    def _roster_outlives_chunk(self, prev, joining) -> bool:
        """True when some request can still be live AFTER the in-flight
        chunk ``prev`` lands: one that joined the roster since (it is
        not in that chunk at all), or one whose budget and row cap —
        both known host-side — survive another ``chunk`` tokens
        (``scheduler.ends_within``, which ``_hand_over`` asks of one
        slot at a time). When nobody can, the next chunk would be
        all-frozen by construction: skip it instead of burning a whole wasted dispatch per burst
        (short generations — budget <= chunk — would otherwise pay ~2x
        decode compute for zero tokens). EOS is the one early stop the
        host can't predict; an EOS-ended burst still wastes at most one
        trailing chunk."""
        active = self.scheduler.active
        if joining or prev is None:
            return bool(joining or active)
        k = self.loop.chunk
        held = prev["held"]
        return any(held.get(r.slot) is not r
                   or not self.scheduler.ends_within(r, k)
                   for r in active)

    def _dispatch_chunk(self, prev: Optional[Dict[str, Any]] = None,
                        joining=(), listened: bool = False):
        """Enqueue one decode chunk (no host sync). Without ``prev``
        the inputs are the host's (``_roster_arrays``). With the record
        of the chunk in flight they are MERGED on the device, slot by
        slot, from what the host knows now:

        - the request ``prev`` was dispatched with: what that chunk
          carries (token, length, budget, ``done`` — a slot that
          finished inside it stays frozen);
        - a request activated since: the host's values;
        - a ``joining`` prefill job: the host's, but the token, which
          the prefill's own output provides, and ``done`` by the scan's
          rules on it;
        - nobody (freed, failed, parked): ``done`` and the parked row.

        ``listened``: the tick waited on its mailbox first, so the
        program ahead may have left the device meanwhile; the queue
        asks it (``DeviceQueue.put``, ``poll``).

        Returns the in-flight record _retire_chunk consumes, or None on
        a dispatch failure (roster and joiners failed)."""
        active = self.scheduler.active
        roster = active + [j.adm.request for j in joining]
        carried = prev is not None
        with self._tick.phase("decode_dispatch", slots=len(roster),
                              carried=carried):
            t0 = self._tick.now
            held = prev["held"] if carried else {}
            kept = [r.slot for r in active if held.get(r.slot) is r]
            fresh = [r for r in active if held.get(r.slot) is not r]
            state = tuple(self._put(a)
                          for a in self._roster_arrays(fresh, joining))
            try:
                if carried:
                    keep = np.zeros((self.max_batch,), bool)
                    keep[kept] = True
                    state = self.loop.roster_merge(
                        self._put(keep), prev["carry"], state)
                for job in joining:
                    tok_d, done_d = self.loop.roster_join(
                        *state, self._put(np.int32(job.adm.slot)),
                        job.token)
                    state = (tok_d, *state[1:4], done_d)
                toks_d, n_valid_d, ntok_d, nlen_d, nrem_d, ndone_d, \
                    self.cache, *counters_d = self.loop.decode_chunk(
                        self.params, self.cache, *state)
            except BaseException as e:  # noqa: BLE001 — fail all waiters
                self._fail_roster(e, joining)
                return None
        self.metrics.record_dispatch(carried)
        if carried:
            self._dispatch_s.append(self._tick.now - t0)
        # A family's counters ride the chunk's one fetch.
        rec = {"outs": (toks_d, n_valid_d, *counters_d),
               "carried": carried,
               "carry": (ntok_d, nlen_d, nrem_d, state[3], ndone_d),
               # Who the chunk was dispatched with, by slot. The next
               # dispatch carries a slot only for the SAME request, and
               # the retire delivers only to it; the strong refs keep a
               # finished request's identity from being recycled for a
               # newly admitted one in the same slot while this record
               # lives.
               "held": {r.slot: r for r in roster},
               "t0": t0}
        # The chunk begins where the program ahead of it ends; a fetch
        # will show that moment if that program is the chunk in flight
        # or a prefill whose token the tick lands.
        rec["program"] = self._devq.put(
            "chunk", t0, lambda: self._chunk_done(rec), poll=listened,
            slots=len(roster))
        return rec

    def _retire_chunk(self, rec: Dict[str, Any]) -> bool:
        """The tick's ONE host fetch: land the chunk's tokens, deliver
        to whoever it was dispatched with and still holds the slot or
        is ``ending`` (handed its slot over ahead: the slot's rows and
        occupancy are the newcomer's, the chunk's tokens still this
        request's, and they finish it). A request that finished since
        reports n_valid 0 — the device carried its done mask; one that
        failed or was parked since has let go of its slot. Retire
        finishes. False on device failure."""
        try:
            with self._tick.phase("decode_fetch",
                                  slots=len(rec["held"])) as attrs:
                # device_get returns host ndarrays: [B, K] ids + [B] valid.
                chunk_ids, n_valid, *counters = self._fetch(rec["outs"])
                attrs["bytes"] = chunk_ids.nbytes + n_valid.nbytes
        except BaseException as e:  # noqa: BLE001 — fail all waiters
            self._fail_roster(e)
            return False
        now = self._tick.now
        # Where fetches that waited saw both its ends: device seconds,
        # whatever the host did (or compiled) in between.
        split = self._devq.seen(rec["program"], now)
        # TPOT window: a PIPELINED chunk was dispatched one tick ago, so
        # dispatch->fetch would fold the whole intervening host tick
        # (which overlapped device compute) into per-token latency — an
        # apparent regression exactly when latency improved. Measure the
        # steady-state cadence instead: time since the LAST fetch
        # completed. Serial ticks reduce to dispatch->fetch (the
        # previous retire ended just before this record's dispatch).
        elapsed = now - max(rec["t0"], self._last_retire_t)
        self._last_retire_t = now
        ending = self.scheduler.ending
        holders = [(slot, req) for slot, req in rec["held"].items()
                   if req.slot == slot or req in ending]
        delivered = 0
        self.metrics.record_model_counters(counters)
        touched = {**self._span_attrs(counters), "period_s": elapsed}
        if split:
            touched["own_s"] = split[1]
        with self._tick.phase("decode_deliver",
                              slots=len(holders)) as attrs:
            for slot, req in holders:
                n = int(n_valid[slot])
                delivered += n
                if req.trace_ctx is not None and n:
                    self._span("engine.decode_chunk", rec["t0"], now, req,
                               {"tokens": n, "slot": slot, **touched})
                for j in range(n):
                    tok = int(chunk_ids[slot, j])
                    req.length += 1
                    if req.slot == slot:
                        self.kv.grow(slot)  # block-granular occupancy
                    req.generated.append(tok)
                    if req.stream_queue is not None:
                        req.stream_queue.put(("token", tok))
                    if self._maybe_finish(req, tok):
                        break  # device froze the slot here; rest repeat
            attrs["tokens"] = delivered
            # Device utilization denominator: a slot that was live at
            # the chunk's first step is scanned for the full chunk
            # (static shapes) whether or not it freezes mid-chunk —
            # delivered/live_steps < 1.0 shows the frozen-overshoot
            # waste. Counted from what the device reports, not from the
            # roster at dispatch: a slot carried into the chunk already
            # frozen was never live in it.
            k, live = self.loop.chunk, n_valid[n_valid > 0]
            live_steps = k * len(live)
            self.metrics.record_chunk(delivered, live_steps, elapsed)
            # ... and of those steps, the ones scanned for a request
            # that had ended inside the chunk.
            self.metrics.record_retire(
                k, elapsed, live_steps - int(live.sum()),
                split[1] if split else None)
            _flight.record("engine_tick", tok=delivered, act=len(holders))
        return True

    def _engine_loop(self) -> None:
        tick = self._tick
        # The decode role's arrivals come by the handoff's own queue.
        installs = self.handoff if self.role == "decode" else None
        while not self._shutdown:
            tick.lap()
            # tick_guard is a null context unless RTPU_DEBUG_JAX=1 and
            # RTPU_DEBUG_JAX_TRANSFER_GUARD are set; then every tick
            # runs under jax.transfer_guard — implicit device traffic
            # raises instead of silently syncing (all engine dispatch
            # inputs go through the explicit _put/_fetch pair).
            with jax_debug.tick_guard():
                with tick.phase("admit") as attrs:
                    self._admit()
                    attrs["prefilling"] = len(self._prefilling)
                if installs is not None:
                    with tick.phase("install"):
                        installs.tick()
                landing = self._prefill_tick()
            self.metrics.record_depths(self.scheduler.queue_depth(),
                                       len(self.scheduler.active),
                                       self.kv.hit_rate())
            if (not self.scheduler.active and not landing
                    and not self.scheduler.ending):
                if self._prefilling or (installs is not None
                                        and installs.waiting):
                    continue  # keep chunked prefills / installs advancing
                # A burst just drained: the multi-step trailing chunk
                # (dispatched while every member was already frozen on
                # device) delivers nothing by construction — drop it
                # unfetched. Its cache output already landed at
                # dispatch time.
                self._inflight = None
                if (installs is not None
                        and not installs.arrivals.empty()):
                    continue  # a handoff just arrived: install it now
                try:
                    # Straight into the waiting line (re-putting to the
                    # mailbox would reorder it behind later arrivals and
                    # break FIFO admission); admitted on the next tick.
                    with tick.phase("idle"):
                        self.scheduler.submit(self._queue.get(timeout=0.1))
                except queue.Empty:
                    pass
                continue
            with jax_debug.tick_guard():
                self._decode_tick(landing)
