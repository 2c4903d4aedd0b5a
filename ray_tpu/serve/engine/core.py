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
"""

from __future__ import annotations

import functools
import math
import queue
import threading
import time
import zlib
from collections import OrderedDict, deque
from typing import Any, Deque, Dict, List, Optional

import numpy as np

from ray_tpu.devtools import jax_debug
from ray_tpu.devtools import res_debug as _resdbg
from ray_tpu.serve.engine.decode_loop import DecodeLoop, serving_params
from ray_tpu.serve.engine.drafter import PromptLookupDrafter, SpecControl
from ray_tpu.serve.engine.kv_manager import KVCacheManager, chain_hashes
from ray_tpu.serve.engine.metrics import (DeviceQueue, EngineMetrics,
                                          TickClock)
from ray_tpu.serve.engine.scheduler import (EngineRequest, Scheduler,
                                            bucket_for)
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

    Constructor signature is a superset of the round-5 ``LLMEngine``:
    ``decode_chunk`` now defaults to 8 (K decode steps per host sync —
    a per-token fetch puts the host round-trip on every token) and
    ``prefix_block`` sets the prefix-cache block granularity.

    Speculative decoding (``spec_draft_len`` > 0): each decode tick the
    host proposes up to ``spec_chunk * spec_draft_len`` continuation
    tokens per request by prompt lookup (drafter.py), the device
    verifies them in multi-token windows (decode_loop.verify_chunk) and
    the host commits exactly the accepted prefix — greedy output is
    token-identical to spec-off, only the number of forward passes per
    token changes. Program choice is per TICK and roster-wide: a tick
    with no drafts anywhere dispatches the unchanged plain chunk, while
    one drafting request routes the whole roster through the verify
    program (draft-free neighbors then advance ``spec_chunk`` tokens
    per dispatch instead of ``decode_chunk`` — co-batching interference
    comparable to sharing the roster with any long request).
    ``spec_draft_len=0`` (the default) builds none of this: no verify
    program, no cache padding, byte-identical engine behavior to the
    pre-speculation subsystem.

    ``quantize="int8"`` quantizes the matmul weights to weight-only
    int8 at engine construction (per-output-channel fp32 scales,
    ``models/quant.py``): decode and verify read HALF the weight bytes
    per step — the same memory-bandwidth bound speculative decoding
    attacks, so the two knobs compound. Greedy outputs may differ from
    the f32 engine (quantization error), but spec-on vs spec-off WITHIN
    a quantized engine keeps the token-identical invariant (both run
    the same quantized weights).

    ``prefill_chunk`` > 0 splits long prompt suffixes into chunks of
    that many real tokens and dispatches ONE chunk per engine tick,
    interleaved with the roster's decode chunks (Sarathi-style chunked
    prefill, Agrawal et al. 2024): a long prompt no longer stalls
    every co-batched request's TPOT for its whole prefill. Only the
    final chunk's token is fetched (still one counted prefill sync
    per admission), the KV manager commits the materialized prefix
    chain per chunk, and greedy output is token-identical to the
    unchunked path (same positions, same rows, same math).

    ``multi_step`` (default on, plain-decode path only) double-buffers
    decode dispatch: each tick enqueues chunk N+1 BEFORE fetching chunk
    N's tokens, WHATEVER the roster did in between. The roster's decode
    state (tokens, lengths, budgets, EOS ids, done mask) lives on the
    device across a roster change: a slot still held by the request it
    was dispatched with takes chunk N's carry (a finish inside chunk N
    stays frozen), a request activated since takes the host's values,
    a request whose prefill was dispatched this tick takes its first
    token from the prefill's own device output, and a slot nobody
    holds is parked and done (``_dispatch_chunk``). Chunk N's fetch
    and delivery and the admissions' first-token fetches then run
    under chunk N+1's device time. Exactly one host sync per FETCHED
    chunk and one per admission either way (the witness budget is
    unchanged); at most one trailing chunk per burst is dispatched
    wastefully (every roster member already frozen on device) and
    dropped unfetched. A request whose budget or row cap must end it
    inside the chunk in flight hands its slot to the next waiter
    before that chunk is fetched (``_hand_over``): the waiter joins
    chunk N+1 where the slot would have ridden it frozen. Disabled
    automatically while speculation drafts
    (drafts are proposed from host-visible tokens, which an in-flight
    chunk would lag by one dispatch); ``multi_step=False`` and the
    speculative engine dispatch, fetch and deliver a chunk in one
    tick, and fetch a first token where its prefill is dispatched.
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
        gate = kv_fleet_min_prefix_blocks
        if gate is None:
            from ray_tpu.core.config import GLOBAL_CONFIG as _cfg

            gate = _cfg.serve_kv_fleet_min_prefix_blocks
        fleet_on = not (isinstance(gate, int) and gate < 0)
        # What this family's cache cannot do yet is refused here, by
        # name, never run wrong.
        asked = {"quantize": quantize is not None,
                 "spec_draft_len": int(spec_draft_len) > 0,
                 "role": role != "colocated", "kv_fleet": fleet_on}
        for option, why in getattr(self.model, "ENGINE_REFUSES", {}).items():
            if option not in asked:
                raise ValueError(
                    f"{self.model.__name__}.ENGINE_REFUSES names "
                    f"{option!r}, which is no option of this engine "
                    f"(it knows {sorted(asked)})")
            if asked[option]:
                raise ValueError(
                    f"{self.model.__name__} cannot serve with {option} "
                    f"yet: {why}")
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
        self.spec_draft_len = max(0, int(spec_draft_len))
        self.spec_adaptive = bool(spec_adaptive)
        self.drafter = (PromptLookupDrafter(ngram_max=spec_ngram_max)
                        if self.spec_draft_len else None)

        # Fleet KV tier gate (kv_fleet.py). None defers to the config
        # knob; -1 = off (the engine below is byte-identical to the
        # pre-fleet one: no transfer programs for colocated roles, no
        # spill hook, no extra snapshot keys); 0 = always pull; n>0 =
        # pull only contiguous runs of >= n blocks; "auto" = gate on
        # the measured pull-vs-recompute crossover.
        self._fleet_min_blocks = gate

        with _compile_cache.phase("engine.decode_loop"):
            self.loop = DecodeLoop(
                self.cfg, max_len=self.max_len, chunk=self.decode_chunk,
                spec_window=self.spec_draft_len + 1, spec_chunk=spec_chunk,
                prefill_budget=len(self.buckets),
                kv_page=(prefix_block
                         if (role != "colocated" or fleet_on) else 0))
        # Verify windows span spec_draft_len+1 rows; the scratch strip
        # past max_len absorbs parked/overrun writes so they can never
        # clamp back onto resident rows (decode_loop docstring). Row
        # accounting everywhere else still uses the logical max_len.
        cache_rows = self.max_len + self.loop.scratch_rows
        if role != "colocated" or fleet_on:
            # KV-page export/install moves whole pages: pad the
            # allocation so the tail page of a max-length prompt never
            # needs the transfer programs' defensive clamp (a clamped
            # start on ONE side of a prefill→decode pair whose scratch
            # strips differ would land rows at the wrong offset). The
            # fleet spill/pull tier moves the same pages, so a
            # fleet-enabled colocated engine pads identically.
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
        # The pipelined chunk schedule (_pipelined_tick) is the
        # drafter-free engine's; drafts need host-visible tokens.
        self._pipelined = self.multi_step and self.drafter is None
        self.metrics = EngineMetrics(
            name, getattr(self.model, "COUNTER_MAXES", ()))
        # The engine thread's phase clock (engine.tick.* counters and
        # spans); created here, used by that thread alone.
        self._tick = TickClock(self.metrics, jax.profiler.TraceAnnotation)
        self._devq = DeviceQueue(self.metrics, self._tick)

        # Fleet KV page tier: evicted prefix blocks spill into a shared
        # page store (shm when a cluster runtime is attached, an
        # in-process LRU otherwise) and cache misses pull them back
        # through the install_page + chain-verify seam. self._fleet is
        # the off switch every fleet code path gates on.
        self._fleet = None
        if fleet_on:
            from ray_tpu.serve.engine import kv_fleet as _kvf

            self._fleet = _kvf.resolve_store(kv_fleet_store)
            self._fleet_ns = _kvf.fleet_namespace(
                self.cfg, self.kv.block_size, quantize, seed)
            self._fleet_lock = threading.Lock()
            self._fleet_recent: "OrderedDict[int, None]" = OrderedDict()
            self._fleet_block_count = 0
            self._fleet_stats = {"kv_fleet_hits": 0,
                                 "kv_fleet_pulled_blocks": 0,
                                 "kv_fleet_spilled_blocks": 0,
                                 "kv_fleet_tokens_reused": 0,
                                 "kv_fleet_rejects": 0}
            # Pull-vs-recompute crossover inputs: store-side costs are
            # measured now (synthetic page roundtrip); the recompute
            # side arrives from real prefill timings (_note_prefill_cost).
            self._fleet_pf_ms_blk: Optional[float] = None
            self._fleet_pf_samples = 0
            self._fleet_pull_ms_page, self._fleet_lookup_ms = \
                self._measure_fleet_costs()
            self.kv.spill_hook = self._spill_evicted
            # Serialization + store puts happen off the engine thread:
            # the engine only exports (device work must stay on its
            # thread) and hands host pages over.
            self._spill_q: "queue.Queue" = queue.Queue()
            self._spill_thread = _resdbg.track_thread(
                threading.Thread(target=self._spill_loop, daemon=True,
                                 name="llm-kv-spill"), owner=self)
            self._spill_thread.start()

        # Chunked-prefill jobs in flight (admitted requests whose
        # suffix is still materializing, one chunk per tick) and the
        # multi-step tick's in-flight decode chunk (dispatched, not yet
        # fetched). Engine-thread-only state; bounded by max_batch and
        # one chunk respectively.
        self._prefilling: List[_PrefillJob] = []
        self._buckets_met: set = set()   # `_compile_bucket`
        self._inflight: Optional[Dict[str, Any]] = None
        # Priority preemption (per-tenant QoS): parked lower-priority
        # requests awaiting resume, plus lifetime counters. Engine-
        # thread-only state like the roster itself.
        self._parked: List[EngineRequest] = []
        self._preempts = 0
        self._resumes = 0
        self._last_retire_t = 0.0  # TPOT cadence anchor (see _retire_chunk)
        # What the listening wait (_listen) derives its deadline from,
        # all measured by the tick itself: the device's queue
        # (``_devq``: when the chunk in flight began, the device
        # seconds of the last few chunks whose ends were both seen) and
        # the host seconds of the last few carried dispatches.
        self._dispatch_s: Deque[float] = deque(maxlen=8)
        self._queue: "queue.Queue[EngineRequest]" = queue.Queue()
        # Decode role: KV-page install jobs handed over from prefill
        # replicas. Device work happens on the engine thread (installs
        # run under the tick transfer guard like every other dispatch);
        # jobs that race slot exhaustion wait in FIFO order.
        self._install_queue: "queue.Queue" = queue.Queue()
        self._install_waiting: List[tuple] = []
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
        self._install_queue.put((req, payload))
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
        if self.spec_draft_len:
            # Draft-buffer capacity at full acceptance: every window
            # advances draft_len+1 positions (_draft_for_roster packs
            # rows at that stride), the last window needs no bonus.
            cap = (self.loop.spec_chunk * (self.spec_draft_len + 1)) - 1
            req.spec = SpecControl(
                allowance=self.spec_draft_len,
                max_allowance=cap if self.spec_adaptive
                else self.spec_draft_len)
        return req

    def stats(self) -> Dict[str, Any]:
        out = {"active": len(self.scheduler.active),
               "free_slots": self.kv.free_slots(),
               "quantize": self.quantize,
               "role": self.role,
               "prefilling": len(self._prefilling),
               "installs_waiting": len(self._install_waiting),
               "waiting": (self._queue.qsize()
                           + self.scheduler.queue_depth()),
               "parked": len(self._parked),
               "preempts": self._preempts,
               "resumes": self._resumes,
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
        if self._fleet is not None:
            with self._fleet_lock:
                out.update(self._fleet_stats)
            out["kv_pull_vs_recompute_crossover_blocks"] = \
                self._crossover_blocks()
            out["kv_fleet_pull_ms_per_page"] = self._fleet_pull_ms_page
            out["kv_fleet_lookup_ms"] = self._fleet_lookup_ms
            out["kv_fleet_prefill_ms_per_block"] = self._fleet_pf_ms_blk
            try:
                out["kv_fleet_store"] = self._fleet.stats()
            except Exception:  # rtpu-lint: disable=swallowed-exception — stats enrichment; a store without a stats endpoint is fine
                pass
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
                        + len(self._install_waiting)
                        + self._install_queue.qsize()),
            "active": len(self.scheduler.active),
            # Admitted but still materializing their prompt (chunked
            # prefill): they hold slots and will decode — surfaced
            # separately so routers that predate the key see unchanged
            # waiting/active semantics.
            "prefilling": len(self._prefilling),
            # Parked (preempted) requests will re-admit: queue pressure
            # the router should see even though they hold no slot.
            "parked": len(self._parked),
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
        if self._fleet is not None:
            # Fleet-residency summary for the router's fleet term:
            # distinct blocks this replica can re-install without
            # recompute, plus the capped newest chain hashes. Keys
            # exist ONLY when the tier is on, so fleet-off snapshots
            # stay byte-identical.
            with self._fleet_lock:
                snap["fleet_kv_blocks"] = self._fleet_block_count
                snap["fleet_kv_hashes"] = list(self._fleet_recent)
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
        # Sessions still parked at close never resume: settle their
        # pins deliberately (teardown mid-workload is a drain, not a
        # leak), then assert nothing else is left outstanding.
        for req in self._parked:
            _resdbg.note_release("parked_kv", (id(self), id(req)))
        self._parked.clear()
        _resdbg.check_balanced("engine.close", kinds=("parked_kv",),
                               owner=self)
        if self._fleet is not None:
            # Drain the spill worker AFTER the engine thread is gone
            # (it was the only producer): every exported page either
            # lands in the store or is released — an in-flight tier
            # transition abandoned here is what kv_page_obj catches.
            self._spill_q.put(None)
            if (self._spill_thread.is_alive()
                    and self._spill_thread
                    is not threading.current_thread()):
                self._spill_thread.join(timeout=30.0)
            _resdbg.check_balanced("engine.close", kinds=("kv_page_obj",),
                                   owner=self)
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
        if self._parked:
            self._resume_tick()
        lent = self._hand_over()
        first = len(self._prefilling)
        self._run_admissions()
        for job in self._prefilling[first:]:
            if job.adm.slot in lent:
                self.metrics.record_ahead()
        if self.scheduler.queue_depth() and not self.kv.free_slots():
            # Slot-starved with waiters present: a strictly higher
            # priority class may preempt the lowest-priority active.
            if self._preempt_tick():
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
        if rec is None or self._parked:
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
            if (self._fleet is not None
                    and adm.cached_len < len(adm.request.prompt_ids) - 1):
                try:
                    self._fleet_extend(adm)
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

    # -------------------------------------------- priority preemption

    def _preempt_tick(self) -> bool:
        """Park the lowest-priority active request when a strictly
        higher-priority arrival is starved for a slot. The victim's
        slot recycles with its confirmed rows prefix-resident
        (scheduler.preempt), so the resume continuation re-prefills
        from cache — or pulls the pages back through the fleet spill
        tier once they're evicted (the export/install seam). Returns
        True when a slot was freed."""
        hp = self.scheduler.max_waiting_priority()
        if hp is None or not self.scheduler.active:
            return False
        # Victim: lowest class, newest arrival within it (LIFO — the
        # request with the least sunk decode work loses its slot).
        victim = min(self.scheduler.active,
                     key=lambda r: (r.priority, -r.arrival_t))
        if victim.priority >= hp:
            return False
        if self._inflight is not None:
            # Land the in-flight decode chunk BEFORE recycling a slot:
            # it was dispatched with the victim in its roster, and
            # _retire_chunk delivers a slot's tokens only to the
            # request the chunk was dispatched with while it still
            # holds the slot — parked first, the victim would lose
            # them (and prefill them again on resume). With nothing in
            # flight the next chunk is built from the host's values,
            # the preemptor's among them.
            prev, self._inflight = self._inflight, None
            if not self._retire_chunk(prev):
                return False
            if self.kv.free_slots():
                return True  # retirement finished someone: slot free
            if victim not in self.scheduler.active:
                victim = min(self.scheduler.active,
                             key=lambda r: (r.priority, -r.arrival_t))
                if victim.priority >= hp:
                    return False
        t0 = time.perf_counter()
        self.scheduler.preempt(victim)
        self._parked.append(victim)
        # RTPU_DEBUG_RES: a parked session pins scheduler + KV residency
        # until it resumes (or the engine closes) — an entry left behind
        # by a resume/close path is exactly the leak the witness flags.
        _resdbg.note_acquire("parked_kv", key=(id(self), id(victim)),
                             owner=self, note="preempt_park")
        self._preempts += 1
        if victim.trace_ctx is not None:
            self._span("engine.preempt_park", t0, time.perf_counter(),
                       victim, {"priority": victim.priority,
                                "generated": len(victim.generated),
                                "remaining": victim.remaining()})
        return True

    def _resume_tick(self) -> None:
        """Re-admit parked requests (highest priority first) while
        slots are free and no strictly higher-priority request is
        still waiting — a resume that would immediately be preempted
        again is thrash, not progress."""
        if not self.kv.free_slots():
            return
        self._parked.sort(key=lambda r: (-r.priority, r.arrival_t))
        waiting_hp = self.scheduler.max_waiting_priority()
        resumed: List[EngineRequest] = []
        for req in self._parked:
            if not self.kv.free_slots():
                break
            if waiting_hp is not None and waiting_hp > req.priority:
                break
            self._resume_one(req)
            resumed.append(req)
        for req in resumed:
            self._parked.remove(req)
            _resdbg.note_release("parked_kv", (id(self), id(req)))

    def _resume_one(self, orig: EngineRequest) -> None:
        """Resume a parked request as a CONTINUATION: a fresh request
        whose prompt is ``prompt + generated`` (greedy determinism
        makes the regenerated suffix token-identical) and whose budget
        is the remainder. The continuation shares the stream queue —
        tokens keep flowing on the original stream — and its result
        merges into the original future. Admission runs the normal
        path, so the parked rows come back as a prefix-cache hit or a
        fleet pull (the park/resume KV round-trip)."""
        t0 = time.perf_counter()
        cont = EngineRequest(
            list(orig.prompt_ids) + list(orig.generated),
            max_new_tokens=orig.remaining(),
            eos_id=orig.eos_id,
            stream_queue=orig.stream_queue,
            arrival_t=orig.arrival_t,
            trace_ctx=orig.trace_ctx,
            tenant=orig.tenant, priority=orig.priority)
        if self.spec_draft_len:
            cap = (self.loop.spec_chunk * (self.spec_draft_len + 1)) - 1
            cont.spec = SpecControl(
                allowance=self.spec_draft_len,
                max_allowance=cap if self.spec_adaptive
                else self.spec_draft_len)

        def _merge(fut, _orig=orig):
            try:
                r = fut.result()
            except BaseException as e:  # noqa: BLE001 — delivered upstream
                if not _orig.future.done():
                    _orig.future.set_exception(e)
                return
            out = dict(r)
            out["token_ids"] = list(_orig.generated) + list(r["token_ids"])
            out["num_generated"] = len(out["token_ids"])
            out["cached_prefix_len"] = _orig.cached_len
            out["preempted"] = out.get("preempted", 0) + 1
            if not _orig.future.done():
                _orig.future.set_result(out)

        cont.future.add_done_callback(_merge)
        self.scheduler.submit(cont)
        self._resumes += 1
        if orig.trace_ctx is not None:
            self._span("engine.preempt_resume", t0, time.perf_counter(),
                       orig, {"priority": orig.priority,
                              "resume_prompt": len(cont.prompt_ids),
                              "remaining": cont.max_new_tokens})

    # -------------------------------------------------- fleet KV tier

    def export_pages(self, slot: int, block_starts: List[int],
                     tag: str = "kv_export"):
        """THE KV page export path — the disagg handoff
        (_finish_handoff) and the spill tier (_spill_evicted) both go
        through here, so they cannot drift: one jitted program per
        page, ONE counted host sync for the whole batch, and the
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

    def _spill_evicted(self, slot: int, resident, chain,
                       keep_blocks: int) -> None:
        """kv_manager spill hook: an acquire is about to overwrite this
        slot's resident rows — export every COMPLETE block the page
        store doesn't already hold (HBM -> shm tier transition). The
        kept prefix (blocks < ``keep_blocks``) is exported too, not
        just the dying suffix: under affinity routing a hot prefix may
        NEVER be fully evicted on its home replica, and spilling it on
        first reuse is what makes it pullable by the rest of the fleet
        (and survivable past this replica's death) — the contains
        dedupe makes the steady-state cost zero. Runs on the engine
        thread before any row is written (the new admission's first
        prefill chunk dispatches strictly later), so the dynamic_slice
        snapshots are taken from live rows; the fetch-to-host is the
        batch's one counted sync (tag kv_spill) and serialization/puts
        happen on the spill worker."""
        from ray_tpu.serve.engine import kv_fleet as _kvf

        P = self.kv.block_size
        todo = []
        for i in range(min(len(chain), len(resident) // P)):
            oid = _kvf.page_object_id(self._fleet_ns, chain[i])
            if not self._fleet.contains(oid):
                todo.append((i, oid))
        if not todo:
            return
        req = getattr(self.kv, "current_request", None)
        t0 = time.perf_counter()
        pages_k, pages_v, crcs = self.export_pages(
            slot, [i * P for i, _ in todo], tag="kv_spill")
        jobs = []
        for (i, oid), k, v, crc in zip(todo, pages_k, pages_v, crcs):
            key = _resdbg.note_acquire("kv_page_obj", owner=self,
                                       note=f"spill block {i}")
            jobs.append((oid, tuple(resident[i * P:(i + 1) * P]),
                         tuple(chain[:i + 1]), k, v, crc, key))
        self._spill_q.put(jobs)
        if req is not None and req.trace_ctx is not None:
            self._span("engine.kv_spill", t0, time.perf_counter(), req,
                       {"blocks": len(todo), "slot": slot})

    def _spill_loop(self) -> None:
        """Spill worker: pack + store-put the exported pages. Pure host
        work on host arrays — no device access, so it needs no tick
        guard and never contends with the engine thread's dispatch."""
        from ray_tpu.serve.engine import kv_fleet as _kvf

        while True:
            jobs = self._spill_q.get()
            if jobs is None:
                return
            for oid, toks, ch, k, v, crc, key in jobs:
                try:
                    payload = _kvf.pack_page(toks, ch, k, v, crc)
                    if self._fleet.put(oid, payload):
                        with self._fleet_lock:
                            self._fleet_stats[
                                "kv_fleet_spilled_blocks"] += 1
                        self._note_fleet_hash(ch[-1])
                except Exception:  # rtpu-lint: disable=swallowed-exception — a failed put is a skipped optimization, never a veto
                    pass
                finally:
                    _resdbg.note_release("kv_page_obj", key)

    def _fleet_extend(self, adm) -> None:
        """Fleet lookup on a (partial) prefix-cache miss: walk the
        prompt's block chain depth by depth past the local hit, pull
        each resident page from the tier store, and install through the
        same install_page + chain/CRC-verify seam as the disagg handoff
        — then shrink the admission's prefill plan to the suffix.
        Longest-contiguous-resident-prefix wins; the walk stops at the
        first miss or rejected payload and never partially applies: a
        failure before commit leaves cached_len untouched and the
        suffix prefill overwrites any rows already written."""
        from ray_tpu.serve.engine import kv_fleet as _kvf

        req = adm.request
        plen = len(req.prompt_ids)
        P = self.kv.block_size
        want = chain_hashes(req.prompt_ids, P)
        max_d = min(len(want), (plen - 1) // P)
        d0 = adm.cached_len // P
        if max_d <= d0:
            return
        t0 = time.perf_counter()
        payloads = []
        for d in range(d0 + 1, max_d + 1):
            oid = _kvf.page_object_id(self._fleet_ns, want[d - 1])
            try:
                raw = self._fleet.get(oid)
            except Exception:  # rtpu-lint: disable=swallowed-exception — a store/pull error is a tier miss; the walk stops here
                raw = None
            if raw is None:
                break
            page = _kvf.unpack_page(raw)
            if (page is None
                    or page["chain"] != [int(h) for h in want[:d]]
                    or page["tokens"] != [
                        int(t) for t in
                        req.prompt_ids[(d - 1) * P:d * P]]):
                # Corrupt bytes (CRC/framing) or a chain-hash collision:
                # reject — recompute covers this depth and everything
                # past it, and the slot keeps its local state.
                with self._fleet_lock:
                    self._fleet_stats["kv_fleet_rejects"] += 1
                break
            payloads.append(page)
        run = len(payloads)
        # Same depth veto as scheduler.admissions: the bucket-padded
        # suffix prefill must still fit under max_len.
        while run > 0 and (adm.cached_len + run * P
                           + self.scheduler._prefill_rows(
                               plen - adm.cached_len - run * P)
                           > self.max_len):
            run -= 1
        if run <= 0 or run < self._fleet_gate():
            return
        keys = [_resdbg.note_acquire("kv_page_obj", owner=self,
                                     note="fleet pull")
                for _ in range(run)]
        try:
            # Pages are verified depth-by-depth but INSTALLED as one
            # contiguous run: install_page's update-slice is
            # polymorphic over the page-row dimension, so stacking the
            # run along the token axis writes all blocks in a single
            # dispatch (one program per run length) instead of one
            # dispatch per block — on small models the per-call
            # overhead of a per-block loop costs more than the prefill
            # it saves.
            k_run = np.concatenate(
                [p["k_page"] for p in payloads[:run]], axis=2)
            v_run = np.concatenate(
                [p["v_page"] for p in payloads[:run]], axis=2)
            self.cache = self.loop.install_page(
                self.cache, self._put(k_run), self._put(v_run),
                self._put(np.int32(adm.slot)),
                self._put(np.int32(d0 * P)))
            new_cached = adm.cached_len + run * P
            self.kv.commit_prefill(adm.slot, req.prompt_ids[:new_cached])
            got_chain = list(self.kv.slot_chain(adm.slot))
            if got_chain != [int(h) for h in want[:d0 + run]]:
                raise RuntimeError(
                    "KV chain mismatch after fleet install: the slot's "
                    "block hashes disagree with the pulled prefix's")
        finally:
            for key in keys:
                _resdbg.note_release("kv_page_obj", key)
        adm.cached_len = new_cached
        req.cached_len = new_cached
        suffix = plen - new_cached
        adm.chunks = self.scheduler.prefill_plan(suffix)
        adm.bucket = bucket_for(suffix, self.buckets)
        with self._fleet_lock:
            self._fleet_stats["kv_fleet_hits"] += 1
            self._fleet_stats["kv_fleet_pulled_blocks"] += run
            self._fleet_stats["kv_fleet_tokens_reused"] += run * P
        for j in range(run):
            self._note_fleet_hash(want[d0 + j])
        if req.trace_ctx is not None:
            self._span("engine.kv_fleet_pull", t0, time.perf_counter(),
                       req, {"blocks": run, "tokens": run * P,
                             "slot": adm.slot})

    def _note_fleet_hash(self, h: int) -> None:
        """Record a chain hash this replica can serve from the fleet
        tier (spilled or pulled) — the capped newest-first summary the
        load snapshot ships for the router's fleet term."""
        from ray_tpu.core.config import GLOBAL_CONFIG as cfg

        cap = max(1, cfg.serve_snapshot_fleet_hashes)
        with self._fleet_lock:
            if h not in self._fleet_recent:
                self._fleet_block_count += 1
            self._fleet_recent[h] = None
            self._fleet_recent.move_to_end(h)
            while len(self._fleet_recent) > cap:
                self._fleet_recent.popitem(last=False)

    def _note_prefill_cost(self, seconds: float,
                           suffix_tokens: int) -> None:
        """Recompute-side crossover input: EWMA of measured prefill
        milliseconds per block. The engine's first admission is
        excluded — it pays the bucket compiles, which are not a
        recompute cost."""
        self._fleet_pf_samples += 1
        if self._fleet_pf_samples == 1 or suffix_tokens <= 0:
            return
        ms_blk = seconds * 1e3 * self.kv.block_size / suffix_tokens
        prev = self._fleet_pf_ms_blk
        self._fleet_pf_ms_blk = (ms_blk if prev is None
                                 else 0.8 * prev + 0.2 * ms_blk)

    def _measure_fleet_costs(self):
        """Pull-side crossover inputs, measured at engine start: the
        per-page cost of a store roundtrip (put+get+decode of a
        real-shaped synthetic page) and the per-walk lookup cost
        (contains probe). Host-only — no device work, no compiles."""
        from ray_tpu.serve.engine import kv_fleet as _kvf

        P = self.kv.block_size
        page = np.zeros((self.cfg.n_layers, self.cfg.n_kv_heads, P,
                         self.cfg.head_dim), np.float32)
        crc = zlib.crc32(page.tobytes()) ^ zlib.crc32(page.tobytes())
        probe_hash = hash(("rtpu-kv-fleet-probe", id(self)))
        oid = _kvf.page_object_id(self._fleet_ns, probe_hash)
        payload = _kvf.pack_page([0] * P, [probe_hash], page, page, crc)
        pull_ms, lookup_ms = [], []
        try:
            for _ in range(5):
                self._fleet.delete(oid)
                t0 = time.perf_counter()
                self._fleet.put(oid, payload)
                raw = self._fleet.get(oid)
                if raw is not None:
                    _kvf.unpack_page(raw)
                pull_ms.append((time.perf_counter() - t0) * 1e3)
                t0 = time.perf_counter()
                self._fleet.contains(oid)
                lookup_ms.append((time.perf_counter() - t0) * 1e3)
        except Exception:  # rtpu-lint: disable=swallowed-exception — an unprobeable store just disables the measured crossover
            return None, None
        finally:
            try:
                self._fleet.delete(oid)
            except Exception:  # rtpu-lint: disable=swallowed-exception — best-effort probe-object cleanup
                pass
        if not pull_ms:
            return None, None
        return min(pull_ms), min(lookup_ms)

    def _crossover_blocks(self) -> Optional[int]:
        """Measured pull-vs-recompute crossover: the contiguous run
        length (blocks) past which pulling beats recomputing. Pulling d
        blocks costs ~lookup + d*pull_page; recomputing them rides the
        suffix prefill at ~d*prefill_block. None until the recompute
        side has a sample; -1 when pulling never pays off."""
        pf, pull = self._fleet_pf_ms_blk, self._fleet_pull_ms_page
        if pf is None or pull is None:
            return None
        margin = pf - pull
        if margin <= 0:
            return -1
        return max(1, math.ceil((self._fleet_lookup_ms or 0.0) / margin))

    def _fleet_gate(self) -> int:
        """Effective minimum pullable run: the knob when explicit, the
        measured crossover when 'auto' (optimistic single-block pulls
        until the recompute side has a sample)."""
        g = self._fleet_min_blocks
        if isinstance(g, int):
            return max(0, g)
        co = self._crossover_blocks()
        if co is None:
            return 1
        if co < 0:
            return 1 << 30
        return co

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
            if self._fleet is not None:
                self._note_prefill_cost(prefill_s,
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
                self._finish_handoff(req)
                return
            self.scheduler.activate(req)
            self._maybe_finish(req, first)

    def _finish_handoff(self, req: EngineRequest) -> None:
        """Prefill role: resolve the request with a KV handoff payload
        (or a completed result when the first token already ends it)
        and recycle the slot — seeding the prefill-side prefix cache
        with the full prompt, so repeat-prefix traffic keeps its reuse
        win on the prefill pool."""
        slot = req.slot
        plen = len(req.prompt_ids)
        first = req.generated[-1]
        done = (len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and first == req.eos_id)
                or plen + 1 >= self.max_len)
        result: Dict[str, Any]
        if done:
            result = {"token_ids": list(req.generated),
                      "num_generated": len(req.generated),
                      "cached_prefix_len": req.cached_len}
        else:
            P = self.kv.block_size
            # Shared export path (export_pages): one program per page,
            # ONE host sync for the batch, tagged kv_export so the
            # RTPU_DEBUG_JAX witness attributes it separately from the
            # counted prefill sync.
            pages_k, pages_v, crcs = self.export_pages(
                slot, [p * P for p in range(-(-plen // P))],
                tag="kv_export")
            result = {
                "kv_handoff": True,
                "prompt_ids": list(req.prompt_ids),
                "first_token": int(first),
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "page": P,
                "rows": plen,
                "pages_k": pages_k,
                "pages_v": pages_v,
                # Content integrity: the chain hashes cover TOKEN
                # identity (both sides derive them from prompt_ids);
                # these cover the page BYTES, so a transport/export bug
                # that mangles KV data fails the install instead of
                # decoding garbage.
                "page_crc": crcs,
                "chain": list(self.kv.slot_chain(slot)),
                "cached_prefix_len": req.cached_len,
            }
            if req.tenant or req.priority:
                # QoS attribution survives the handoff: the decode-role
                # engine schedules the installed request in the same
                # class the prefill side admitted it in.
                result["tenant"] = req.tenant
                result["priority"] = req.priority
        self.kv.release(slot, resident_tokens=req.prompt_ids)
        req.slot = -1
        if not req.future.done():
            req.future.set_result(result)
        if req.stream_queue is not None and done:
            req.stream_queue.put(("done", None))
        if req.trace_ctx is not None:
            _tracing.flush()

    def _install_tick(self) -> None:
        """Decode role: install queued KV handoffs into free slots,
        FIFO. A job that races slot exhaustion waits (installs never
        jump the line — later handoffs can't acquire either)."""
        while True:
            try:
                self._install_waiting.append(
                    self._install_queue.get_nowait())
            except queue.Empty:
                break
        pending = self._install_waiting
        self._install_waiting = []
        for i, (req, payload) in enumerate(pending):
            if not self.kv.free_slots():
                self._install_waiting.extend(pending[i:])
                return
            try:
                self._install_one(req, payload)
            except BaseException as e:  # noqa: BLE001 — one bad handoff
                # must not kill the engine thread
                self._recover_cache(e)
                self._deliver_error([req], e)

    def _install_one(self, req: EngineRequest,
                     payload: Dict[str, Any]) -> None:
        # fit vetoes every reuse depth: the handoff's pages OVERWRITE
        # the slot's rows wholesale, so counting a resident-prefix
        # "hit" here would pollute the prefix-cache stats with reuse
        # that never happens.
        self.kv.current_request = req
        try:
            got = self.kv.acquire(req.prompt_ids, fit=lambda c: False)
        finally:
            self.kv.current_request = None
        if got is None:
            raise RuntimeError("no free slot for KV install")
        slot, _cached = got
        P = int(payload["page"])
        try:
            crcs = payload.get("page_crc")
            for i, (kp, vp) in enumerate(zip(payload["pages_k"],
                                             payload["pages_v"])):
                if crcs is not None:
                    import zlib

                    got_crc = (zlib.crc32(np.ascontiguousarray(kp)
                                          .tobytes())
                               ^ zlib.crc32(np.ascontiguousarray(vp)
                                            .tobytes()))
                    if got_crc != crcs[i]:
                        raise RuntimeError(
                            f"KV page {i} checksum mismatch: the page "
                            "bytes were corrupted in transit")
                self.cache = self.loop.install_page(
                    self.cache, self._put(kp), self._put(vp),
                    self._put(np.int32(slot)),
                    self._put(np.int32(i * P)))
            self.kv.commit_prefill(slot, req.prompt_ids)
            # Chain equality covers TOKEN/protocol identity (same
            # prompt, same block algorithm/size); the per-page CRCs
            # above cover the KV BYTES themselves.
            chain = list(self.kv.slot_chain(slot))
            want = payload.get("chain")
            if want is not None and chain != list(want):
                raise RuntimeError(
                    "KV chain mismatch after install: the decode side's "
                    "block hashes disagree with the prefill side's")
        except BaseException:
            self.kv.release(slot, resident_tokens=())
            raise
        req.slot = slot
        req.first_token_t = time.perf_counter()
        self.scheduler.activate(req)
        self._maybe_finish(req, req.generated[-1])

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
        if self.drafter is not None:
            with self._tick.phase("decode_dispatch", drafting=True):
                drafts = self._draft_for_roster()
            if drafts:
                self._spec_tick(drafts)
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
        return (self.kv.free_slots() > 0 and not self._parked
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

    # -------------------------------------------------------- speculation

    def _draft_for_roster(self) -> Dict[int, List[int]]:
        """Prompt-lookup proposals for this tick, keyed by slot.
        Empty dict = nothing to verify (dispatch the plain program)."""
        # A fully accepted window advances W = K+1 positions (K drafts
        # + the model's bonus token), so a continuation long enough to
        # keep all spec_chunk windows fed spans C*W - 1 positions (the
        # final window needs no bonus prediction).
        cap = self.loop.spec_chunk * (self.spec_draft_len + 1) - 1
        out: Dict[int, List[int]] = {}
        for req in self.scheduler.active:
            # Drafting past the request's own stopping point is pure
            # waste: at most remaining-1 drafts can be emitted (the last
            # budgeted token is always the model's own), and the row cap
            # freezes the slot at max_len-1 rows.
            need = min(req.spec.budget(), cap, req.remaining() - 1,
                       self.max_len - req.length - 2)
            if need <= 0:
                continue
            cont = self.drafter.draft(req.prompt_ids + req.generated,
                                      need)
            if cont:
                out[req.slot] = cont
            else:
                req.spec.miss()
        return out

    def _spec_tick(self, drafts: Dict[int, List[int]]) -> None:
        """One speculative verify chunk: K-token draft windows verified
        on device, accepted prefixes committed, rejected rows rolled
        back — still ONE host fetch."""
        active = self.scheduler.active
        C, K = self.loop.spec_chunk, self.spec_draft_len
        W = K + 1
        try:
            with self._tick.phase("decode_dispatch", slots=len(active),
                                  spec=True):
                t0 = self._tick.now
                tokens, lengths, remaining, eos_ids, done = \
                    self._roster_arrays(active)
                draft_buf = np.zeros((self.max_batch, C, K), np.int32)
                ndraft = np.zeros((self.max_batch,), np.int32)
                for slot, cont in drafts.items():
                    # Window rows are packed at stride W = K+1, not K:
                    # the only path to row i is i FULLY accepted
                    # windows, and each full window advances K+1
                    # positions (K drafts + the model's bonus token).
                    # The continuation's prediction for a bonus position
                    # is skipped — the bonus comes from the model's own
                    # argmax, so drafting it would desynchronize every
                    # later row by one position per window (systematic
                    # row-1+ rejection on any repetition with period
                    # > 1).
                    packed = 0
                    for i in range(C):
                        row = cont[i * (K + 1):i * (K + 1) + K]
                        if not row:
                            break
                        draft_buf[slot, i, :len(row)] = row
                        packed += len(row)
                    ndraft[slot] = packed
                for req in active:
                    self.kv.begin_speculation(
                        req.slot, min(C * W, self.max_len - req.length))
                emits_d, counts_d, _len_d, _done_d, self.cache = \
                    self.loop.verify_chunk(
                        self.params, self.cache, self._put(tokens),
                        self._put(draft_buf), self._put(ndraft),
                        self._put(lengths), self._put(remaining),
                        self._put(eos_ids), self._put(done))
                program = self._devq.put("chunk", t0, counts_d.is_ready,
                                         slots=len(active), spec=True)
            with self._tick.phase("decode_fetch",
                                  slots=len(active)) as attrs:
                # device_get returns host ndarrays: [B,C,W] + [B,C].
                emits, counts = self._fetch((emits_d, counts_d))
                attrs["bytes"] = emits.nbytes + counts.nbytes
        except BaseException as e:  # noqa: BLE001 — fail all waiters
            self._fail_roster(e)
            return
        now = self._tick.now
        self._devq.seen(program, now)
        live_steps = len(active) * C * W  # token-positions scanned
        delivered = 0
        accepted_total = 0
        with self._tick.phase("decode_deliver", slots=len(active),
                              spec=True) as attrs:
            for req in list(active):
                s = req.slot
                n = int(counts[s].sum())
                # Commit the verified rows, roll back the reservation
                # for the rejected remainder BEFORE delivery:
                # _maybe_finish may release the slot, and a released
                # slot must carry no in-flight reservation into the
                # free pool.
                self.kv.commit_speculation(s, n)
                delivered += n
                req_accepted = int(np.maximum(counts[s] - 1, 0).sum())
                accepted_total += req_accepted
                if req.trace_ctx is not None and n:
                    self._span("engine.decode_chunk", t0, now, req,
                               {"tokens": n, "slot": s, "spec": True,
                                "spec_accepted": req_accepted,
                                "drafted": int(ndraft[s])})
                finished = False
                for i in range(C):
                    for j in range(int(counts[s, i])):
                        tok = int(emits[s, i, j])
                        req.length += 1
                        req.generated.append(tok)
                        if req.stream_queue is not None:
                            req.stream_queue.put(("token", tok))
                        if self._maybe_finish(req, tok):
                            finished = True
                            break
                    if finished:
                        break
                if (self.spec_adaptive and not finished
                        and s in drafts):
                    consumed, acc = self._spec_outcome(
                        counts[s], int(ndraft[s]), K, W)
                    if consumed:
                        req.spec.observe(consumed, acc)
            attrs["tokens"] = delivered
            self.metrics.record_chunk(delivered, live_steps, now - t0)
            self.metrics.record_spec(int(ndraft.sum()), accepted_total)
            _flight.record("engine_tick", tok=delivered, act=len(active),
                           spec=True)

    @staticmethod
    def _spec_outcome(counts_row, drafted: int, K: int, W: int):
        """(verified, accepted) draft tokens for one non-finished slot's
        chunk — the adaptive controller's signal. Only drafts the device
        actually checked count as verified: a request that finished
        mid-chunk never reaches here (its unchecked tail is neither
        accepted nor rejected), and windows after a divergence run
        draft-free, consuming nothing."""
        consumed = accepted = 0
        nd_rem = drafted
        for m in (int(x) for x in counts_row):
            if m == 0:
                break
            k_i = min(nd_rem, K)
            if m == W:  # full window: all K drafts accepted
                consumed += k_i
                accepted += k_i
                nd_rem -= k_i
            else:
                consumed += k_i
                accepted += m - 1
                nd_rem = 0
        return consumed, accepted

    def _engine_loop(self) -> None:
        tick = self._tick
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
                if self.role == "decode":
                    with tick.phase("install"):
                        self._install_tick()
                landing = self._prefill_tick()
            self.metrics.record_depths(self.scheduler.queue_depth(),
                                       len(self.scheduler.active),
                                       self.kv.hit_rate())
            if (not self.scheduler.active and not landing
                    and not self.scheduler.ending):
                if self._prefilling or self._install_waiting:
                    continue  # keep chunked prefills / installs advancing
                # A burst just drained: the multi-step trailing chunk
                # (dispatched while every member was already frozen on
                # device) delivers nothing by construction — drop it
                # unfetched. Its cache output already landed at
                # dispatch time.
                self._inflight = None
                if (self.role == "decode"
                        and not self._install_queue.empty()):
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
