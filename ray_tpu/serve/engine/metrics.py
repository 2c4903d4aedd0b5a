"""Per-engine serving metrics: TTFT/TPOT/queue-depth/prefix-hit-rate.

Two surfaces, one source of truth:

- the process-global Prometheus registry (util/metrics.py) gets the
  engine-labelled counters/histograms/gauges — they ride the existing
  head-KV publication path, so ``util.state.cluster_metrics()`` and the
  dashboard see serving health with zero new plumbing;
- ``EngineMetrics.snapshot()`` feeds the engine's ``stats()`` surface
  (and the bench rows) with plain floats.

Histogram boundaries are latency-shaped (seconds): TTFT spans prefill
compiles (first request pays XLA), TPOT sits in the ms range.

`TickClock` times the engine thread itself: always-on seconds per tick
phase in the snapshot, and — while tracing is on — the same stamps as
``engine.tick.<phase>`` spans and profiler annotations (one set of
clock reads, two outputs; engine/README.md "Tick phases").
`DeviceQueue` keeps, on those stamps, the programs the thread has put on
the device and not yet seen end: what each waited behind, what it took
itself, and when the device ran dry (engine/README.md "The device's
queue").
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, Optional, Tuple

from ray_tpu.util import metrics as _m
from ray_tpu.util import tracing as _tracing

_ENGINE_SEQ = itertools.count()

# Registry metrics are process-global and engine-labelled; module import
# creates them once (util/metrics.py registers by name).
TTFT_SECONDS = _m.Histogram(
    "rtpu_llm_ttft_seconds", "time to first generated token",
    boundaries=[0.001, 0.005, 0.02, 0.1, 0.5, 2, 10, 60])
TPOT_SECONDS = _m.Histogram(
    "rtpu_llm_tpot_seconds", "per-output-token decode time",
    boundaries=[0.0005, 0.002, 0.01, 0.05, 0.2, 1])
QUEUE_DEPTH = _m.Gauge("rtpu_llm_queue_depth",
                       "requests waiting for a slot")
ACTIVE_SLOTS = _m.Gauge("rtpu_llm_active_slots",
                        "slots decoding this tick")
PREFIX_HIT_RATE = _m.Gauge("rtpu_llm_prefix_hit_rate",
                           "prefix-cache hit rate since engine start")
REQUESTS_TOTAL = _m.Counter("rtpu_llm_requests_total",
                            "generation requests accepted")
TOKENS_TOTAL = _m.Counter("rtpu_llm_tokens_generated_total",
                          "tokens returned to callers")
PREFILL_TOKENS_TOTAL = _m.Counter(
    "rtpu_llm_prefill_tokens_total",
    "prompt tokens run through prefill (bucket-padded tokens excluded)")
PREFIX_REUSED_TOTAL = _m.Counter(
    "rtpu_llm_prefix_tokens_reused_total",
    "prompt tokens served from the prefix cache instead of prefill")
HOST_SYNCS_TOTAL = _m.Counter(
    "rtpu_llm_decode_host_syncs_total",
    "device->host fetches issued by the decode loop (one per chunk)")
ADMISSIONS_HEARD_TOTAL = _m.Counter(
    "rtpu_llm_admissions_heard_total",
    "admissions whose first prefill chunk was dispatched from the tick's "
    "listening wait, behind the decode chunk in flight")
ADMISSIONS_AHEAD_TOTAL = _m.Counter(
    "rtpu_llm_admissions_ahead_total",
    "admissions into a slot whose last holder, sure to end inside the "
    "decode chunk in flight, let go of it ahead of that chunk's retire")
DEVICE_DRY_DISPATCHES_TOTAL = _m.Counter(
    "rtpu_llm_device_dry_dispatches_total",
    "programs dispatched to a device the engine thread knew had run "
    "dry: every program ahead had been seen to end")
SPEC_DRAFTED_TOTAL = _m.Counter(
    "rtpu_llm_spec_drafted_total",
    "draft tokens proposed by prompt-lookup speculation")
SPEC_ACCEPTED_TOTAL = _m.Counter(
    "rtpu_llm_spec_accepted_total",
    "draft tokens accepted by the device verify step")
SPEC_ACCEPT_RATE = _m.Gauge(
    "rtpu_llm_spec_accept_rate",
    "accepted/drafted ratio since engine start")
SPEC_CHUNKS_TOTAL = _m.Counter(
    "rtpu_llm_spec_chunks_total",
    "decode chunks dispatched through the speculative verify program")
# TTFT decomposition (labels: component=queue|route|prefill|deliver) —
# the serve-path breakdown the router/SLO PRs are judged on: `queue` is
# the engine-side wait from arrival to the first prefill dispatch,
# `route` the handle-side replica choice, `prefill` first dispatch to
# the first token on the host (the device prefill, whatever the device
# had queued before it, and the logits fetch), `deliver` the first
# token's time on the stream queue until the consumer's thread takes
# it. Fed by api.DeploymentHandle (route), the engine's admission path
# (queue/prefill) and generate_stream (deliver); always on. Boundaries
# resolve 50 ms to 1.5 s, where a loaded replica's components lie.
SERVE_TTFT_BREAKDOWN_MS = _m.Histogram(
    "rtpu_serve_ttft_breakdown_ms",
    "TTFT component breakdown in milliseconds (component label)",
    boundaries=[1, 2, 5, 10, 20, 50, 100, 150, 200, 300, 400, 500, 750,
                1000, 1500, 2500, 5000, 10000])

# The engine thread's time, cut into phases (engine/README.md "Tick
# phases"; `install` is the decode role's own). Counter keys are
# `tick_<phase>_s`, span and annotation names `engine.tick.<phase>`.
TICK_PHASES = ("admit", "install", "prefill_dispatch", "prefill_fetch",
               "prefill_deliver", "decode_dispatch", "decode_fetch",
               "decode_deliver", "idle")


class EngineMetrics:
    """One engine's counters; thread-safe enough for engine-thread writes
    + caller-thread snapshot reads (all updates hold ``_lock``)."""

    def __init__(self, name: Optional[str] = None,
                 counter_maxes: Tuple[str, ...] = ()):
        self.name = name or f"engine-{next(_ENGINE_SEQ)}"
        # The model's counters of which the largest is kept, not the
        # sum (the family's ``COUNTER_MAXES``).
        self._counter_maxes = frozenset(counter_maxes)
        self._labels = {"engine": self.name}
        self._lock = threading.Lock()
        self.requests = 0
        self.tokens_generated = 0
        self.prefill_tokens = 0
        self.host_syncs = 0        # decode-loop device fetches
        self.decode_steps = 0      # live slot-steps advanced on device
        # Plain decode chunks dispatched, and how many of them went out
        # AHEAD of the previous chunk's fetch (the pipelined schedule).
        # One writer, the engine thread; no lock (like ``tick_s``).
        self.chunks_dispatched = 0
        self.chunks_carried = 0
        # The listening wait (core.py ``_listen``): admissions whose
        # first prefill chunk it dispatched (against ``requests``).
        self.admissions_heard = 0
        # The early hand-over (core.py ``_hand_over``): admissions into
        # a slot whose last holder was still in the chunk in flight
        # (against ``requests``).
        self.admissions_ahead = 0
        # Prefill programs dispatched and the REAL tokens they carried
        # (``prefill_tokens`` counts a whole prompt, at its last chunk).
        self.prefill_chunks_dispatched = 0
        self.prefill_chunk_tokens = 0
        # ... and those of them that carried TWO admissions (core.py
        # ``_partner``; against the admissions, two a pair).
        self.prefill_pairs = 0
        self.spec_drafted = 0      # draft tokens proposed
        self.spec_accepted = 0     # draft tokens verified + accepted
        self.spec_chunks = 0       # chunks through the verify program
        self._ttfts = collections.deque(maxlen=256)   # seconds
        self._tpots = collections.deque(maxlen=1024)  # seconds/token
        # EWMA TTFT (alpha 0.3): the load-snapshot freshness signal —
        # a single float the router can compare across replicas without
        # shipping the whole window.
        self._ewma_ttft_s: Optional[float] = None
        # TTFT, decomposed: sums over `requests` (queue + prefill, the
        # two halves of every TTFT record_admit sees) and over `streams`
        # (the serve front's share, written by the consumer's thread).
        self.queue_wait_s = 0.0
        self.prefill_wait_s = 0.0
        self.first_deliver_s = 0.0
        self.streams = 0
        # Bytes the prefill syncs fetched, over `requests`.
        self.prefill_fetch_bytes = 0
        # Tick phases: seconds per phase, the loop's wall seconds and
        # its iterations. Written by the engine thread alone (TickClock)
        # without the lock; a snapshot reads each float whole.
        # What the model's own programs count (a routed family's expert
        # counters), by the names they give, summed as fetched.
        self.model_counters: Dict[str, float] = {}
        self.tick_s = dict.fromkeys(TICK_PHASES, 0.0)
        self.tick_loop_s = 0.0
        self.ticks = 0
        # What `DeviceQueue` and the retire read off the device's queue
        # (engine/README.md "The device's queue" has each key). One
        # writer, the engine thread; no lock (like ``tick_s``).
        self.device_queue: Dict[str, Any] = {
            "prefill_behind_s": 0.0, "prefill_own_s": 0.0,
            "prefill_split": 0,
            "prefill_ahead_chunks": 0, "prefill_ahead_prefills": 0,
            "chunk_period_s": 0.0, "chunk_steps_retired": 0,
            "chunk_own_s": 0.0, "chunk_steps_timed": 0,
            "device_dry_s": 0.0, "device_dry_dispatches": 0,
            "decode_steps_frozen": 0}

    # ------------------------------------------------------------ records

    def record_admit(self, queue_s: float, prefill_s: float,
                     prefill_tokens: int, reused_tokens: int) -> None:
        """One admission reached its first token: ``queue_s`` from
        arrival to the first prefill dispatch, ``prefill_s`` from there
        to the first token on the host. TTFT is their sum."""
        ttft_s = queue_s + prefill_s
        with self._lock:
            self.requests += 1
            self.queue_wait_s += queue_s
            self.prefill_wait_s += prefill_s
            self.prefill_tokens += prefill_tokens
            self.tokens_generated += 1  # prefill yields the first token
            self._ttfts.append(ttft_s)
            self._ewma_ttft_s = (ttft_s if self._ewma_ttft_s is None
                                 else 0.3 * ttft_s
                                 + 0.7 * self._ewma_ttft_s)
        REQUESTS_TOTAL.inc(labels=self._labels)
        TOKENS_TOTAL.inc(labels=self._labels)
        TTFT_SECONDS.observe(ttft_s, labels=self._labels)
        PREFILL_TOKENS_TOTAL.inc(prefill_tokens, labels=self._labels)
        if reused_tokens:
            PREFIX_REUSED_TOTAL.inc(reused_tokens, labels=self._labels)
        SERVE_TTFT_BREAKDOWN_MS.observe(queue_s * 1e3,
                                        labels={"component": "queue"})
        SERVE_TTFT_BREAKDOWN_MS.observe(prefill_s * 1e3,
                                        labels={"component": "prefill"})

    def record_prefill_fetch(self, nbytes: int) -> None:
        """One admission's counted prefill sync brought ``nbytes`` to
        the host: the first token and the family's counters."""
        with self._lock:
            self.prefill_fetch_bytes += nbytes

    def record_first_deliver(self, seconds: float) -> None:
        """One stream's first token left the stream queue ``seconds``
        after the engine thread put it there. Called on the CONSUMER's
        thread (generate_stream), never on the tick."""
        with self._lock:
            self.streams += 1
            self.first_deliver_s += seconds
        SERVE_TTFT_BREAKDOWN_MS.observe(seconds * 1e3,
                                        labels={"component": "deliver"})

    def record_chunk(self, tokens: int, live_steps: int,
                     elapsed_s: float) -> None:
        """One decode-loop dispatch+fetch: ``tokens`` delivered to
        callers, ``live_steps`` device steps across live slots."""
        with self._lock:
            self.host_syncs += 1
            self.tokens_generated += tokens
            self.decode_steps += live_steps
            if tokens:
                self._tpots.append(elapsed_s / tokens)
        HOST_SYNCS_TOTAL.inc(labels=self._labels)
        if tokens:
            TOKENS_TOTAL.inc(tokens, labels=self._labels)
            TPOT_SECONDS.observe(elapsed_s / tokens, labels=self._labels)

    def record_dispatch(self, carried: bool) -> None:
        """One plain decode chunk enqueued; ``carried``: while the
        previous one was still unfetched, its inputs merged on the
        device from that chunk's carry."""
        self.chunks_dispatched += 1
        self.chunks_carried += carried

    def record_heard(self) -> None:
        """One admission made from the listening wait."""
        self.admissions_heard += 1
        ADMISSIONS_HEARD_TOTAL.inc(labels=self._labels)

    def record_ahead(self) -> None:
        """One admission into a slot handed over ahead."""
        self.admissions_ahead += 1
        ADMISSIONS_AHEAD_TOTAL.inc(labels=self._labels)

    def record_dry_dispatch(self, dry_s: float) -> None:
        """One program dispatched to a device known to have run dry,
        ``dry_s`` seconds before (a lower bound)."""
        self.device_queue["device_dry_dispatches"] += 1
        self.device_queue["device_dry_s"] += dry_s
        DEVICE_DRY_DISPATCHES_TOTAL.inc(labels=self._labels)

    def record_first_dispatch(self, ahead_chunks: int,
                              ahead_prefills: int) -> None:
        """One admission's first prefill program went out behind that
        many programs the thread had not yet seen end."""
        self.device_queue["prefill_ahead_chunks"] += ahead_chunks
        self.device_queue["prefill_ahead_prefills"] += ahead_prefills

    def record_prefill_split(self, behind_s: float, own_s: float) -> None:
        """One admission ALL of whose prefill programs had their start
        and end seen: the seconds they waited on the device behind
        other programs, and the seconds they took themselves."""
        q = self.device_queue
        q["prefill_split"] += 1
        q["prefill_behind_s"] += behind_s
        q["prefill_own_s"] += own_s

    def record_retire(self, steps: int, period_s: float, frozen: int,
                      own_s: Optional[float]) -> None:
        """One plain chunk of ``steps`` steps retired ``period_s`` after
        the last one (the cadence a roster member feels); ``frozen`` of
        its slot-steps were scanned for a request that had ended inside
        it; ``own_s`` its device seconds, where the queue saw both its
        ends."""
        q = self.device_queue
        q["chunk_steps_retired"] += steps
        q["chunk_period_s"] += period_s
        q["decode_steps_frozen"] += frozen
        if own_s is not None:
            q["chunk_steps_timed"] += steps
            q["chunk_own_s"] += own_s

    def record_prefill_chunk(self, tokens: int) -> None:
        """One prefill program enqueued, carrying ``tokens`` real
        prompt tokens (bucket padding left out; a pair's program
        carries both prompts')."""
        self.prefill_chunks_dispatched += 1
        self.prefill_chunk_tokens += tokens

    def record_prefill_pair(self) -> None:
        """That program carried two admissions."""
        self.prefill_pairs += 1

    def record_model_counters(self, counters) -> None:
        """What a prefill or a chunk's steps counted on the device: a
        list holding one dict of named scalars, or nothing (a family
        without counters). They came with a fetch the tick makes
        anyway. Summed over the engine's life, but for the names the
        family lists as ``COUNTER_MAXES``: of those the largest."""
        with self._lock:
            for fetched in counters:
                for name, value in fetched.items():
                    kept, new = self.model_counters.get(name, 0), value.item()
                    self.model_counters[name] = (
                        max(kept, new) if name in self._counter_maxes
                        else kept + new)

    def record_spec(self, drafted: int, accepted: int) -> None:
        """One speculative verify chunk: ``drafted`` tokens proposed
        across the roster, ``accepted`` of them verified correct."""
        with self._lock:
            self.spec_chunks += 1
            self.spec_drafted += drafted
            self.spec_accepted += accepted
            rate = (self.spec_accepted / self.spec_drafted
                    if self.spec_drafted else 0.0)
        SPEC_CHUNKS_TOTAL.inc(labels=self._labels)
        if drafted:
            SPEC_DRAFTED_TOTAL.inc(drafted, labels=self._labels)
        if accepted:
            SPEC_ACCEPTED_TOTAL.inc(accepted, labels=self._labels)
        SPEC_ACCEPT_RATE.set(rate, labels=self._labels)

    def record_depths(self, queue_depth: int, active: int,
                      prefix_hit_rate: float) -> None:
        QUEUE_DEPTH.set(queue_depth, labels=self._labels)
        ACTIVE_SLOTS.set(active, labels=self._labels)
        PREFIX_HIT_RATE.set(prefix_hit_rate, labels=self._labels)

    # ----------------------------------------------------------- snapshot

    @staticmethod
    def _p50(values) -> float:
        vals = sorted(values)
        return vals[len(vals) // 2] if vals else 0.0

    def snapshot(self) -> Dict[str, Any]:
        tick = {f"tick_{k}_s": v for k, v in self.tick_s.items()}
        tick.update(self.device_queue, tick_loop_s=self.tick_loop_s,
                    ticks=self.ticks)
        with self._lock:
            return {
                **tick,
                **self.model_counters,
                "queue_wait_s": self.queue_wait_s,
                "prefill_wait_s": self.prefill_wait_s,
                "prefill_fetch_bytes": self.prefill_fetch_bytes,
                "first_deliver_s": self.first_deliver_s,
                "streams": self.streams,
                "engine": self.name,
                "requests": self.requests,
                "tokens_generated": self.tokens_generated,
                "prefill_tokens": self.prefill_tokens,
                "decode_host_syncs": self.host_syncs,
                "decode_steps": self.decode_steps,
                "decode_chunks_dispatched": self.chunks_dispatched,
                "decode_chunks_carried": self.chunks_carried,
                "admissions_heard": self.admissions_heard,
                "admissions_ahead": self.admissions_ahead,
                "prefill_chunks_dispatched": self.prefill_chunks_dispatched,
                "prefill_chunk_tokens": self.prefill_chunk_tokens,
                "prefill_pairs": self.prefill_pairs,
                # decode tokens delivered per device token-position
                # scanned (first tokens come from prefill, so they're
                # excluded): < 1.0 when slots freeze mid-chunk or
                # drafted window positions get rejected; 1.0 = every
                # scanned position produced a delivered token.
                "decode_utilization": round(
                    (self.tokens_generated - self.requests)
                    / self.decode_steps, 4) if self.decode_steps else 0.0,
                "spec_chunks": self.spec_chunks,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
                "spec_accept_rate": round(
                    self.spec_accepted / self.spec_drafted, 4)
                    if self.spec_drafted else 0.0,
                "ttft_ms_p50": round(self._p50(self._ttfts) * 1e3, 3),
                "ttft_ms_ewma": round((self._ewma_ttft_s or 0.0) * 1e3, 3),
                "tpot_ms_p50": round(self._p50(self._tpots) * 1e3, 3),
            }


class _Phase:
    """One `with` block of a `TickClock`; yields its ``attrs``."""

    __slots__ = ("_clock", "name", "attrs")

    def __init__(self, clock: "TickClock", name: str, attrs: dict):
        self._clock, self.name, self.attrs = clock, name, attrs

    def __enter__(self) -> dict:
        self._clock._open(self)
        return self.attrs

    def __exit__(self, *exc) -> None:
        self._clock._close()


class TickClock:
    """The engine thread's time, cut into `TICK_PHASES`: one
    `time.perf_counter()` read per phase boundary, two outputs.

    Always: the seconds between two boundaries are added to
    ``EngineMetrics.tick_s[phase]``. For a phase that begins while
    `tracing.enabled()`: the same two stamps become one
    ``engine.tick.<phase>`` span (parent: this engine's ``serve.engine``
    root, made at the first traced boundary) and bracket a same-named
    profiler annotation, so a device profile shows the phases on the
    host plane with no alignment step.

    The thread is in at most one phase at a time: a phase opened inside
    another (a preemption that lands the chunk in flight during
    ``admit``) suspends the outer one, which resumes as a new span when
    the inner one closes — spans of one engine never overlap. ``now``
    is the last boundary's stamp, for per-request spans that share it.
    Engine-thread-only; ``annotation`` is `jax.profiler.TraceAnnotation`
    (handed in: `util/tracing` and this module import no jax).
    """

    def __init__(self, metrics: EngineMetrics, annotation):
        self._metrics = metrics
        self._annotation = annotation
        self._open_phases: list = []      # innermost last
        # The running phase's span record and annotation; None while
        # tracing is off (decided where the phase starts).
        self._span: Optional[Dict[str, Any]] = None
        self._twin = None
        self._root: Optional[Dict[str, str]] = None
        self.now = time.perf_counter()
        self._lap_t = self.now

    def phase(self, name: str, **attrs) -> _Phase:
        return _Phase(self, name, attrs)

    def lap(self) -> None:
        """Top of a loop iteration: the loop's wall seconds so far."""
        t = time.perf_counter()
        self._metrics.tick_loop_s += t - self._lap_t
        self._metrics.ticks += 1
        self._lap_t = t

    def _open(self, phase: _Phase) -> None:
        t = time.perf_counter()
        if self._open_phases:
            self._stop(t)
        self._open_phases.append(phase)
        self._start(t)

    def _close(self) -> None:
        t = time.perf_counter()
        self._stop(t)
        self._open_phases.pop()
        if self._open_phases:
            self._start(t)

    def _start(self, t: float) -> None:
        self.now = t
        if not _tracing.enabled():
            return
        wall = _tracing.wall(t)
        name = "engine.tick." + self._open_phases[-1].name
        self._span = _tracing.start_span(name, parent=self.root(wall),
                                         start=wall)
        self._twin = self._annotation(name)
        self._twin.__enter__()

    def root(self, wall: float) -> Optional[Dict[str, str]]:
        """This engine's ``serve.engine`` root span, made at ``wall``
        by whoever first asks while tracing is on."""
        if self._root is None:
            # parent={}: a trace of its own, whatever span the thread
            # that first ticks traced happens to be under.
            self._root = _tracing.emit_span(
                "serve.engine", wall, wall, parent={},
                attrs={"engine": self._metrics.name})
        return self._root

    def _stop(self, t: float) -> None:
        phase, t0, self.now = self._open_phases[-1], self.now, t
        self._metrics.tick_s[phase.name] += t - t0
        if self._twin is not None:
            self._twin.__exit__(None, None, None)
            self._twin = None
        if self._span is not None:
            # A phase that BEGAN traced is recorded even if tracing has
            # been switched off since: the last phase of a profiled
            # stretch would otherwise leave its gap unnamed.
            self._span["attrs"] = dict(phase.attrs)
            _tracing.end_span(self._span, end=_tracing.wall(t))
            self._span = None


class _Program:
    """One program on the device, as `DeviceQueue` knows it. ``start``
    and ``end`` are None until seen (or for good, where they never
    are); ``done`` asks the device whether the program has left it (no
    sync)."""

    __slots__ = ("kind", "t_dispatch", "start", "end", "done", "attrs",
                 "open")

    def __init__(self, kind: str, t_dispatch: float, done, attrs: dict):
        self.kind, self.t_dispatch = kind, t_dispatch
        self.start: Optional[float] = None
        self.end: Optional[float] = None
        self.done, self.attrs, self.open = done, attrs, True

    def split(self) -> Optional[tuple]:
        """(behind, own) seconds: on the device behind other programs,
        then running. None unless both ends were seen."""
        if self.start is None or self.end is None:
            return None
        return self.start - self.t_dispatch, self.end - self.start


class DeviceQueue:
    """The programs the engine thread has put on the device and not yet
    seen end, in the order it dispatched them, which is the order the
    device runs them in. Kept on stamps the tick takes anyway: a
    program's dispatch (its phase's opening stamp) and, where the fetch
    of its result had to wait, that fetch's return: the device's own
    clock, the moment the program ended and the next one began.

    For a program ``e`` behind ``p``: ``start(e) = max(t_dispatch(e),
    end(p))``, ``own = end - start``, ``behind = start - t_dispatch``.
    A stamp stays unknown in three cases: the fetch found its result
    ready (the program ended by then, nobody saw when); nobody fetches
    the program at all (a prefill chunk that is not an admission's
    last, a trailing chunk dropped; the next fetch closes it, end
    unseen); and so the START of whatever ran behind either. A program
    dispatched to a queue known empty starts at its dispatch, and the
    device has been dry since the host knew it empty: a lower bound.

    Engine-thread-only, no lock; ``clock`` is the thread's `TickClock`
    (the ``device.<kind>`` spans go under its root). Programs the tick
    puts on the device outside it (page exports and installs) run
    unseen: their time counts as dry.
    """

    def __init__(self, metrics: EngineMetrics, clock: TickClock):
        self._metrics = metrics
        self._clock = clock
        self._open: Deque[_Program] = collections.deque()
        # Since when the host has known the device dry: the exact end
        # of the last program where a fetch saw it, else the stamp at
        # which a fetch found it ended. None while a program is open,
        # and before the first.
        self._empty_since: Optional[float] = None
        # The return stamp of the last fetch, if it had to wait.
        self._fetched_t: Optional[float] = None
        # The listening wait's last poll that found the device busy.
        self._busy_t: Optional[float] = None
        # Device seconds of the last chunks whose ends were both seen.
        self.chunk_owns: Deque[float] = collections.deque(maxlen=8)

    def ahead(self) -> tuple:
        """(chunks, prefills) not yet seen to end: what a program
        dispatched now queues behind."""
        chunks = sum(p.kind == "chunk" for p in self._open)
        return chunks, len(self._open) - chunks

    def put(self, kind: str, t_dispatch: float, done, poll: bool = False,
            **attrs) -> _Program:
        """A program was dispatched at ``t_dispatch``. ``poll``: ask
        the program ahead whether it has left the device already (the
        listening wait may have outrun it); if so the device has been
        dry since the wait last found it busy, at most."""
        e = _Program(kind, t_dispatch, done, attrs)
        since = self._empty_since
        if poll and self._open and self._open[-1].done():
            since = self._busy_t
            while self._open:
                self._close()
        if not self._open:
            e.start = t_dispatch
            if since is not None:
                self._metrics.record_dry_dispatch(
                    max(0.0, t_dispatch - since))
            self._empty_since = None
        self._open.append(e)
        return e

    def _close(self) -> _Program:
        """The head of the queue has left the device."""
        p = self._open.popleft()
        p.open, p.done = False, None    # (a chunk's probe holds its record)
        return p

    def busy_at(self, t: float) -> None:
        """The listening wait found the program it waits for still on
        the device at ``t``."""
        self._busy_t = t

    def fetched(self, t_blocked: Optional[float]) -> None:
        """A fetch returned: at ``t_blocked`` if it had to wait for the
        device, which ended the fetched program then; None if the
        result was ready."""
        self._fetched_t = t_blocked

    def seen(self, e: _Program, now: float) -> Optional[tuple]:
        """The tick has fetched ``e``'s result (`fetched` has the
        stamp; ``now`` is the witness where it has none). Closes ``e``
        and every program ahead of it, those with their end unseen.
        Returns ``e.split()``."""
        if not e.open:
            return None     # closed since: a later program's end, a poll
        while self._close() is not e:
            pass
        e.end = self._fetched_t
        if self._open:
            if e.end is not None:
                nxt = self._open[0]
                nxt.start = max(nxt.t_dispatch, e.end)
        else:
            self._empty_since = now if e.end is None else e.end
        split = e.split()
        if split is not None:
            if e.kind == "chunk":
                self.chunk_owns.append(split[1])
            if _tracing.enabled():
                start = _tracing.wall(e.start)
                _tracing.emit_span(
                    "device." + e.kind, start, _tracing.wall(e.end),
                    parent=self._clock.root(start),
                    attrs=dict(e.attrs, behind_s=split[0],
                               own_s=split[1]))
        return split
