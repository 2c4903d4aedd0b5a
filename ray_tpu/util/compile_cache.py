"""JAX's persistent compilation cache, placed from outside, and the
account of what getting programs onto the chip cost this process.

The cache's directory is part of its key, so a directory that moves
never hits: the entry scripts (``chip_smoke.py``, ``bench.py``'s chip
children) call `configure` once before their first compile and the
library itself never touches the setting. Where the machine sets
``JAX_COMPILATION_CACHE_DIR`` JAX already reads it and no directory is
set in code; otherwise one fixed directory inside the checkout is used.

**The account** (`CompileCache`, the object `configure` makes; `account`
hands it to whoever has no engine to ask). JAX times every stage of a
program's way onto the chip and says which program it was
(``jax.monitoring``, jax 0.9.0): a `record_scalar` at a stage's start
and a `record_event_duration_secs` at its end, both with ``fun_name=``,
for ``jaxpr_trace_duration`` (``fun_name`` the function's name),
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
(``jit(<name>)``); and, from inside the backend stage on the same
thread and with no name, the events ``compile_requests_use_cache`` and
``cache_hits`` and the durations ``cache_retrieval_time_sec`` (not
read: it lies inside the backend stage, which is what is booked) and
``compile_time_saved_sec`` (on a hit only). One listener of each of the
three kinds keeps, under the program's bare name:

- ``requests``: backend compiles asked for (a second signature, or a
  second lowering of the same one, shows as 2), ``hits`` of them served
  from the persistent cache;
- ``trace_s``, ``lower_s``: seconds tracing to a jaxpr and lowering it
  to a module, paid on every start, warm or cold;
- ``compile_s``: backend seconds of requests that missed or did not
  consult the cache; ``cache_load_s``: backend seconds of requests that
  hit (the key, the read, the load); ``saved_s``: what JAX says the hits
  saved. A stage JAX never reported for a name stays ``None``.

A stage opened inside another on the same thread (a jitted helper traced
inside the program that calls it) belongs to the outer one and gets no
row. Rows sum a program's own seconds; `totals` are the UNION of the
stages' intervals on the wall clock, so two threads that compile at once
(the engine thread's tick programs beside a caller's) cannot add up to
more than the time that passed.

**Phases** (`phase`): a constructor's wall time, less the stages booked
while it was open on its thread, so the parts add up and nothing is
counted twice. While ``tracing_enabled`` is on a finished program is
also a ``compile.<name>`` span and a phase a ``setup.<phase>`` span
(`tracing.emit_span`, children of the thread's current span).
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from typing import Any, Dict, Optional

from ray_tpu.util import tracing as _tracing

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_BACKEND = "/jax/core/compile/backend_compile_duration"
# Stage event -> the row's field (a backend stage's is decided by its hit).
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           _BACKEND: "compile_s"}
# What a program's way onto the device cost, and what the cache saved.
_PAID = ("trace_s", "lower_s", "compile_s", "cache_load_s")
_SECONDS = _PAID + ("saved_s",)


def _bare(fun_name: Optional[str]) -> str:
    """``jit(f)`` / ``pmap(f)`` (the lowering's and the backend's name
    of a program) as ``f`` (the trace's)."""
    name = fun_name or "?"
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name


class _Thread:
    """What one thread has open: stages (innermost last), phases, and
    the program whose stages are being gathered into one span."""

    __slots__ = ("stages", "phases", "program")

    def __init__(self):
        self.stages: list = []     # [event, name, start, hit, saved_s]
        self.phases: list = []     # _Phase
        self.program: Optional[Dict[str, Any]] = None


class _Phase:
    """One ``with account.phase(name)`` block; ``own_s`` after it: the
    block's wall seconds less the compile stages booked inside it."""

    __slots__ = ("_account", "name", "_t0", "_compile_s", "own_s")

    def __init__(self, account: Optional["CompileCache"], name: str):
        self._account, self.name = account, name
        self._compile_s = 0.0
        self.own_s = 0.0

    def __enter__(self) -> "_Phase":
        if self._account is not None:
            self._account._thread().phases.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.own_s = max(0.0, t1 - self._t0 - self._compile_s)
        if self._account is not None:
            self._account._close_phase(self, t1 - self._t0, t1)


class CompileCache:
    """The directory in use, and what it served since `configure`:
    ``requests`` compiles consulted the cache, ``hits`` of them were
    read from it; ``rows`` and `totals` say what each program cost
    (the module's docstring)."""

    def __init__(self, path: str):
        self.path = path
        self.requests = 0
        self.hits = 0
        self.rows: Dict[str, Dict[str, Any]] = {}
        self.phases: Dict[str, Dict[str, float]] = {}
        self._lock = threading.Lock()
        self._threads: Dict[int, _Thread] = {}     # by thread ident
        self._union = dict.fromkeys(_PAID + ("init_s",), 0.0)
        # Disjoint stage intervals already counted, sorted; pruned to
        # those a stage still open could overlap.
        self._covered: list = []

    # ------------------------------------------------------------ listeners

    def _thread(self) -> _Thread:
        ident = threading.get_ident()
        th = self._threads.get(ident)
        if th is None:
            with self._lock:
                th = self._threads[ident] = _Thread()
        return th

    def _on_scalar(self, event: str, value, **kw) -> None:
        if event in _STAGES:
            self._thread().stages.append(
                [event, _bare(kw.get("fun_name")), float(value), False, None])

    def _on_event(self, event: str, **_kw) -> None:
        if event == _REQUESTS:
            with self._lock:
                self.requests += 1
        elif event == _HITS:
            with self._lock:
                self.hits += 1
            stages = self._thread().stages
            if stages and stages[-1][0] == _BACKEND:
                stages[-1][3] = True

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        th = self._thread()
        if event == _SAVED:
            if th.stages and th.stages[-1][0] == _BACKEND:
                th.stages[-1][4] = secs
            return
        field = _STAGES.get(event)
        if field is None:
            return
        if not th.stages or th.stages[-1][0] != event:
            return  # opened before this listener was registered
        _, name, start, hit, saved = th.stages.pop()
        if th.stages:
            return  # inside another stage on this thread: the outer one's
        if event == _BACKEND and hit:
            field = "cache_load_s"
        with self._lock:
            row = self.rows.get(name)
            if row is None:
                row = self.rows[name] = dict.fromkeys(_SECONDS)
                row.update(requests=0, hits=0)
            row[field] = (row[field] or 0.0) + secs
            if event == _BACKEND:
                row["requests"] += 1
                row["hits"] += hit
                if saved is not None:
                    row["saved_s"] = (row["saved_s"] or 0.0) + saved
            self._union[field] += self._uncovered(start, start + secs)
        for phase in th.phases:
            phase._compile_s += secs
        self._gather_span(th, name, field, start, secs, hit,
                          last=event == _BACKEND)

    def _uncovered(self, start: float, end: float) -> float:
        """Seconds of [start, end] no counted stage covers yet; counts
        the interval. Called under the lock."""
        cov = self._covered
        i = bisect.bisect_left(cov, (start,))
        if i and cov[i - 1][1] > start:
            i -= 1
        new, lo, hi, j = end - start, start, end, i
        while j < len(cov) and cov[j][0] < end:
            a, b = cov[j]
            new -= max(0.0, min(b, end) - max(a, start))
            lo, hi, j = min(lo, a), max(hi, b), j + 1
        cov[i:j] = [(lo, hi)]
        # Nothing that closes later can begin before the oldest stage
        # still open: what ends before it is never asked about again.
        oldest = hi
        for t in self._threads.values():
            try:
                oldest = min(oldest, t.stages[0][2])
            except IndexError:   # its owner closed it meanwhile
                pass
        k = 0
        while k < len(cov) and cov[k][1] <= oldest:
            k += 1
        del cov[:k]
        return max(0.0, new)

    def _gather_span(self, th: _Thread, name: str, field: str, start: float,
                     secs: float, hit: bool, last: bool) -> None:
        """A program's stages follow each other on one thread: gather
        them, and at the backend's end emit the one span."""
        if not _tracing.enabled():
            th.program = None
            return
        prog = th.program
        if prog is None or prog["name"] != name:
            prog = th.program = {"name": name, "start": start, "attrs": {}}
        prog["attrs"][field] = secs
        if last:
            th.program = None
            _tracing.emit_span("compile." + name, prog["start"], start + secs,
                               attrs=dict(prog["attrs"], hit=bool(hit)))

    # --------------------------------------------------------------- phases

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def _close_phase(self, phase: _Phase, wall_s: float, t1: float) -> None:
        phases = self._thread().phases
        phases.remove(phase)
        with self._lock:
            row = self.phases.setdefault(
                phase.name, {"n": 0, "wall_s": 0.0, "own_s": 0.0})
            row["n"] += 1
            row["wall_s"] += wall_s
            row["own_s"] += phase.own_s
            if not phases:
                self._union["init_s"] += phase.own_s
        if _tracing.enabled():
            end = _tracing.wall(t1)
            _tracing.emit_span("setup." + phase.name, end - wall_s, end,
                               attrs={"own_s": phase.own_s})

    # ---------------------------------------------------------------- views

    def totals(self) -> Dict[str, Any]:
        """The account in one flat dict: how many programs and backend
        requests, and the seconds of each kind on the wall clock
        (``init_s``: the outermost phases, less their compile seconds)."""
        with self._lock:
            rows = list(self.rows.values())
            out = dict(self._union)
        out.update(programs=len(rows),
                   requests=sum(r["requests"] for r in rows),
                   hits=sum(r["hits"] for r in rows),
                   saved_s=sum(r["saved_s"] or 0.0 for r in rows))
        return out

    def _listen(self, monitoring) -> None:
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_scalar_listener(self._on_scalar)


_ACCOUNT: Optional[CompileCache] = None


def account() -> Optional[CompileCache]:
    """The object `configure` made for this process, or None."""
    return _ACCOUNT


def phase(name: str) -> _Phase:
    """``with phase("engine.init") as p:`` — timed either way
    (``p.own_s``), booked where the process has an account."""
    return _Phase(_ACCOUNT, name)


def configure() -> CompileCache:
    """Turn the persistent cache and the account on for this process
    (once: a second call returns the first one's object)."""
    global _ACCOUNT
    if _ACCOUNT is not None:
        return _ACCOUNT
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: a warm start should skip the small ones too
    # (the default keeps only compiles that took over a second).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _ACCOUNT = CompileCache(path)
    _ACCOUNT._listen(jax.monitoring)
    return _ACCOUNT
