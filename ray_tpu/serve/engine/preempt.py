"""Priority preemption (per-tenant QoS): a starved higher-priority
arrival parks the lowest-priority active request, which resumes later as
a continuation of itself."""

from __future__ import annotations

import time
from typing import Any, Dict, List

from ray_tpu.devtools import res_debug as _resdbg
from ray_tpu.serve.engine.scheduler import EngineRequest


class Preemption:
    """``engine.preemption`` (on every engine: it has no keyword):
    parked lower-priority requests awaiting resume, plus lifetime
    counters. Engine-thread-only state like the roster itself; called
    in ``_admit`` alone, through the engine's device surface
    (engine/README.md) and nothing else of the tick."""

    def __init__(self, engine):
        self.engine = engine
        self.parked: List[EngineRequest] = []
        self.preempts = 0
        self.resumes = 0

    def stats(self) -> Dict[str, Any]:
        return {"parked": len(self.parked), "preempts": self.preempts,
                "resumes": self.resumes}

    def close(self) -> None:
        """Sessions still parked at close never resume: settle their
        pins deliberately (teardown mid-workload is a drain, not a
        leak), then assert nothing else is left outstanding."""
        for req in self.parked:
            _resdbg.note_release("parked_kv", (id(self.engine), id(req)))
        self.parked.clear()
        _resdbg.check_balanced("engine.close", kinds=("parked_kv",),
                               owner=self.engine)

    def park(self) -> bool:
        """Park the lowest-priority active request when a strictly
        higher-priority arrival is starved for a slot. The victim's
        slot recycles with its confirmed rows prefix-resident
        (scheduler.preempt), so the resume continuation re-prefills
        from cache — or pulls the pages back through the fleet spill
        tier once they're evicted (the export/install seam). Returns
        True when a slot was freed."""
        eng = self.engine
        scheduler = eng.scheduler
        hp = scheduler.max_waiting_priority()
        if hp is None or not scheduler.active:
            return False
        # Victim: lowest class, newest arrival within it (LIFO — the
        # request with the least sunk decode work loses its slot).
        def lowest():
            return min(scheduler.active,
                       key=lambda r: (r.priority, -r.arrival_t))

        victim = lowest()
        if victim.priority >= hp:
            return False
        # Land the in-flight decode chunk BEFORE recycling a slot: it
        # was dispatched with the victim in its roster, and the retire
        # delivers a slot's tokens only to the request the chunk was
        # dispatched with while it still holds the slot — parked first,
        # the victim would lose them (and prefill them again on
        # resume). With nothing in flight the next chunk is built from
        # the host's values, the preemptor's among them.
        if not eng._land_inflight():
            return False
        if eng.kv.free_slots():
            return True  # retirement finished someone: slot free
        if victim not in scheduler.active:
            victim = lowest()
            if victim.priority >= hp:
                return False
        t0 = time.perf_counter()
        scheduler.preempt(victim)
        self.parked.append(victim)
        # RTPU_DEBUG_RES: a parked session pins scheduler + KV residency
        # until it resumes (or the engine closes) — an entry left behind
        # by a resume/close path is exactly the leak the witness flags.
        _resdbg.note_acquire("parked_kv", key=(id(eng), id(victim)),
                             owner=eng, note="preempt_park")
        self.preempts += 1
        if victim.trace_ctx is not None:
            eng._span("engine.preempt_park", t0, time.perf_counter(),
                      victim, {"priority": victim.priority,
                               "generated": len(victim.generated),
                               "remaining": victim.remaining()})
        return True

    def resume(self) -> None:
        """Re-admit parked requests (highest priority first) while
        slots are free and no strictly higher-priority request is
        still waiting — a resume that would immediately be preempted
        again is thrash, not progress."""
        eng = self.engine
        if not eng.kv.free_slots():
            return
        self.parked.sort(key=lambda r: (-r.priority, r.arrival_t))
        waiting_hp = eng.scheduler.max_waiting_priority()
        resumed: List[EngineRequest] = []
        for req in self.parked:
            if not eng.kv.free_slots():
                break
            if waiting_hp is not None and waiting_hp > req.priority:
                break
            self._resume_one(req)
            resumed.append(req)
        for req in resumed:
            self.parked.remove(req)
            _resdbg.note_release("parked_kv", (id(eng), id(req)))

    def _resume_one(self, orig: EngineRequest) -> None:
        """Resume a parked request as a CONTINUATION: a fresh request
        whose prompt is ``prompt + generated`` (greedy determinism
        makes the regenerated suffix token-identical) and whose budget
        is the remainder. The continuation shares the stream queue —
        tokens keep flowing on the original stream — and its result
        merges into the original future. Admission runs the normal
        path, so the parked rows come back as a prefix-cache hit or a
        fleet pull (the park/resume KV round-trip)."""
        eng = self.engine
        t0 = time.perf_counter()
        cont = EngineRequest(
            list(orig.prompt_ids) + list(orig.generated),
            max_new_tokens=orig.remaining(),
            eos_id=orig.eos_id,
            stream_queue=orig.stream_queue,
            arrival_t=orig.arrival_t,
            trace_ctx=orig.trace_ctx,
            tenant=orig.tenant, priority=orig.priority)
        if eng.speculation is not None:
            cont.spec = eng.speculation.control()

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
        eng.scheduler.submit(cont)
        self.resumes += 1
        if orig.trace_ctx is not None:
            eng._span("engine.preempt_resume", t0, time.perf_counter(),
                      orig, {"priority": orig.priority,
                             "resume_prompt": len(cont.prompt_ids),
                             "remaining": cont.max_new_tokens})
