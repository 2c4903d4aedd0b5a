"""The prefill and decode roles of disaggregated serving: hand a
prefilled slot's KV pages over, install pages handed in."""

from __future__ import annotations

import queue
import time
import zlib
from typing import Any, Dict, List

import numpy as np

from ray_tpu.serve.engine.scheduler import EngineRequest
from ray_tpu.util import tracing as _tracing


class KVHandoff:
    """``engine.handoff`` (None on a ``role="colocated"`` engine). The
    decode role's install jobs come by ``arrivals``; those that race
    slot exhaustion wait in FIFO order (``waiting``). The prefill role
    uses ``finish`` alone. Device work stays on the engine thread,
    under the tick's transfer guard like every other dispatch, through
    the engine's device surface (engine/README.md) alone."""

    def __init__(self, engine):
        self.engine = engine
        self.arrivals: "queue.Queue" = queue.Queue()  # (request, payload)
        self.waiting: List[tuple] = []

    def finish(self, req: EngineRequest) -> None:
        """Prefill role: resolve the request with a KV handoff payload
        (or a completed result when the first token already ends it)
        and recycle the slot — seeding the prefill-side prefix cache
        with the full prompt, so repeat-prefix traffic keeps its reuse
        win on the prefill pool."""
        eng = self.engine
        slot = req.slot
        plen = len(req.prompt_ids)
        first = req.generated[-1]
        done = (len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and first == req.eos_id)
                or plen + 1 >= eng.max_len)
        result: Dict[str, Any]
        if done:
            result = {"token_ids": list(req.generated),
                      "num_generated": len(req.generated),
                      "cached_prefix_len": req.cached_len}
        else:
            P = eng.kv.block_size
            # Shared export path (export_pages): one program per page,
            # ONE host sync for the batch, tagged kv_export so the
            # RTPU_DEBUG_JAX witness attributes it separately from the
            # counted prefill sync.
            pages_k, pages_v, crcs = eng.export_pages(
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
                "chain": list(eng.kv.slot_chain(slot)),
                "cached_prefix_len": req.cached_len,
            }
            if req.tenant or req.priority:
                # QoS attribution survives the handoff: the decode-role
                # engine schedules the installed request in the same
                # class the prefill side admitted it in.
                result["tenant"] = req.tenant
                result["priority"] = req.priority
        eng.kv.release(slot, resident_tokens=req.prompt_ids)
        req.slot = -1
        if not req.future.done():
            req.future.set_result(result)
        if req.stream_queue is not None and done:
            req.stream_queue.put(("done", None))
        if req.trace_ctx is not None:
            _tracing.flush()

    def tick(self) -> None:
        """Decode role: install queued KV handoffs into free slots,
        FIFO. A job that races slot exhaustion waits (installs never
        jump the line — later handoffs can't acquire either)."""
        eng = self.engine
        while True:
            try:
                self.waiting.append(self.arrivals.get_nowait())
            except queue.Empty:
                break
        pending = self.waiting
        self.waiting = []
        for i, (req, payload) in enumerate(pending):
            if not eng.kv.free_slots():
                self.waiting.extend(pending[i:])
                return
            try:
                self._install_one(req, payload)
            except BaseException as e:  # noqa: BLE001 — one bad handoff
                # must not kill the engine thread
                eng._recover_cache(e)
                eng._deliver_error([req], e)

    def _install_one(self, req: EngineRequest,
                     payload: Dict[str, Any]) -> None:
        eng = self.engine
        # fit vetoes every reuse depth: the handoff's pages OVERWRITE
        # the slot's rows wholesale, so counting a resident-prefix
        # "hit" here would pollute the prefix-cache stats with reuse
        # that never happens.
        eng.kv.current_request = req
        try:
            got = eng.kv.acquire(req.prompt_ids, fit=lambda c: False)
        finally:
            eng.kv.current_request = None
        if got is None:
            raise RuntimeError("no free slot for KV install")
        slot, _cached = got
        P = int(payload["page"])
        try:
            crcs = payload.get("page_crc")
            for i, (kp, vp) in enumerate(zip(payload["pages_k"],
                                             payload["pages_v"])):
                if crcs is not None:
                    got_crc = (zlib.crc32(np.ascontiguousarray(kp)
                                          .tobytes())
                               ^ zlib.crc32(np.ascontiguousarray(vp)
                                            .tobytes()))
                    if got_crc != crcs[i]:
                        raise RuntimeError(
                            f"KV page {i} checksum mismatch: the page "
                            "bytes were corrupted in transit")
                eng.cache = eng.loop.install_page(
                    eng.cache, eng._put(kp), eng._put(vp),
                    eng._put(np.int32(slot)),
                    eng._put(np.int32(i * P)))
            eng.kv.commit_prefill(slot, req.prompt_ids)
            # Chain equality covers TOKEN/protocol identity (same
            # prompt, same block algorithm/size); the per-page CRCs
            # above cover the KV BYTES themselves.
            chain = list(eng.kv.slot_chain(slot))
            want = payload.get("chain")
            if want is not None and chain != list(want):
                raise RuntimeError(
                    "KV chain mismatch after install: the decode side's "
                    "block hashes disagree with the prefill side's")
        except BaseException:
            eng.kv.release(slot, resident_tokens=())
            raise
        req.slot = slot
        req.first_token_t = time.perf_counter()
        eng.scheduler.activate(req)
        eng._maybe_finish(req, req.generated[-1])
