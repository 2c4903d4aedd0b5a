"""Fleet KV-cache economy: chain-hashed prefix pages as tiered objects.

Per-replica prefix caching (kv_manager.py) dies with its process — an
evicted block's KV is recomputed even when an identical prefix was
materialized seconds ago on this node or a peer. This module gives the
chain-hashed KV page a cluster-object lifecycle instead:

  HBM (slot rows)  --evict-->  shm store  --LRU clock-->  disk spill
        ^                          |
        +------- fleet pull -------+   (local memcpy, or the peer /
                                        multi-source pull path when the
                                        holder is another node)

One object per COMPLETE prefix block, keyed by a deterministic object
id derived from (model fingerprint, chain hash). The chain-hash
property — ``h_i = H(h_{i-1}, block_i)`` — means a single hash
identifies the whole prefix through block ``i``, so a puller walks its
own prompt's chain depth by depth and stops at the first miss:
longest-resident-prefix wins without a directory range scan.

The payload is the PR 15 export shape (``k_page``/``v_page`` of
``[L, KH, P, D]`` + per-page CRC + the chain prefix), and installs go
through the same ``install_page`` + chain-verify seam as the disagg
handoff, so wrong KV cannot decode silently no matter which tier it
came from.

Two store backends behind one duck type (``put/get/contains/stats``):

* ``LocalKVPageStore`` — in-process dict with an LRU byte cap. The
  store-free fallback (unit tests, single-process serving without a
  cluster runtime); also shareable between engines in one process to
  model a node's shm tier.
* ``ClusterKVPageStore`` — rides the real shm object store: puts
  register in the sharded head object directory like any other object,
  gets fall back to a directory lookup + ``pull_object`` through the
  multi-source pull manager, and tier residency (shm -> disk spill)
  rides the store's existing global eviction clock for free.

Model identity matters: chain hashes cover TOKENS only, so the object
id namespace folds in every config knob that changes KV bytes for the
same tokens (dims, layers, dtype, quantization, block size, param
seed). Two deployments of different models can share a store without
ever resolving each other's pages.
"""

from __future__ import annotations

import hashlib
import io
import math
import queue
import struct
import threading
import time
import zlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.devtools import res_debug as _resdbg
from ray_tpu.serve.engine.kv_manager import chain_hashes
from ray_tpu.serve.engine.scheduler import bucket_for

# Matches ids._FLAG_PUT: fleet page ids present as ordinary put-objects
# to the directory/pull plumbing (no task lineage to reconstruct them).
_PUT_FLAGS = struct.pack("<I", 0x1)

_MAGIC = b"RTKV1\n"


def fleet_namespace(cfg, block_size: int, quantize: Optional[str],
                    seed: int) -> bytes:
    """20-byte namespace digest over everything that changes KV BYTES
    for the same token ids. Engines whose namespaces differ can never
    resolve each other's pages — the silent-wrong-KV failure mode is
    structurally unreachable, not just checked."""
    ident = (
        "rtpu-kv-fleet", int(cfg.vocab_size), int(cfg.d_model),
        int(cfg.n_layers), int(cfg.n_heads), int(cfg.n_kv_heads),
        int(cfg.max_seq_len), str(getattr(cfg, "dtype", "")),
        str(quantize), int(seed), int(block_size),
    )
    return hashlib.blake2b(repr(ident).encode(), digest_size=20).digest()


def page_object_id(namespace: bytes, chain_hash: int):
    """Deterministic ObjectID for the prefix ending at ``chain_hash``.
    Layout matches ids.ObjectID (index 4B + task 20B + flags 4B): the
    24 content bytes come from hashing (namespace, chain hash), the
    flags mark it a put-object. Every holder of the same prefix derives
    the same id — which is what makes dedupe and fleet lookup work with
    no coordination."""
    from ray_tpu.core.ids import ObjectID

    h = hashlib.blake2b(
        namespace + struct.pack("<Q", chain_hash & (2 ** 64 - 1)),
        digest_size=24).digest()
    return ObjectID(h + _PUT_FLAGS)


def pack_page(block_tokens, chain, k_page: np.ndarray,
              v_page: np.ndarray, crc: int) -> bytes:
    """Serialize one block's payload. np.save framing (not pickle):
    shape/dtype ride in the header, the page bytes stream raw, and
    unpack never executes attacker-controlled bytecode."""
    buf = io.BytesIO()
    buf.write(_MAGIC)
    toks = np.asarray(block_tokens, np.int64)
    ch = np.asarray(chain, np.int64)
    buf.write(struct.pack("<qII", crc & 0xFFFFFFFF, len(toks), len(ch)))
    buf.write(toks.tobytes())
    buf.write(ch.tobytes())
    np.save(buf, np.ascontiguousarray(k_page), allow_pickle=False)
    np.save(buf, np.ascontiguousarray(v_page), allow_pickle=False)
    return buf.getvalue()


def unpack_page(raw: bytes) -> Optional[Dict[str, Any]]:
    """Decode + integrity-check one payload. Returns None on ANY
    corruption (bad magic, short read, CRC mismatch) — the caller
    treats it exactly like a store miss and recomputes."""
    try:
        buf = io.BytesIO(raw)
        if buf.read(len(_MAGIC)) != _MAGIC:
            return None
        crc, nt, nc = struct.unpack("<qII", buf.read(16))
        tokens = np.frombuffer(buf.read(8 * nt), np.int64)
        chain = np.frombuffer(buf.read(8 * nc), np.int64)
        k_page = np.load(buf, allow_pickle=False)
        v_page = np.load(buf, allow_pickle=False)
    except Exception:  # rtpu-lint: disable=swallowed-exception — truncated/garbled frame reads as a store miss by design
        return None
    got = (zlib.crc32(np.ascontiguousarray(k_page).tobytes())
           ^ zlib.crc32(np.ascontiguousarray(v_page).tobytes()))
    if (got & 0xFFFFFFFF) != (crc & 0xFFFFFFFF):
        return None
    return {"tokens": [int(t) for t in tokens],
            "chain": [int(h) for h in chain],
            "k_page": k_page, "v_page": v_page, "crc": int(crc)}


class LocalKVPageStore:
    """In-process page tier: dict + LRU byte cap. The store-free
    fallback when no cluster runtime (and thus no shm arena) is
    attached; tests share one instance between engines to model the
    node-local shm tier without the native library."""

    def __init__(self, capacity_bytes: Optional[int] = None):
        if capacity_bytes is None:
            from ray_tpu.core.config import GLOBAL_CONFIG as cfg

            capacity_bytes = cfg.serve_kv_fleet_local_bytes
        self.capacity_bytes = int(capacity_bytes)
        self._lock = threading.Lock()
        self._objs: "OrderedDict[bytes, bytes]" = OrderedDict()
        self._bytes = 0
        self.evictions = 0

    def put(self, oid, payload: bytes) -> bool:
        key = oid.binary()
        with self._lock:
            if key in self._objs:
                return False
            self._objs[key] = payload
            self._bytes += len(payload)
            while self._bytes > self.capacity_bytes and len(self._objs) > 1:
                _k, old = self._objs.popitem(last=False)
                self._bytes -= len(old)
                self.evictions += 1
            return True

    def get(self, oid) -> Optional[bytes]:
        key = oid.binary()
        with self._lock:
            raw = self._objs.get(key)
            if raw is not None:
                self._objs.move_to_end(key)  # a hit is a hotness signal
            return raw

    def contains(self, oid) -> bool:
        with self._lock:
            return oid.binary() in self._objs

    def delete(self, oid) -> bool:
        key = oid.binary()
        with self._lock:
            raw = self._objs.pop(key, None)
            if raw is not None:
                self._bytes -= len(raw)
            return raw is not None

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"objects": len(self._objs), "bytes": self._bytes,
                    "evictions": self.evictions}


_local_singleton: Optional[LocalKVPageStore] = None
_local_lock = threading.Lock()


def local_store() -> LocalKVPageStore:
    """Process-wide LocalKVPageStore: engines in one process share the
    "node" tier even without a cluster runtime."""
    global _local_singleton
    with _local_lock:
        if _local_singleton is None:
            _local_singleton = LocalKVPageStore()
        return _local_singleton


class ClusterKVPageStore:
    """Page tier over the real cluster object plane. Puts land in the
    node's shm arena and register in the sharded head directory via the
    same batched object-notify path as task outputs; gets try the local
    arena (memcpy), then one directory-guided ``pull_object`` through
    the node manager's multi-source pull manager. Eviction needs no new
    code: the arena's global LRU clock spills cold pages to disk and
    ``get`` transparently restores them."""

    def __init__(self, core, pull_timeout_ms: int = 2000):
        self._core = core          # ClusterCore (driver or worker runtime)
        self._pull_timeout_ms = int(pull_timeout_ms)

    def put(self, oid, payload: bytes) -> bool:
        store = self._core.store
        try:
            if store.contains(oid):
                return False
            store.put_bytes(oid, payload)
        except Exception:  # rtpu-lint: disable=swallowed-exception — duplicate-create race / arena pressure; see below
            # Duplicate create (a sibling replica on this node raced the
            # same chain hash) or arena pressure: the page tier is a
            # cache — a failed put is a skipped optimization, never an
            # error the engine should see.
            return False
        self._core._queue_object_notify("add", oid.binary(), len(payload))
        return True

    def get(self, oid, remote: bool = True) -> Optional[bytes]:
        store = self._core.store
        raw = store.get_bytes(oid)
        if raw is not None or not remote:
            return raw
        try:
            holders = self._core.head.call(
                "object_locations", oid.binary(),
                getattr(self._core, "node_id", None), timeout=2)
        except Exception:  # rtpu-lint: disable=swallowed-exception — directory unreachable == tier miss; recompute covers it
            return None
        if not holders:
            return None
        try:
            ok = bool(self._core.node.call(
                "pull_object", oid.binary(), self._pull_timeout_ms, None,
                timeout=self._pull_timeout_ms / 1e3 + 2))
        except Exception:  # rtpu-lint: disable=swallowed-exception — failed peer pull == tier miss; recompute covers it
            return None
        return store.get_bytes(oid) if ok else None

    def contains(self, oid) -> bool:
        return self._core.store.contains(oid)

    def delete(self, oid) -> bool:
        store = self._core.store
        if store.delete(oid):
            self._core._queue_object_notify("rm", oid.binary())
            return True
        return False

    def stats(self) -> Dict[str, int]:
        used, cap, n, ev = self._core.store.stats()
        return {"objects": n, "bytes": used, "evictions": ev}


def resolve_store(explicit=None):
    """Pick the page tier for an engine: an explicit store instance
    (tests, bench), else the cluster shm store when a runtime is
    attached, else the process-local fallback."""
    if explicit is not None:
        return explicit
    from ray_tpu.core.runtime_context import get_runtime

    rt = get_runtime()
    if (rt is not None and getattr(rt, "store", None) is not None
            and getattr(rt, "node", None) is not None):
        return ClusterKVPageStore(rt)
    return local_store()


class FleetTier:
    """The engine's half of the tier (``engine.fleet``; None when the
    gate is -1, and the engine is then byte-identical to the pre-fleet
    one: no transfer programs for colocated roles, no spill hook, no
    extra snapshot keys). Evicted prefix blocks spill into the page
    store and cache misses pull them back through the install_page +
    chain-verify seam, on the engine thread, through the engine's
    device surface (engine/README.md) alone.

    ``min_blocks``: 0 = always pull; n>0 = pull only contiguous runs of
    >= n blocks; "auto" = gate on the measured pull-vs-recompute
    crossover."""

    def __init__(self, engine, min_blocks, store, seed):
        self.engine = engine
        self.min_blocks = min_blocks
        self.store = resolve_store(store)
        self.ns = fleet_namespace(engine.cfg, engine.kv.block_size,
                                  engine.quantize, seed)
        self._lock = threading.Lock()
        self._recent: "OrderedDict[int, None]" = OrderedDict()
        self._block_count = 0
        self._stats = {"kv_fleet_hits": 0,
                       "kv_fleet_pulled_blocks": 0,
                       "kv_fleet_spilled_blocks": 0,
                       "kv_fleet_tokens_reused": 0,
                       "kv_fleet_rejects": 0}
        # Pull-vs-recompute crossover inputs: store-side costs are
        # measured now (synthetic page roundtrip); the recompute side
        # arrives from real prefill timings (note_prefill_cost).
        self._pf_ms_blk: Optional[float] = None
        self._pf_samples = 0
        self._pull_ms_page, self._lookup_ms = self._measure_costs()
        engine.kv.spill_hook = self.spill_evicted
        # Serialization + store puts happen off the engine thread: the
        # engine only exports (device work must stay on its thread) and
        # hands host pages over.
        self._spill_q: "queue.Queue" = queue.Queue()
        self._spill_thread = _resdbg.track_thread(
            threading.Thread(target=self._spill_loop, daemon=True,
                             name="llm-kv-spill"), owner=engine)
        self._spill_thread.start()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self._stats)
        out["kv_pull_vs_recompute_crossover_blocks"] = \
            self.crossover_blocks()
        out["kv_fleet_pull_ms_per_page"] = self._pull_ms_page
        out["kv_fleet_lookup_ms"] = self._lookup_ms
        out["kv_fleet_prefill_ms_per_block"] = self._pf_ms_blk
        try:
            out["kv_fleet_store"] = self.store.stats()
        except Exception:  # rtpu-lint: disable=swallowed-exception — stats enrichment; a store without a stats endpoint is fine
            pass
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Fleet-residency summary for the router's fleet term:
        distinct blocks this replica can re-install without recompute,
        plus the capped newest chain hashes. Keys exist ONLY when the
        tier is on, so fleet-off snapshots stay byte-identical."""
        with self._lock:
            return {"fleet_kv_blocks": self._block_count,
                    "fleet_kv_hashes": list(self._recent)}

    def close(self) -> None:
        """Drain the spill worker AFTER the engine thread is gone (it
        was the only producer): every exported page either lands in the
        store or is released — an in-flight tier transition abandoned
        here is what kv_page_obj catches."""
        self._spill_q.put(None)
        if (self._spill_thread.is_alive()
                and self._spill_thread is not threading.current_thread()):
            self._spill_thread.join(timeout=30.0)
        _resdbg.check_balanced("engine.close", kinds=("kv_page_obj",),
                               owner=self.engine)

    def spill_evicted(self, slot: int, resident, chain,
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
        eng = self.engine
        P = eng.kv.block_size
        todo = []
        for i in range(min(len(chain), len(resident) // P)):
            oid = page_object_id(self.ns, chain[i])
            if not self.store.contains(oid):
                todo.append((i, oid))
        if not todo:
            return
        req = getattr(eng.kv, "current_request", None)
        t0 = time.perf_counter()
        pages_k, pages_v, crcs = eng.export_pages(
            slot, [i * P for i, _ in todo], tag="kv_spill")
        jobs = []
        for (i, oid), k, v, crc in zip(todo, pages_k, pages_v, crcs):
            key = _resdbg.note_acquire("kv_page_obj", owner=eng,
                                       note=f"spill block {i}")
            jobs.append((oid, tuple(resident[i * P:(i + 1) * P]),
                         tuple(chain[:i + 1]), k, v, crc, key))
        self._spill_q.put(jobs)
        if req is not None and req.trace_ctx is not None:
            eng._span("engine.kv_spill", t0, time.perf_counter(), req,
                      {"blocks": len(todo), "slot": slot})

    def _spill_loop(self) -> None:
        """Spill worker: pack + store-put the exported pages. Pure host
        work on host arrays — no device access, so it needs no tick
        guard and never contends with the engine thread's dispatch."""
        while True:
            jobs = self._spill_q.get()
            if jobs is None:
                return
            for oid, toks, ch, k, v, crc, key in jobs:
                try:
                    payload = pack_page(toks, ch, k, v, crc)
                    if self.store.put(oid, payload):
                        with self._lock:
                            self._stats["kv_fleet_spilled_blocks"] += 1
                        self._note_hash(ch[-1])
                except Exception:  # rtpu-lint: disable=swallowed-exception — a failed put is a skipped optimization, never a veto
                    pass
                finally:
                    _resdbg.note_release("kv_page_obj", key)

    def extend(self, adm) -> None:
        """Fleet lookup on a (partial) prefix-cache miss: walk the
        prompt's block chain depth by depth past the local hit, pull
        each resident page from the tier store, and install through the
        same install_page + chain/CRC-verify seam as the disagg handoff
        — then shrink the admission's prefill plan to the suffix.
        Longest-contiguous-resident-prefix wins; the walk stops at the
        first miss or rejected payload and never partially applies: a
        failure before commit leaves cached_len untouched and the
        suffix prefill overwrites any rows already written."""
        eng = self.engine
        req = adm.request
        plen = len(req.prompt_ids)
        P = eng.kv.block_size
        want = chain_hashes(req.prompt_ids, P)
        max_d = min(len(want), (plen - 1) // P)
        d0 = adm.cached_len // P
        if max_d <= d0:
            return
        t0 = time.perf_counter()
        payloads = []
        for d in range(d0 + 1, max_d + 1):
            oid = page_object_id(self.ns, want[d - 1])
            try:
                raw = self.store.get(oid)
            except Exception:  # rtpu-lint: disable=swallowed-exception — a store/pull error is a tier miss; the walk stops here
                raw = None
            if raw is None:
                break
            page = unpack_page(raw)
            if (page is None
                    or page["chain"] != [int(h) for h in want[:d]]
                    or page["tokens"] != [
                        int(t) for t in
                        req.prompt_ids[(d - 1) * P:d * P]]):
                # Corrupt bytes (CRC/framing) or a chain-hash collision:
                # reject — recompute covers this depth and everything
                # past it, and the slot keeps its local state.
                with self._lock:
                    self._stats["kv_fleet_rejects"] += 1
                break
            payloads.append(page)
        run = len(payloads)
        # Same depth veto as scheduler.admissions: the bucket-padded
        # suffix prefill must still fit under max_len.
        while run > 0 and (adm.cached_len + run * P
                           + eng.scheduler._prefill_rows(
                               plen - adm.cached_len - run * P)
                           > eng.max_len):
            run -= 1
        if run <= 0 or run < self._gate():
            return
        keys = [_resdbg.note_acquire("kv_page_obj", owner=eng,
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
            eng.cache = eng.loop.install_page(
                eng.cache, eng._put(k_run), eng._put(v_run),
                eng._put(np.int32(adm.slot)),
                eng._put(np.int32(d0 * P)))
            new_cached = adm.cached_len + run * P
            eng.kv.commit_prefill(adm.slot, req.prompt_ids[:new_cached])
            got_chain = list(eng.kv.slot_chain(adm.slot))
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
        adm.chunks = eng.scheduler.prefill_plan(suffix)
        adm.bucket = bucket_for(suffix, eng.buckets)
        with self._lock:
            self._stats["kv_fleet_hits"] += 1
            self._stats["kv_fleet_pulled_blocks"] += run
            self._stats["kv_fleet_tokens_reused"] += run * P
        for j in range(run):
            self._note_hash(want[d0 + j])
        if req.trace_ctx is not None:
            eng._span("engine.kv_fleet_pull", t0, time.perf_counter(),
                      req, {"blocks": run, "tokens": run * P,
                            "slot": adm.slot})

    def _note_hash(self, h: int) -> None:
        """Record a chain hash this replica can serve from the fleet
        tier (spilled or pulled) — the capped newest-first summary the
        load snapshot ships for the router's fleet term."""
        from ray_tpu.core.config import GLOBAL_CONFIG as cfg

        cap = max(1, cfg.serve_snapshot_fleet_hashes)
        with self._lock:
            if h not in self._recent:
                self._block_count += 1
            self._recent[h] = None
            self._recent.move_to_end(h)
            while len(self._recent) > cap:
                self._recent.popitem(last=False)

    def note_prefill_cost(self, seconds: float,
                          suffix_tokens: int) -> None:
        """Recompute-side crossover input: EWMA of measured prefill
        milliseconds per block. The engine's first admission is
        excluded — it pays the bucket compiles, which are not a
        recompute cost."""
        self._pf_samples += 1
        if self._pf_samples == 1 or suffix_tokens <= 0:
            return
        ms_blk = (seconds * 1e3 * self.engine.kv.block_size
                  / suffix_tokens)
        prev = self._pf_ms_blk
        self._pf_ms_blk = (ms_blk if prev is None
                           else 0.8 * prev + 0.2 * ms_blk)

    def _measure_costs(self):
        """Pull-side crossover inputs, measured at engine start: the
        per-page cost of a store roundtrip (put+get+decode of a
        real-shaped synthetic page) and the per-walk lookup cost
        (contains probe). Host-only — no device work, no compiles."""
        cfg, P = self.engine.cfg, self.engine.kv.block_size
        page = np.zeros((cfg.n_layers, cfg.n_kv_heads, P, cfg.head_dim),
                        np.float32)
        crc = zlib.crc32(page.tobytes()) ^ zlib.crc32(page.tobytes())
        probe_hash = hash(("rtpu-kv-fleet-probe", id(self)))
        oid = page_object_id(self.ns, probe_hash)
        payload = pack_page([0] * P, [probe_hash], page, page, crc)
        pull_ms, lookup_ms = [], []
        try:
            for _ in range(5):
                self.store.delete(oid)
                t0 = time.perf_counter()
                self.store.put(oid, payload)
                raw = self.store.get(oid)
                if raw is not None:
                    unpack_page(raw)
                pull_ms.append((time.perf_counter() - t0) * 1e3)
                t0 = time.perf_counter()
                self.store.contains(oid)
                lookup_ms.append((time.perf_counter() - t0) * 1e3)
        except Exception:  # rtpu-lint: disable=swallowed-exception — an unprobeable store just disables the measured crossover
            return None, None
        finally:
            try:
                self.store.delete(oid)
            except Exception:  # rtpu-lint: disable=swallowed-exception — best-effort probe-object cleanup
                pass
        if not pull_ms:
            return None, None
        return min(pull_ms), min(lookup_ms)

    def crossover_blocks(self) -> Optional[int]:
        """Measured pull-vs-recompute crossover: the contiguous run
        length (blocks) past which pulling beats recomputing. Pulling d
        blocks costs ~lookup + d*pull_page; recomputing them rides the
        suffix prefill at ~d*prefill_block. None until the recompute
        side has a sample; -1 when pulling never pays off."""
        pf, pull = self._pf_ms_blk, self._pull_ms_page
        if pf is None or pull is None:
            return None
        margin = pf - pull
        if margin <= 0:
            return -1
        return max(1, math.ceil((self._lookup_ms or 0.0) / margin))

    def _gate(self) -> int:
        """Effective minimum pullable run: the knob when explicit, the
        measured crossover when 'auto' (optimistic single-block pulls
        until the recompute side has a sample)."""
        g = self.min_blocks
        if isinstance(g, int):
            return max(0, g)
        co = self.crossover_blocks()
        if co is None:
            return 1
        if co < 0:
            return 1 << 30
        return co
