"""Fleet KV-cache economy: tiered prefix-page objects (PR 18).

Store tier (no jax): deterministic page object ids, the pack/unpack
codec's corruption rejection, and the LocalKVPageStore LRU cap.

Engine tier (store-free, tier-1): evict -> spill -> re-install must be
TOKEN-IDENTICAL to pure recompute on a fresh engine sharing only the
page store; corrupted payloads and chain mismatches are rejected
without hurting output or leaking slots; tier transitions balance
under RTPU_DEBUG_RES; fleet-off engines stay byte-identical to today.

Cluster tier (needs the native store lib): spilled pages ride the real
shm arena + sharded head directory, and survive a SIGKILL'd replica —
the churn win the whole tier exists for.
"""

import time

import numpy as np
import pytest

BLOCK = 8


def _engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    base = dict(max_batch=1, max_len=96, prompt_buckets=[8, 16, 32],
                decode_chunk=4, seed=0, prefix_block=BLOCK)
    base.update(kw)
    return LLMEngine(**base)


def _store(cap=64 << 20):
    from ray_tpu.serve.engine.kv_fleet import LocalKVPageStore

    return LocalKVPageStore(capacity_bytes=cap)


P1 = list(range(1, 33))      # 32 tokens = 4 complete blocks @ BLOCK=8
P2 = list(range(100, 132))   # disjoint: admitting it evicts P1's slot


def _wait_objects(store, n, timeout=30.0):
    """Spill packing/putting happens on the engine's spill worker —
    poll until the store holds >= n objects."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if store.stats()["objects"] >= n:
            return
        time.sleep(0.01)
    raise AssertionError(
        f"store never reached {n} objects: {store.stats()}")


def _spill_from_fresh_engine(store, **kw):
    """Run P1 then P2 through a fleet engine with one slot: admitting
    P2 evicts P1's resident prefix, spilling its complete blocks into
    ``store``. Returns (engine, P1 reference tokens)."""
    eng = _engine(kv_fleet_min_prefix_blocks=0, kv_fleet_store=store,
                  **kw)
    ref = eng.generate(P1, max_new_tokens=8)
    eng.generate(P2, max_new_tokens=8)
    _wait_objects(store, 4)  # P1's 4 complete blocks (prompt side)
    return eng, ref


# ------------------------------------------------------------ store tier


def test_page_object_id_deterministic_and_namespaced():
    from ray_tpu.serve.engine.kv_fleet import page_object_id

    ns_a, ns_b = b"a" * 20, b"b" * 20
    oid = page_object_id(ns_a, 12345)
    assert oid.binary() == page_object_id(ns_a, 12345).binary()
    assert len(oid.binary()) == 28
    assert oid.binary() != page_object_id(ns_a, 12346).binary()
    # Same chain hash under a different model fingerprint must resolve
    # to a DIFFERENT object: cross-model KV reuse is unreachable.
    assert oid.binary() != page_object_id(ns_b, 12345).binary()
    assert page_object_id(ns_a, -7)  # negative Python hashes are fine


def test_fleet_namespace_tracks_model_identity():
    from ray_tpu.models import llama
    from ray_tpu.serve.engine.kv_fleet import fleet_namespace

    cfg = llama.tiny_config(max_seq_len=96)
    base = fleet_namespace(cfg, 8, None, 0)
    assert base == fleet_namespace(cfg, 8, None, 0)
    assert base != fleet_namespace(cfg, 16, None, 0)      # block size
    assert base != fleet_namespace(cfg, 8, "int8", 0)     # quantize
    assert base != fleet_namespace(cfg, 8, None, 1)       # param seed


def test_pack_unpack_roundtrip_and_corruption_rejected():
    import zlib

    from ray_tpu.serve.engine.kv_fleet import pack_page, unpack_page

    k = np.arange(2 * 4 * 8 * 16, dtype=np.float32).reshape(2, 4, 8, 16)
    v = k * 2.0
    crc = zlib.crc32(k.tobytes()) ^ zlib.crc32(v.tobytes())
    raw = pack_page(list(range(8)), [11, 22], k, v, crc)
    page = unpack_page(raw)
    assert page is not None
    assert page["tokens"] == list(range(8))
    assert page["chain"] == [11, 22]
    np.testing.assert_array_equal(page["k_page"], k)
    np.testing.assert_array_equal(page["v_page"], v)
    # Flip one payload byte: the CRC covers the page BYTES, so decode
    # fails closed (None == treat as a store miss).
    bad = bytearray(raw)
    bad[-9] ^= 0xFF
    assert unpack_page(bytes(bad)) is None
    assert unpack_page(b"junk") is None
    assert unpack_page(raw[:40]) is None


def test_local_store_lru_byte_cap():
    from ray_tpu.serve.engine.kv_fleet import (LocalKVPageStore,
                                               page_object_id)

    store = LocalKVPageStore(capacity_bytes=3000)
    ns = b"n" * 20
    oids = [page_object_id(ns, i) for i in range(4)]
    for oid in oids:
        assert store.put(oid, b"x" * 1000)
    assert not store.put(oids[-1], b"dup")  # dedupe: second put is a no-op
    st = store.stats()
    assert st["bytes"] <= 3000 and st["evictions"] >= 1
    assert not store.contains(oids[0])  # oldest evicted first
    assert store.contains(oids[-1])
    assert store.get(oids[-1]) == b"x" * 1000
    assert store.delete(oids[-1]) and not store.contains(oids[-1])


# ------------------------------------------------------------ engine tier


def test_evict_spill_reinstall_token_identity():
    """The tentpole: blocks evicted from engine A's HBM spill into the
    shared page tier; a FRESH engine B (cold HBM, same model) pulls
    them back through install_page + chain verify and produces
    token-identical greedy output to pure recompute."""
    store = _store()
    eng_a, ref = _spill_from_fresh_engine(store)
    try:
        assert eng_a.stats()["kv_fleet_spilled_blocks"] >= 4
        eng_b = _engine(kv_fleet_min_prefix_blocks=0,
                        kv_fleet_store=store)
        try:
            out = eng_b.generate(P1, max_new_tokens=8)
            assert out["token_ids"] == ref["token_ids"]
            st = eng_b.stats()
            assert st["kv_fleet_hits"] == 1
            # Reuse is clamped to len(prompt)-1 like the local cache:
            # 3 of the 4 spilled blocks install, the last token prefills.
            assert st["kv_fleet_pulled_blocks"] == 3
            assert st["kv_fleet_tokens_reused"] == 3 * BLOCK
            assert out["cached_prefix_len"] == 3 * BLOCK
            assert eng_b.kv.free_slots() == eng_b.max_batch
        finally:
            eng_b.close()
    finally:
        eng_a.close()


def test_corrupted_payload_rejected_recomputes():
    """Bit-rot in the tier store (CRC mismatch) must read as a miss:
    output stays token-identical via recompute and the admission's
    slot is unharmed."""
    store = _store()
    eng_a, ref = _spill_from_fresh_engine(store)
    eng_a.close()
    # Corrupt EVERY spilled payload in place.
    with store._lock:
        for key, raw in list(store._objs.items()):
            bad = bytearray(raw)
            bad[-9] ^= 0xFF
            store._objs[key] = bytes(bad)
    eng_b = _engine(kv_fleet_min_prefix_blocks=0, kv_fleet_store=store)
    try:
        out = eng_b.generate(P1, max_new_tokens=8)
        assert out["token_ids"] == ref["token_ids"]
        st = eng_b.stats()
        assert st["kv_fleet_hits"] == 0
        assert st["kv_fleet_rejects"] >= 1
        assert eng_b.kv.free_slots() == eng_b.max_batch
    finally:
        eng_b.close()


def test_chain_mismatch_rejected_recomputes():
    """A payload whose bytes are intact but whose chain prefix
    disagrees with the prompt's (hash collision / wrong-prefix object)
    is rejected by the chain-verify seam, not installed."""
    from ray_tpu.serve.engine.kv_fleet import (fleet_namespace,
                                               pack_page,
                                               page_object_id,
                                               unpack_page)
    from ray_tpu.serve.engine.kv_manager import chain_hashes

    store = _store()
    eng_a, ref = _spill_from_fresh_engine(store)
    ns = fleet_namespace(eng_a.cfg, BLOCK, None, 0)
    eng_a.close()
    want = chain_hashes(P1, BLOCK)
    oid = page_object_id(ns, want[0])
    page = unpack_page(store.get(oid))
    assert page is not None
    store.delete(oid)
    # Valid CRC, wrong chain: only the verify seam can catch this.
    store.put(oid, pack_page(page["tokens"], [123456789],
                             page["k_page"], page["v_page"],
                             page["crc"]))
    eng_b = _engine(kv_fleet_min_prefix_blocks=0, kv_fleet_store=store)
    try:
        out = eng_b.generate(P1, max_new_tokens=8)
        assert out["token_ids"] == ref["token_ids"]
        st = eng_b.stats()
        assert st["kv_fleet_hits"] == 0 and st["kv_fleet_rejects"] >= 1
        assert eng_b.kv.free_slots() == eng_b.max_batch
    finally:
        eng_b.close()


def test_min_prefix_blocks_gate_blocks_short_pulls():
    store = _store()
    eng_a, ref = _spill_from_fresh_engine(store)
    eng_a.close()
    # Only 3 blocks are pullable (len-1 clamp); a floor of 4 vetoes.
    eng_b = _engine(kv_fleet_min_prefix_blocks=4, kv_fleet_store=store)
    try:
        out = eng_b.generate(P1, max_new_tokens=8)
        assert out["token_ids"] == ref["token_ids"]
        assert eng_b.stats()["kv_fleet_hits"] == 0
    finally:
        eng_b.close()


def test_fleet_off_is_byte_identical_surface():
    """The default (-1) builds NOTHING new: no transfer programs on a
    colocated engine, no spill hook, no fleet snapshot/stats keys."""
    eng = _engine()
    try:
        assert eng.fleet is None
        assert eng.kv.spill_hook is None
        assert eng.loop.kv_page == 0
        assert "kv_fleet_hits" not in eng.stats()
        snap = eng.load_snapshot()
        assert "fleet_kv_blocks" not in snap
        assert "fleet_kv_hashes" not in snap
    finally:
        eng.close()


def test_fleet_snapshot_and_crossover_stat():
    store = _store()
    eng_a, _ref = _spill_from_fresh_engine(store)
    try:
        snap = eng_a.load_snapshot()
        assert snap["fleet_kv_blocks"] >= 4
        assert len(snap["fleet_kv_hashes"]) >= 4
        st = eng_a.stats()
        # Pull-side costs are measured at engine start; the crossover
        # key is always present on a fleet engine (None until the
        # recompute side has its first post-compile sample).
        assert "kv_pull_vs_recompute_crossover_blocks" in st
        assert st["kv_fleet_pull_ms_per_page"] > 0.0
        co = st["kv_pull_vs_recompute_crossover_blocks"]
        assert co is None or co == -1 or co >= 1
    finally:
        eng_a.close()


def test_fleet_tier_transitions_balance_under_res_debug(monkeypatch):
    """RTPU_DEBUG_RES: every kv_page_obj acquire (a block exported for
    spill, a payload pulled for install) is released by the time the
    engines close — an abandoned tier transition is a leak."""
    monkeypatch.setenv("RTPU_DEBUG_RES", "1")
    from ray_tpu.devtools import res_debug

    res_debug.reset()
    store = _store()
    eng_a, ref = _spill_from_fresh_engine(store)
    eng_b = _engine(kv_fleet_min_prefix_blocks=0, kv_fleet_store=store)
    out = eng_b.generate(P1, max_new_tokens=8)
    assert out["token_ids"] == ref["token_ids"]
    assert eng_b.stats()["kv_fleet_hits"] == 1
    eng_a.close()
    eng_b.close()
    assert not res_debug.violations(), res_debug.violations()
    assert res_debug.outstanding("kv_page_obj").get("kv_page_obj", 0) \
        == 0
    res_debug.reset()


def test_eviction_under_preemption_cross_replica_resume():
    """ROADMAP carry-forward: a PREEMPTED session's parked KV pages are
    evicted under memory pressure, spill into the shared fleet store,
    and the session resumes TOKEN-IDENTICALLY on a DIFFERENT replica
    that pulls them back — priority park/resume (PR 19) composed with
    the spill tier (PR 18). Replica A never resumes the victim; the
    continuation (prompt + confirmed tokens, remaining budget) runs on
    replica B against the store alone."""
    eng_ref = _engine()
    try:
        ref = eng_ref.generate(P1, max_new_tokens=24)["token_ids"]
    finally:
        eng_ref.close()

    store = _store()
    eng_a = _engine(kv_fleet_min_prefix_blocks=0, kv_fleet_store=store)
    # The victim stays parked on A (the replica it must leave): resume
    # is disabled, so only the cross-replica continuation can finish it.
    eng_a.preemption.resume = lambda: None
    try:
        lo = eng_a._make_request(P1, 24, None, stream=True, priority=0)
        eng_a._queue.put(lo)
        # First streamed token: lo holds the slot with sunk decode work
        # — the continuation below must splice, not recompute from zero.
        kind, val = lo.stream_queue.get(timeout=120)
        assert kind not in ("done", "error"), (kind, val)
        hi = eng_a._make_request(list(range(200, 216)), 8, None,
                                 priority=5)
        eng_a._queue.put(hi)
        deadline = time.time() + 120
        while not eng_a.preemption.parked:
            assert time.time() < deadline, "lo never parked"
            time.sleep(0.001)
        hi.future.result(timeout=120)
        # Memory pressure on A: a disjoint admission storms the slot
        # pool, evicting the parked session's resident prefix rows —
        # their complete blocks spill into the shared store.
        eng_a.generate(P2, max_new_tokens=8)
        _wait_objects(store, 4)  # the victim's 4 complete prompt blocks
        assert eng_a.preemption.preempts >= 1
        assert eng_a.preemption.parked[0] is lo
        prefix = list(lo.prompt_ids) + list(lo.generated)
        remaining = lo.remaining()
        assert lo.generated and remaining > 0
    finally:
        eng_a.close()

    eng_b = _engine(kv_fleet_min_prefix_blocks=0, kv_fleet_store=store)
    try:
        out = eng_b.generate(prefix, max_new_tokens=remaining)
        st = eng_b.stats()
    finally:
        eng_b.close()
    # Token identity across park + evict + spill + cross-replica pull.
    assert list(lo.generated) + out["token_ids"] == ref
    # ...and the resume really rode the fleet tier, not pure recompute.
    assert st["kv_fleet_hits"] >= 1
    assert st["kv_fleet_pulled_blocks"] >= 1


def test_router_fleet_term_scores_spilled_residency():
    """Score identity at weight 0 (the default) and a fleet boost when
    the deployment opts in — on a __new__-built Router, the satellite's
    compat contract."""
    from ray_tpu.serve._private.router import Router
    from ray_tpu.serve.engine.kv_manager import chain_hashes

    prompt = list(range(48))
    chain = chain_hashes(prompt, BLOCK)
    cold = {"slots": 4, "waiting": 0, "prefix_block_size": BLOCK}
    warm = dict(cold, fleet_kv_hashes=frozenset(chain))

    r = Router.__new__(Router)
    r._inflight = {}
    s_cold, _ = r._score("a", cold, chain, len(prompt))
    s_warm, _ = r._score("b", warm, chain, len(prompt))
    assert s_cold == s_warm  # default weight 0: byte-identical scores

    r._weights = {"fleet": 1.0}
    s_cold, _ = r._score("a", cold, chain, len(prompt))
    s_warm, d = r._score("b", warm, chain, len(prompt))
    assert s_warm > s_cold
    assert d == 0  # fleet residency is NOT an HBM prefix match
    # An HBM-resident prefix must still outrank the same depth held
    # only in the fleet tier (a pull costs a store roundtrip).
    r._weights = {"prefix": 1.5, "fleet": 0.75}
    hbm = dict(cold, prefix_hashes=frozenset(chain))
    s_hbm, d_hbm = r._score("c", hbm, chain, len(prompt))
    assert s_hbm > s_warm and d_hbm == len(chain)


# ----------------------------------------------------------- cluster tier


@pytest.fixture(scope="module")
def fleet_cluster(native_store):
    import ray_tpu
    import ray_tpu.serve as serve

    rt = ray_tpu.init(num_cpus=16)
    yield rt
    serve.shutdown()
    ray_tpu.shutdown()


def test_fleet_pages_survive_replica_sigkill(fleet_cluster):
    """Churn: a killed replica's HBM cache dies with it, but its
    SPILLED pages live in the node's shm arena — still pullable, so
    the fleet hit rate survives the restart (ISSUE 18 acceptance)."""
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.models import llama
    from ray_tpu.serve.engine.kv_fleet import (ClusterKVPageStore,
                                               fleet_namespace,
                                               page_object_id,
                                               unpack_page)
    from ray_tpu.serve.engine.kv_manager import chain_hashes
    from ray_tpu.serve.llm import build_llm_deployment

    ek = dict(max_batch=1, max_len=96, prompt_buckets=[8, 16, 32],
              decode_chunk=4, seed=0, prefix_block=BLOCK,
              kv_fleet_min_prefix_blocks=0)
    h = serve.run(build_llm_deployment(name="kvfleet", num_replicas=2,
                                       engine_kwargs=ek))
    refs = {}
    for p in (P1, P2):
        refs[tuple(p)] = h.remote(
            {"prompt_ids": p, "max_new_tokens": 8}).result(timeout=180)
    # Force evictions on every replica that held P1: single-slot
    # engines evict on each new prompt, so one more round of P2/P1
    # guarantees spills on whichever replicas served them.
    for p in (P2, P1, P2):
        out = h.remote({"prompt_ids": p,
                        "max_new_tokens": 8}).result(timeout=180)
        assert out["token_ids"] == refs[tuple(p)]["token_ids"]

    ns = fleet_namespace(llama.tiny_config(max_seq_len=96), BLOCK,
                         None, 0)
    store = ClusterKVPageStore(fleet_cluster)
    want = chain_hashes(P1, BLOCK)

    def pullable():
        return all(
            unpack_page(store.get(page_object_id(ns, hh)) or b"")
            is not None for hh in want[:3])

    deadline = time.time() + 60
    while time.time() < deadline and not pullable():
        time.sleep(0.2)
    assert pullable(), "P1's spilled pages never landed in the store"

    from ray_tpu.serve._private.controller import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    _v, replicas = ray_tpu.get(
        controller.get_replica_set.remote("kvfleet"), timeout=30)
    assert len(replicas) == 2
    ray_tpu.kill(replicas[0])
    # The dead replica's pages must REMAIN pullable from the node store
    # (the whole point of the spill tier)...
    assert pullable()

    # ...and traffic keeps flowing token-identically through the
    # survivor/restart, which can itself pull instead of recomputing.
    # Requests racing the controller's death report may land on the
    # corpse — that window is the router's to close, not this tier's,
    # so transient ActorDiedError retries until the set converges.
    from ray_tpu.exceptions import ActorDiedError

    def gen(p, deadline):
        while True:
            try:
                return h.remote({"prompt_ids": p,
                                 "max_new_tokens": 8}).result(
                                     timeout=180)
            except ActorDiedError:
                if time.time() > deadline:
                    raise
                time.sleep(0.5)

    deadline = time.time() + 120
    for _trip in range(3):
        for p in (P1, P2):
            out = gen(p, deadline)
            assert out["token_ids"] == refs[tuple(p)]["token_ids"]
