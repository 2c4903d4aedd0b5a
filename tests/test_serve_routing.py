"""Scored (prefix-affinity / queue / KV) routing + router lifecycle.

Unit tier drives Router directly with injected replica sets and load
snapshots (no cluster: choose() only RPCs when unseeded). Cluster tier
covers the controller snapshot push end-to-end and the
controller-replacement re-resolve path.
"""

import random
import threading
import time

import pytest

import ray_tpu
import ray_tpu.serve as serve
from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.serve._private.router import Router
from ray_tpu.serve.engine.kv_manager import chain_hashes


def make_router(replicas, loads=None, policy="scored"):
    """A seeded Router with no controller and no poller thread."""
    r = Router.__new__(Router)
    from ray_tpu.devtools.lock_debug import make_lock

    r._controller = None
    r._deployment = "unit"
    r._lock = make_lock("serve.router._lock")
    r._replicas = []
    r._version = -1
    r._load_gen = -1
    r._loads = {}
    r._inflight = {}
    r._model_affinity = {}
    r._scored_routes = 0
    r._pow2_routes = 0
    r._affinity_routes = 0
    r._poller_started = True  # unit mode: never spawn the long-poller
    r._poll_thread = None
    r._stopped = False
    r._apply(1, replicas, 1, loads)
    return r


def snap(**kw):
    base = {"ts": time.time(), "queue_depth": 0, "waiting": 0,
            "slots": 4, "kv_free_blocks": 8, "kv_total_blocks": 8,
            "prefix_block_size": 4, "prefix_hashes": []}
    base.update(kw)
    return base


@pytest.fixture(autouse=True)
def _scored_policy():
    old = cfg.serve_router_policy
    cfg.set("serve_router_policy", "scored")
    yield
    cfg.set("serve_router_policy", old)


def test_scored_prefers_prefix_affinity():
    prompt = list(range(16))
    chain = chain_hashes(prompt, 4)
    r = make_router(
        ["a", "b", "c"],
        [snap(), snap(prefix_hashes=chain), snap()])
    for _ in range(8):
        choice = r.choose(prefix_tokens=prompt)
        assert choice == "b"
        r.done(choice)
    st = r.stats()
    assert st["scored_routes"] == 8
    assert st["affinity_routes"] == 8
    assert st["pow2_routes"] == 0


def test_deeper_prefix_match_wins():
    prompt = list(range(16))
    chain = chain_hashes(prompt, 4)  # 4 blocks
    r = make_router(
        ["shallow", "deep"],
        [snap(prefix_hashes=chain[:1]), snap(prefix_hashes=chain[:3])])
    assert r.choose(prefix_tokens=prompt) == "deep"


def test_scored_prefers_short_queue():
    r = make_router(["busy", "idle"],
                    [snap(queue_depth=6), snap(queue_depth=0)])
    assert r.choose() == "idle"


def test_engine_waiting_counts_as_queue_pressure():
    # A saturated engine parks callers inside generate(): its replica
    # gauge alone under-reads, the snapshot's waiting line must count.
    r = make_router(["stuffed", "free"],
                    [snap(queue_depth=1, waiting=9), snap(queue_depth=2)])
    assert r.choose() == "free"


def test_kv_pressure_breaks_ties():
    r = make_router(["full", "roomy"],
                    [snap(kv_free_blocks=0), snap(kv_free_blocks=8)])
    assert r.choose() == "roomy"


def test_affinity_loses_to_overload():
    # Prefix affinity is a preference, not a pin: a hot replica whose
    # queue is deep enough loses to a cold-but-idle one.
    prompt = list(range(16))
    chain = chain_hashes(prompt, 4)
    r = make_router(
        ["hot", "idle"],
        [snap(prefix_hashes=chain, queue_depth=20), snap()])
    assert r.choose(prefix_tokens=prompt) == "idle"


def test_pow2_fallback_when_snapshots_stale(monkeypatch):
    stale = snap()
    stale["ts"] = time.time() - 3600.0
    r = make_router(["a", "b"], [stale, snap()])
    # Deterministic sample: byte-compatible legacy pow-2 must run.
    monkeypatch.setattr(random, "sample", lambda seq, k: list(seq)[:k])
    r._inflight["a"] = 3
    assert r.choose() == "b"  # fewer local in-flight wins
    st = r.stats()
    assert st["pow2_routes"] == 1 and st["scored_routes"] == 0


def test_age_restamps_freshness_on_local_clock():
    """Controller-shipped age_s overrides the replica host's wall-clock
    ts: a snapshot stamped by a skewed replica clock stays fresh when
    its AGE is small, and goes stale when its age is past the TTL —
    freshness never compares clocks across hosts."""
    skewed = snap(age_s=0.1)
    skewed["ts"] = time.time() - 3600.0  # replica clock an hour behind
    r = make_router(["a", "b"], [skewed, snap(age_s=0.1)])
    r.choose()
    assert r.stats()["scored_routes"] == 1  # fresh by age, not by ts

    old = snap(age_s=3600.0)
    old["ts"] = time.time()  # replica clock claims "right now"
    r2 = make_router(["a", "b"], [old, snap(age_s=3600.0)])
    r2.choose()
    assert r2.stats()["pow2_routes"] == 1  # stale by age despite ts


def test_pow2_fallback_byte_compatible_with_legacy():
    """Same RNG stream + same inflight updates => the metrics-absent
    router replays the pre-snapshot policy decision for decision."""
    replicas = [f"r{i}" for i in range(5)]
    r = make_router(replicas, loads=None)  # no snapshots at all

    def legacy(replicas, inflight, rng):
        a, b = rng.sample(replicas, 2)
        return a if inflight.get(a, 0) <= inflight.get(b, 0) else b

    random.seed(1234)
    got = []
    for _ in range(50):
        c = r.choose()
        got.append(c)  # inflight grows: decisions feed back
    random.seed(1234)
    rng = random
    inflight = {}
    want = []
    for _ in range(50):
        c = legacy(replicas, inflight, rng)
        inflight[c] = inflight.get(c, 0) + 1
        want.append(c)
    assert got == want


def test_random_policy():
    cfg.set("serve_router_policy", "random")
    r = make_router(["a", "b", "c"],
                    [snap(queue_depth=99), snap(queue_depth=99), snap()])
    seen = {r.choose() for _ in range(64)}
    assert seen == {"a", "b", "c"}


def test_done_underflow_guard():
    r = make_router(["a", "b"], [snap(), snap()])
    # done() without (or beyond) a matching choose: never negative.
    r.done("a")
    r.done("a")
    assert r._inflight["a"] == 0
    c = r.choose()
    assert r._inflight[c] == 1
    r.done(c)
    r.done(c)
    assert r._inflight[c] == 0
    # Routing still balanced afterwards: with counts sane, the local
    # in-flight feedback spreads un-done() requests across replicas
    # (a leaked negative count would pin everything to one).
    counts = {"a": 0, "b": 0}
    for _ in range(4):
        counts[r.choose()] += 1
    assert counts["a"] >= 1 and counts["b"] >= 1, counts


def test_candidate_subset_bounds_scoring_at_scale():
    """Past serve_router_score_all_max replicas the router scores only
    the O(touched) candidate subset (session pin + inverted prefix
    index + base-score top-K), never the whole pool — and the index
    still finds the one resident replica out of 200."""
    n = 200
    prompt = list(range(16))
    chain = chain_hashes(prompt, 4)
    loads = [snap() for _ in range(n)]
    loads[137] = snap(prefix_hashes=chain)
    r = make_router([f"r{i}" for i in range(n)], loads)
    for _ in range(8):
        choice = r.choose(prefix_tokens=prompt)
        assert choice == "r137"
        r.done(choice)
    st = r.stats()
    assert st["scored_routes"] == 8
    bound = cfg.serve_router_topk + cfg.serve_router_affinity_cands + 1
    assert st["candidates_scored"] <= 8 * bound, st


def test_session_affinity_pin_survives_index_outage():
    """The session-affinity LRU keeps a conversation on its home
    replica even when the inverted index can't surface it (the
    delta-lag window): the pin injects the home into the candidate
    set, and prefix residency wins the score."""
    n = 64
    prompt = list(range(16))
    chain = chain_hashes(prompt, 4)
    loads = [snap() for _ in range(n)]
    loads[50] = snap(prefix_hashes=chain)
    r = make_router([f"r{i}" for i in range(n)], loads)
    assert r.choose(prefix_tokens=prompt, session_key="u") == "r50"
    r.done("r50")
    old = cfg.serve_router_affinity_cands
    cfg.set("serve_router_affinity_cands", 0)  # index blind
    try:
        for _ in range(4):
            assert r.choose(prefix_tokens=prompt,
                            session_key="u") == "r50"
            r.done("r50")
    finally:
        cfg.set("serve_router_affinity_cands", old)
    assert r.stats()["session_affinity_routes"] >= 4


def test_session_affinity_lru_capped():
    old = cfg.serve_router_session_affinity_max
    cfg.set("serve_router_session_affinity_max", 4)
    try:
        n = 32
        r = make_router([f"r{i}" for i in range(n)],
                        [snap() for _ in range(n)])
        for i in range(7):
            r.done(r.choose(session_key=f"s{i}"))
        assert len(r._session_affinity) == 4
        assert "s0" not in r._session_affinity  # oldest aged out
        assert "s6" in r._session_affinity
    finally:
        cfg.set("serve_router_session_affinity_max", old)


def test_apply_delta_updates_routing():
    """A journal delta flips the routing decision in place; deltas
    from a moved replica-set version or with out-of-range indices are
    refused (caller re-seeds with a full payload)."""
    r = make_router(["a", "b"], [snap(queue_depth=9), snap()])
    assert r.choose() == "b"
    r.done("b")
    assert r._apply_delta(1, {0: snap(), 1: snap(queue_depth=9)},
                          load_gen=2)
    assert r.choose() == "a"
    assert r._load_gen == 2
    assert not r._apply_delta(99, {0: snap()})  # version moved
    assert not r._apply_delta(1, {7: snap()})   # index out of range


def test_apply_delta_none_snapshot_drops_entry():
    """snap=None in a delta means the replica missed the sweep: its
    loads entry drops (pow-2 fallback semantics), matching what a full
    payload without that replica would do."""
    r = make_router(["a", "b"], [snap(), snap()])
    assert r._apply_delta(1, {0: None})
    assert "a" not in r._loads and "b" in r._loads


def test_controller_delta_since_unit():
    """_delta_since ships exactly the touched indices past the
    caller's generation; a generation that fell out of the bounded
    journal forces a full resync (None)."""
    import collections

    from ray_tpu.serve._private.controller import ServeController

    d = {"replicas": ["a", "b", "c"],
         "loads": {"a": snap(), "b": snap(), "c": snap()},
         "journal": collections.deque(
             [(5, frozenset({0})), (6, frozenset({1, 2}))], maxlen=8)}
    ds = ServeController._delta_since
    assert set(ds(None, d, 5)) == {1, 2}
    assert ds(None, d, 6) == {}      # caught up: empty delta
    assert ds(None, d, 4) is None    # journal gap: full payload
    assert ds(None, d, 7) is None    # future gen: full payload


def test_stop_joins_poller():
    r = make_router(["a"], [snap()])
    done = threading.Event()

    def fake_poll():
        while not r._stopped:
            time.sleep(0.01)
        done.set()

    t = threading.Thread(target=fake_poll, daemon=True)
    r._poll_thread = t
    t.start()
    r.stop()
    assert done.wait(2.0)
    assert not t.is_alive()


# ---------------------------------------------------------------- cluster


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=16)
    yield rt
    serve.shutdown()
    ray_tpu.shutdown()


def test_snapshots_flow_to_router(cluster):
    @serve.deployment(name="snapflow", num_replicas=2)
    class Echo:
        def __call__(self, x):
            return x

    h = serve.run(Echo.bind())
    assert h.remote(1).result() == 1
    # The controller's sweep runs once per reconcile period; the
    # long-poller must deliver snapshots for BOTH replicas shortly.
    router = h._router
    deadline = time.time() + 30
    while time.time() < deadline:
        with router._lock:
            if len(router._loads) == 2 and router._fresh_loads():
                break
        time.sleep(0.2)
    with router._lock:
        fresh = router._fresh_loads()
    assert fresh is not None and len(fresh) == 2
    for s in fresh.values():
        assert "queue_depth" in s and "ts" in s
    before = router.stats()["scored_routes"]
    assert h.remote(2).result() == 2
    assert router.stats()["scored_routes"] == before + 1
    serve.delete("snapflow")


def test_controller_replacement_reresolves(cluster):
    @serve.deployment(name="cr", num_replicas=1)
    class CR:
        def __call__(self, x):
            return x + 1

    h = serve.run(CR.bind())
    assert h.remote(1).result() == 2
    router = h._router
    old_controller = ray_tpu.get_actor("rtpu-serve-controller")
    with router._lock:
        old_set = list(router._replicas)
    ray_tpu.kill(old_controller)
    # Mid-poll the controller dies; the poller's re-resolve path
    # (failures % 5 == 0 -> get_actor + reseed) must latch onto the
    # REPLACEMENT controller and its new replica set.
    deadline = time.time() + 90
    new_h = None
    while time.time() < deadline and new_h is None:
        try:
            new_h = serve.run(CR.options(num_replicas=2).bind())
        except Exception:
            time.sleep(1.0)  # old name may still be unregistering
    assert new_h is not None, "could not start replacement controller"
    converged = False
    while time.time() < deadline and not converged:
        with router._lock:
            current = list(router._replicas)
        converged = (len(current) == 2
                     and not (set(current) & set(old_set)))
        if not converged:
            time.sleep(0.5)
    assert converged, "router never converged on the new replica set"
    # And the SAME router object routes to the new set.
    assert h.remote(5).result(timeout=30) == 6
    serve.delete("cr")
