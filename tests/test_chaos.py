"""Deterministic chaos harness: FaultPlan engine units (tier-1), RPC-layer
injection over a live server/client pair (tier-1), failure-domain
reconciliation over in-process HeadServer + NodeManagers (skip without a
loadable store lib), and the standing kill-head / kill-node / drop-ack
scenarios over real subprocess clusters (slow).

Parity model: the reference's rpc_chaos.h scripted failures + the
NodeKiller/WorkerKiller chaos actors (_private/test_utils.py) + the GCS
FT suite (test_gcs_fault_tolerance.py), generalized from the
test_dataplane.py chaos-retry idiom.

Every scenario runs under a FIXED plan + seed: re-running it replays the
identical fault sequence (acceptance: 3/3 consecutive green).
"""

from __future__ import annotations

import threading
import time
import uuid

import pytest

from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.devtools import chaos
from ray_tpu.devtools.chaos import ChaosPlanError, FaultPlan


# --------------------------------------------------------------------------
# plan engine (no cluster, no store — tier-1)
# --------------------------------------------------------------------------


def test_plan_parse_defaults_and_repr():
    plan = FaultPlan.parse(
        "drop_request:method=push_*:role=worker;"
        "delay:secs=0.5;kill:role=head:nth=2")
    assert len(plan.rules) == 3
    r0, r1, r2 = plan.rules
    assert (r0.action, r0.method, r0.role, r0.side) == (
        "drop_request", "push_*", "worker", "request")
    assert r1.secs == 0.5 and r1.count is None  # unlimited without nth
    assert r2.nth == 2 and r2.count == 1  # nth rules are one-shot
    assert "kill" in repr(r2) and "nth=2" in repr(r2)


def test_plan_parse_rejects_garbage():
    with pytest.raises(ChaosPlanError, match="unknown chaos action"):
        FaultPlan.parse("explode:method=x")
    with pytest.raises(ChaosPlanError, match="key=value"):
        FaultPlan.parse("delay:whoops")
    with pytest.raises(ChaosPlanError, match="unknown key"):
        FaultPlan.parse("delay:wibble=3")


def test_plan_parse_peer_value_with_colon():
    """The documented peer=<ip:port> form: a ':'-split piece with no
    '=' folds into the preceding value instead of failing the parse."""
    plan = FaultPlan.parse("sever:peer=127.0.0.1:9000:method=echo")
    r = plan.rules[0]
    assert r.peer == "127.0.0.1:9000" and r.method == "echo"
    assert r.decide("", "echo", "request", peer="127.0.0.1:9000")
    assert not plan.rules[0].decide("", "echo", "request",
                                    peer="127.0.0.1:9001")


def test_invalid_plan_disables_loudly_not_fatally(capsys):
    """A malformed RTPU_CHAOS_PLAN must not crash every RPC dispatch in
    the cluster: current_plan() reports it once and runs with chaos
    disabled (the scenario's fault assertions then point at the plan)."""
    try:
        cfg.set("chaos_plan", "explode:method=x")
        assert chaos.current_plan() is None
        assert "invalid plan" in capsys.readouterr().out
        assert chaos.current_plan() is None  # cached; no repeat spam
        assert "invalid plan" not in capsys.readouterr().out
    finally:
        cfg.set("chaos_plan", "")


def test_nth_after_count_semantics():
    plan = FaultPlan.parse("drop_request:method=m:nth=2")
    fires = [bool(plan.actions_for("", "m", "request")) for _ in range(5)]
    assert fires == [False, True, False, False, False]

    plan = FaultPlan.parse("drop_request:method=m:after=2:count=3")
    fires = [bool(plan.actions_for("", "m", "request")) for _ in range(7)]
    assert fires == [False, False, True, True, True, False, False]


def test_role_method_side_scoping():
    plan = FaultPlan.parse("drop_response:method=kill_actor:role=worker")
    assert not plan.actions_for("worker", "kill_actor", "request")
    assert not plan.actions_for("head", "kill_actor", "response")
    assert not plan.actions_for("worker", "heartbeat", "response")
    assert plan.actions_for("worker", "kill_actor", "response")


def test_prob_rules_are_seed_deterministic():
    a = FaultPlan.parse("drop_request:method=m:prob=0.3:seed=7")
    b = FaultPlan.parse("drop_request:method=m:prob=0.3:seed=7")
    seq_a = [bool(a.actions_for("", "m", "request")) for _ in range(200)]
    seq_b = [bool(b.actions_for("", "m", "request")) for _ in range(200)]
    assert seq_a == seq_b
    assert 20 < sum(seq_a) < 120  # actually probabilistic, not all/none
    c = FaultPlan.parse("drop_request:method=m:prob=0.3:seed=8")
    seq_c = [bool(c.actions_for("", "m", "request")) for _ in range(200)]
    assert seq_a != seq_c


def test_plan_cache_tracks_config_changes():
    try:
        cfg.set("chaos_plan", "delay:method=x:secs=0.1")
        p1 = chaos.current_plan()
        assert p1 is not None and p1.rules[0].secs == 0.1
        assert chaos.current_plan() is p1  # cached
        cfg.set("chaos_plan", "delay:method=x:secs=0.2")
        p2 = chaos.current_plan()
        assert p2 is not p1 and p2.rules[0].secs == 0.2
    finally:
        cfg.set("chaos_plan", "")
    assert chaos.current_plan() is None
    assert not chaos.chaos_enabled()


def test_plan_rearm_after_clear_resets_counters():
    """chaos_plan='' then the SAME plan string again must re-parse with
    fresh counters — a spent nth-rule from the previous arming must not
    silently disable the re-armed plan."""
    plan_str = "drop_request:method=m:nth=1"
    try:
        cfg.set("chaos_plan", plan_str)
        assert chaos.current_plan().actions_for("", "m", "request")
        cfg.set("chaos_plan", "")
        assert chaos.current_plan() is None
        cfg.set("chaos_plan", plan_str)
        assert chaos.current_plan().actions_for("", "m", "request"), \
            "re-armed plan inherited spent counters"
    finally:
        cfg.set("chaos_plan", "")


# --------------------------------------------------------------------------
# protocol integration (real sockets, no cluster — tier-1)
# --------------------------------------------------------------------------


class _EchoHandler:
    chaos_role = "node"
    # Local classification (RTPU_DEBUG_RPC witness + dist lint): echo is
    # a pure function, safe to retry/re-deliver.
    extra_retry_safe_rpcs = frozenset({"echo"})

    def __init__(self):
        self.calls = 0

    def rpc_echo(self, conn, x):
        self.calls += 1
        return x

    def rpc_ping(self, conn):  # name IS in RETRY_SAFE_RPCS
        return "pong"


@pytest.fixture
def rpc_pair():
    from ray_tpu.cluster.protocol import RpcClient, RpcServer

    h = _EchoHandler()
    server = RpcServer(h).start()
    client = RpcClient(server.address)
    yield h, server, client
    cfg.set("chaos_plan", "")
    cfg.set("rpc_chaos_failure_prob", 0.0)
    client.close()
    server.stop()


def test_drop_request_then_retry_recovers(rpc_pair):
    h, _s, client = rpc_pair
    cfg.set("chaos_plan", "drop_request:role=node:method=echo:nth=1")
    with pytest.raises(TimeoutError):
        client.call("echo", 1, timeout=0.5)
    assert h.calls == 0  # the handler never saw the dropped request
    assert client.call("echo", 2, timeout=10) == 2  # one-shot rule spent


def test_drop_response_runs_handler_but_loses_reply(rpc_pair):
    h, _s, client = rpc_pair
    cfg.set("chaos_plan", "drop_response:method=echo:nth=1")
    with pytest.raises(TimeoutError):
        client.call("echo", 1, timeout=0.5)
    assert h.calls == 1  # side effect happened; only the ack was lost
    assert client.call("echo", 2, timeout=10) == 2


def test_delay_rule_adds_latency(rpc_pair):
    _h, _s, client = rpc_pair
    cfg.set("chaos_plan", "delay:method=echo:secs=0.4:count=1")
    t0 = time.monotonic()
    assert client.call("echo", 3, timeout=10) == 3
    assert time.monotonic() - t0 >= 0.35
    t0 = time.monotonic()
    assert client.call("echo", 4, timeout=10) == 4  # count spent
    assert time.monotonic() - t0 < 0.3


def test_sever_kills_connection_and_retrying_call_recovers(rpc_pair):
    from ray_tpu.cluster.protocol import ConnectionLost

    _h, _s, client = rpc_pair
    cfg.set("chaos_plan", "sever:method=echo:nth=1")
    with pytest.raises((ConnectionLost, TimeoutError)):
        client.call("echo", 1, timeout=5)
    client.reconnect()
    assert client.call("echo", 2, timeout=10) == 2
    # retrying_call rides a sever transparently (reconnect + retry).
    cfg.set("chaos_plan", "sever:method=echo:nth=1")
    assert client.retrying_call("echo", 3, timeout=5) == 3


def test_kill_action_reaches_kill_hook(rpc_pair, monkeypatch):
    _h, _s, client = rpc_pair
    hits = []
    monkeypatch.setattr(chaos, "_kill_self", lambda: hits.append(1))
    cfg.set("chaos_plan", "kill:role=node:method=echo:nth=1")
    with pytest.raises(TimeoutError):
        # Under the monkeypatch the frame is dropped instead of the
        # process dying; the real SIGKILL path is covered by the slow
        # scenarios below.
        client.call("echo", 1, timeout=0.5)
    assert hits == [1]


def test_blind_chaos_only_drops_retry_safe_methods(rpc_pair):
    from ray_tpu.cluster.protocol import RETRY_SAFE_RPCS

    h, _s, client = rpc_pair
    assert "ping" in RETRY_SAFE_RPCS and "echo" not in RETRY_SAFE_RPCS
    cfg.set("rpc_chaos_failure_prob", 1.0)
    # Non-retry-safe method: NEVER blindly dropped, first try lands.
    assert client.call("echo", 7, timeout=10) == 7
    # Retry-safe method: dropped at p=1.
    with pytest.raises(TimeoutError):
        client.call("ping", timeout=0.5)
    cfg.set("rpc_chaos_failure_prob", 0.0)
    assert client.call("ping", timeout=10) == "pong"


def test_retrying_call_outlasts_respawn_window(rpc_pair):
    """A peer that is DOWN for ~2x the backoff-exhaustion time but comes
    back within rpc_retry_min_window_s is ridden out — the pre-fix
    attempt counting gave up in ~3s, less than a head/node respawn."""
    from ray_tpu.cluster.protocol import RpcClient, RpcServer

    h, server, client = rpc_pair
    host, port = server.address.rsplit(":", 1)
    server.stop()  # peer "dies"; the port is gone
    restarted = {}

    def respawn():
        time.sleep(4.0)  # longer than 5 attempts' ~3.1s of backoff
        s2 = RpcServer(h, host=host, port=int(port)).start()
        restarted["server"] = s2

    threading.Thread(target=respawn, daemon=True).start()
    try:
        assert client.retrying_call("echo", 42, timeout=5) == 42
    finally:
        s2 = restarted.get("server")
        if s2 is not None:
            s2.stop()


# --------------------------------------------------------------------------
# failure-domain reconciliation (in-process head + node manager; needs a
# loadable native store lib — skips where the checked-in .so cannot load)
# --------------------------------------------------------------------------


def _node(head_addr: str, resources=None):
    from ray_tpu.cluster.node_manager import NodeManager

    return NodeManager(head_addr, uuid.uuid4().hex,
                       resources or {"CPU": 2.0}, {}, 64 << 20)


class _FakeProc:
    def poll(self):
        return None

    def terminate(self):
        pass

    def kill(self):
        pass

    def wait(self, timeout=None):
        return 0


def test_head_restart_rehydrates_directory_and_reconciles_leases(native_store):
    """The two head-restart invariants, driven synchronously:

    1. holder-set rehydration — a head that restarts with an empty
       object directory relearns this node's copies from the node's
       local mirror on re-registration;
    2. era reconciliation — a lease granted to the DEAD head's in-flight
       actor creation (lessee "head:<old-era>") is returned, while an
       actor-hosting lease survives."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.node_manager import Lease, WorkerProc

    head = HeadServer()
    nm = _node(head.address)
    try:
        old_inc = head.incarnation
        assert nm._head_incarnation == old_inc
        # An owner-published object (the batch routes through the node).
        oid = ObjectID.from_random()
        mv = nm.store.create_buffer(oid, 1024)
        mv[:] = b"x" * 1024
        nm.store.seal(oid)
        nm.rpc_object_batch(None, [("add", oid.binary(), 1024)])
        _wait_until(lambda: head.rpc_object_locations(
            None, oid.binary()), 10, "object never reached the head")

        # Two head-era leases: one mid-creation (no actor), one landed.
        def fake_lease(lid, actor_host):
            w = WorkerProc(_FakeProc(), uuid.uuid4().hex)
            w.ready.set()
            w.address = f"fake:{lid}"
            w.is_actor_host = actor_host
            lease = Lease(lid, w, {"CPU": 1.0}, "main",
                          lessee=f"head:{old_inc}")
            with nm._lock:
                nm._workers[w.worker_id] = w
                nm._leases[lid] = lease
                nm.available["CPU"] -= 1.0
            return lease

        fake_lease("stale-era", actor_host=False)
        fake_lease("actor-host", actor_host=True)

        # Head "restarts": fresh process state on the same port.
        port = int(head.address.rsplit(":", 1)[1])
        head.shutdown()
        head2 = HeadServer(port=port)
        try:
            assert head2.incarnation != old_inc
            assert head2.rpc_object_locations(None, oid.binary()) == []
            # The node's next heartbeat gets False -> re-register ->
            # republish + reconcile.
            _wait_until(lambda: head2.rpc_object_locations(
                None, oid.binary()), 20,
                "holder set never republished after head restart")
            _wait_until(lambda: "stale-era" not in nm._leases, 10,
                        "stale head-era lease never reconciled")
            with nm._lock:
                assert "actor-host" in nm._leases  # landed actor stays
                assert nm.available["CPU"] == 1.0  # stale lease refunded
        finally:
            head2.shutdown()
            head = None  # already shut down
    finally:
        nm.shutdown()
        if head is not None:
            head.shutdown()


def test_pull_survives_severed_holder_connection(native_store):
    """Mid-pull connection loss to the holder (sever on fetch_object
    chunk 2) must not wedge or corrupt the pull: the retry lap
    re-fetches and the object arrives intact (the test_dataplane
    chaos-retry idiom generalized to the pull manager)."""
    import os as _os

    from ray_tpu.core.ids import ObjectID
    from ray_tpu.cluster.head import HeadServer

    head = HeadServer()
    holder = _node(head.address)
    puller = _node(head.address)
    old_chunk = cfg.object_transfer_chunk_bytes
    try:
        oid = ObjectID.from_random()
        data = _os.urandom(3 << 20)
        mv = holder.store.create_buffer(oid, len(data))
        mv[:] = data
        holder.store.seal(oid)
        head.rpc_object_added(None, oid.binary(), holder.node_id,
                              len(data))
        cfg.set("object_transfer_chunk_bytes", 1 << 20)  # 3 chunks
        cfg.set("chaos_plan", "sever:role=node:method=fetch_object:nth=2")
        assert puller.rpc_pull_object(None, oid.binary(), 30000) is True
        buf = puller.store.get(oid, timeout_ms=1000)
        assert bytes(buf.buffer) == data
        buf.release()
    finally:
        cfg.set("chaos_plan", "")
        cfg.set("object_transfer_chunk_bytes", old_chunk)
        puller.shutdown()
        holder.shutdown()
        head.shutdown()


def test_lost_heartbeats_cost_the_miss_window_one_period_each(native_store):
    """Eight heartbeats lost in a row inside a miss window of thirteen
    periods: the node stays alive. A beat whose reply never comes times
    out after one period and the next goes out AT ONCE (a gap of
    0.5 + 8 x 0.5 s here); while the loop also waited out a period
    before it (until PR 31) each lost beat cost two, eight cost
    0.5 + 8 x 1.0 s of a 6.5 s window, the head read a dead node and
    every actor on it died — which 5 % blind RPC chaos did to
    `test_stress.py::test_cross_node_dag_exact_under_chaos` about one
    run in seventeen ("node ... died", `ActorDiedError`)."""
    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.devtools import chaos

    old = (cfg.health_check_period_ms, cfg.health_check_failure_threshold)
    cfg.set("health_check_period_ms", 500)
    cfg.set("health_check_failure_threshold", 13)
    head = HeadServer()
    nm = _node(head.address)
    try:
        info = head._nodes[nm.node_id]
        first = info.last_heartbeat
        _wait_until(lambda: info.last_heartbeat > first, 10,
                    "no heartbeat reached the head")
        cfg.set("chaos_plan",
                "drop_request:role=head:method=heartbeat:count=8")
        t0 = time.monotonic()
        while time.monotonic() - t0 < 9.0:  # past the whole miss window
            assert info.alive, (
                f"node read as dead {time.monotonic() - t0:.1f} s after "
                f"its beats began to be lost")
            time.sleep(0.1)
        assert chaos.current_plan().rules[0]._fired == 8
        assert time.monotonic() - info.last_heartbeat < 3.0, "beats resumed"
    finally:
        cfg.set("chaos_plan", "")
        cfg.set("health_check_period_ms", old[0])
        cfg.set("health_check_failure_threshold", old[1])
        nm.shutdown()
        head.shutdown()


def _wait_until(fn, timeout_s, msg):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.1)
    raise AssertionError(msg)


# --------------------------------------------------------------------------
# standing scenarios (subprocess clusters, SIGKILL faults — slow)
# --------------------------------------------------------------------------


@pytest.fixture
def chaos_cluster(request):
    """A real subprocess cluster booted under a FIXED chaos plan (the
    plan + seed ride RTPU_CHAOS_PLAN env into every spawned process)."""
    import ray_tpu

    plan = request.param

    def boot(num_cpus=2):
        rt = ray_tpu.init(num_cpus=num_cpus,
                          _system_config={"chaos_plan": plan,
                                          "chaos_seed": 42})
        return rt

    yield boot
    import ray_tpu

    ray_tpu.shutdown()
    cfg.set("chaos_plan", "")


@pytest.mark.slow
@pytest.mark.parametrize(
    "chaos_cluster", ["kill:role=head:method=register_actor:nth=2"],
    indirect=True)
def test_scenario_kill_head_mid_submission(chaos_cluster):
    """The head SIGKILLs itself as the 2nd actor registration arrives.
    The supervisor respawns it on the same port with its durable tables;
    the submitter's retrying_call rides the outage; the node republishes
    its holder sets so a pre-kill object stays pullable; no lease leaks."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu.core.runtime_context import require_runtime
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    runtime = chaos_cluster()
    node_b = runtime.add_node(num_cpus=2)
    time.sleep(1.5)

    @rt.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=node_b.node_id, soft=True))
    def produce():
        return np.arange(300_000)

    ref = produce.remote()
    ready, _ = rt.wait([ref], num_returns=1, timeout=90, fetch_local=False)
    assert ready

    @rt.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    old_pid = runtime._head_proc.pid
    c1 = Counter.remote()  # registration 1: survives
    assert rt.get(c1.inc.remote(), timeout=60) == 1
    c2 = Counter.remote()  # registration 2: SIGKILLs the head
    assert rt.get(c2.inc.remote(), timeout=120) == 1
    assert runtime._head_proc.pid != old_pid, "head did not respawn"

    # Fresh work flows, and the restarted head's directory was
    # REHYDRATED: it lists a holder for the pre-kill object (pull rides
    # the directory, not lineage re-execution).
    @rt.remote
    def ping(i):
        return i

    assert rt.get([ping.remote(i) for i in range(8)],
                  timeout=120) == list(range(8))
    _wait_until(
        lambda: runtime.head.retrying_call(
            "object_locations", ref.id().binary(), timeout=10),
        30, "holder set never republished to the restarted head")
    got = rt.get(ref, timeout=90)
    assert got[0] == 0 and got[-1] == 299_999
    _assert_leases_drain(runtime, allowed_actor_hosts=2)


@pytest.mark.slow
@pytest.mark.parametrize(
    "chaos_cluster", ["kill:role=node:method=fetch_object:nth=2"],
    indirect=True)
def test_scenario_kill_holder_mid_chunked_pull(chaos_cluster):
    """The holder node SIGKILLs itself serving chunk 2 of a chunked
    pull. The puller's in-flight sink must not be corrupted; the get()
    completes via lineage re-execution once the head scrubs the dead
    holder from the directory."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    runtime = chaos_cluster()
    node_b = runtime.add_node(num_cpus=2)
    time.sleep(1.5)
    n = 3_000_000  # ~24 MB -> 6 chunks at the default 4 MB

    @rt.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=node_b.node_id, soft=True))
    def produce():
        return np.arange(n)

    ref = produce.remote()
    ready, _ = rt.wait([ref], num_returns=1, timeout=90, fetch_local=False)
    assert ready
    got = rt.get(ref, timeout=120)  # chunk 2 kills the holder mid-pull
    assert got[0] == 0 and got[-1] == n - 1
    assert node_b.proc.poll() is not None, "holder should be dead"
    _assert_leases_drain(runtime, allowed_actor_hosts=0)


@pytest.mark.slow
@pytest.mark.parametrize(
    "chaos_cluster",
    ["drop_response:role=worker:method=kill_actor:count=2"],
    indirect=True)
def test_scenario_dropped_actor_kill_acks(chaos_cluster):
    """The first two kill_actor acks are lost: the head's re-ack loop
    must still land the kill — no zombie actor keeps answering, and the
    actor's worker lease is reclaimed (head.py's 'a chaos-dropped kill
    would leave a zombie actor' comment, now exercised)."""
    import ray_tpu as rt
    from ray_tpu.exceptions import ActorDiedError

    runtime = chaos_cluster()

    @rt.remote
    class Svc:
        def ping(self):
            return "pong"

    a = Svc.remote()
    assert rt.get(a.ping.remote(), timeout=60) == "pong"
    rt.kill(a)
    with pytest.raises(ActorDiedError):
        rt.get(a.ping.remote(), timeout=30)
    _assert_leases_drain(runtime, allowed_actor_hosts=0)


@pytest.mark.slow
@pytest.mark.parametrize(
    "chaos_cluster", ["kill:role=head:method=create_pg:nth=2"],
    indirect=True)
def test_scenario_head_restart_with_inflight_pg_and_queued_leases(
        chaos_cluster):
    """The head dies receiving the 2nd create_pg (in-flight bundle
    reservation) while plain tasks are queued. The respawned head must
    complete the reservation on the client's retry, the queued leases
    must flow, and PG-placed work must run."""
    import ray_tpu as rt
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy)

    runtime = chaos_cluster(num_cpus=4)
    pg1 = placement_group([{"CPU": 1}], strategy="PACK")
    assert pg1.ready(timeout=60)
    old_pid = runtime._head_proc.pid

    @rt.remote
    def ping(i):
        return i

    refs = [ping.remote(i) for i in range(4)]  # queued across the outage
    pg2 = placement_group([{"CPU": 1}, {"CPU": 1}],
                          strategy="PACK")  # kills the head
    assert pg2.ready(timeout=90)
    assert runtime._head_proc.pid != old_pid, "head did not respawn"

    @rt.remote(scheduling_strategy=PlacementGroupSchedulingStrategy(
        placement_group=pg2))
    def inside():
        return "in-pg"

    assert rt.get(inside.remote(), timeout=60) == "in-pg"
    assert rt.get(refs, timeout=120) == list(range(4))
    remove_placement_group(pg2)
    remove_placement_group(pg1)
    _assert_leases_drain(runtime, allowed_actor_hosts=0)


@pytest.fixture
def plain_cluster():
    """Subprocess cluster with NO chaos plan: scenarios drive real
    SIGKILLs from the test body (the all-holders-dead shapes kill two
    processes at once, which the one-process-kills-itself plan grammar
    cannot express)."""
    import ray_tpu

    def boot(num_cpus=2):
        return ray_tpu.init(num_cpus=num_cpus)

    yield boot
    import ray_tpu

    ray_tpu.shutdown()


@pytest.mark.slow
def test_scenario_all_holders_dead_actor(plain_cluster):
    """A registered actor's host NODE and the head die TOGETHER. No
    worker_dead_at report can ever arrive (its target died too), and
    the respawned head recovers the actor ALIVE from sqlite pointing at
    a node that will never re-register. The recovered-ALIVE watch must
    declare it dead after the grace window and re-drive it through
    max_restarts; the caller's queued calls replay onto the new
    incarnation (at-least-once) — PR 8's harness could not pass this
    because the head had no durable actor table and no zombie-ALIVE
    sweep."""
    import os
    import signal

    import ray_tpu as rt
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    runtime = plain_cluster()
    node_b = runtime.add_node(num_cpus=2)
    time.sleep(1.5)

    @rt.remote(max_restarts=2, max_task_retries=-1,
               scheduling_strategy=NodeAffinitySchedulingStrategy(
                   node_id=node_b.node_id, soft=True))
    class Svc:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    a = Svc.remote()
    assert rt.get(a.inc.remote(), timeout=60) == 1
    info = runtime.head.retrying_call("get_actor_info",
                                      a._actor_id.binary(), timeout=15)
    assert info["state"] == "ALIVE"  # placed on node_b (soft affinity)
    head_pid = runtime._head_proc.pid
    # Kill BOTH: the actor's host node first (so its death report has no
    # live head to land on), then the head before its health sweep can
    # notice the node.
    node_b.proc.kill()
    os.kill(head_pid, signal.SIGKILL)
    # Queued during the outage: must park (restart-pending queueing),
    # then replay against the re-created incarnation on node A.
    refs = [a.inc.remote() for _ in range(4)]
    vals = rt.get(refs, timeout=180)
    # Fresh incarnation: counter restarts from 0; exactly-once per
    # incarnation means the four replayed calls count 1..4.
    assert vals == [1, 2, 3, 4], vals
    assert runtime._head_proc.pid != head_pid, "head did not respawn"
    info = runtime.head.retrying_call("get_actor_info",
                                      a._actor_id.binary(), timeout=15)
    assert info["state"] == "ALIVE"
    assert info["restarts"] >= 1
    _assert_leases_drain(runtime, allowed_actor_hosts=1)


@pytest.mark.slow
def test_scenario_all_holders_dead_object_while_head_down(plain_cluster):
    """Every holder of an object dies WHILE the head is down. The
    respawned head's directory rehydrates only from surviving nodes —
    none has a copy — so the owner's get() must fall through to lineage
    re-execution (sqlite brings the control plane back; lineage brings
    the data back)."""
    import os
    import signal

    import numpy as np

    import ray_tpu as rt
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    runtime = plain_cluster()
    node_b = runtime.add_node(num_cpus=2)
    time.sleep(1.5)
    n = 500_000

    @rt.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=node_b.node_id, soft=True))
    def produce():
        return np.arange(n)

    ref = produce.remote()
    ready, _ = rt.wait([ref], num_returns=1, timeout=90, fetch_local=False)
    assert ready
    head_pid = runtime._head_proc.pid
    os.kill(head_pid, signal.SIGKILL)  # head down first...
    node_b.proc.kill()                 # ...then the only holder dies
    got = rt.get(ref, timeout=180)     # recovers via lineage post-respawn
    assert got[0] == 0 and got[-1] == n - 1
    assert runtime._head_proc.pid != head_pid, "head did not respawn"
    _assert_leases_drain(runtime, allowed_actor_hosts=0)


@pytest.mark.slow
def test_scenario_node_death_recreates_actor_and_replays_calls(
        plain_cluster):
    """The one-continuous-story scenario: host node dies (head alive),
    head's health sweep restarts the actor on another node via
    max_restarts, and the caller's unacked calls replay there."""
    import ray_tpu as rt
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    runtime = plain_cluster()
    node_b = runtime.add_node(num_cpus=2)
    time.sleep(1.5)

    @rt.remote(max_restarts=1, max_task_retries=-1,
               scheduling_strategy=NodeAffinitySchedulingStrategy(
                   node_id=node_b.node_id, soft=True))
    class Svc:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    a = Svc.remote()
    assert rt.get(a.inc.remote(), timeout=60) == 1
    refs = [a.inc.remote() for _ in range(6)]
    runtime.kill_node(node_b)
    vals = rt.get(refs, timeout=180)
    # Some of the 6 may have executed on the dying incarnation with
    # results delivered (those keep their old-incarnation values); the
    # rest replay in order onto the fresh one. NONE may fail, and every
    # replayed run must be exactly-once (strictly increasing counter
    # runs — a duplicate execution would repeat or skip a value).
    assert len(vals) == 6
    assert all(isinstance(v, int) for v in vals), vals
    runs = [vals[i] for i in range(len(vals))
            if i == 0 or vals[i] != vals[i - 1] + 1]
    assert len(runs) <= 2, f"more than one incarnation boundary: {vals}"
    # The restarted incarnation answers fresh calls.
    assert rt.get(a.inc.remote(), timeout=60) >= 1
    _wait_until(
        lambda: runtime.head.retrying_call(
            "get_actor_info", a._actor_id.binary(),
            timeout=15)["restarts"] >= 1,
        60, "actor never restarted after node death")
    _assert_leases_drain(runtime, allowed_actor_hosts=1)


@pytest.mark.slow
def test_scenario_rolling_head_upgrade_zero_failures(plain_cluster):
    """The rolling-upgrade scenario (devtools.chaos.run_rolling_upgrade):
    drain -> sqlite checkpoint -> old head releases the port -> new
    incarnation serves, under continuous task + actor-call load.
    Acceptance: ZERO failed client requests — latency may spike while
    requests ride their retry loops across the gap, failures fail."""
    import ray_tpu as rt

    runtime = plain_cluster()

    @rt.remote
    def ping(i):
        return i

    @rt.remote(max_restarts=1, max_task_retries=-1)
    class Echo:
        def hit(self, i):
            return i

    e = Echo.remote()
    assert rt.get(e.hit.remote(-1), timeout=60) == -1

    def request(i):
        if i % 2:
            assert rt.get(ping.remote(i), timeout=120) == i
        else:
            assert rt.get(e.hit.remote(i), timeout=120) == i

    report = chaos.run_rolling_upgrade(runtime, request, clients=2)
    assert report["request_failures"] == [], report["request_failures"]
    assert report["requests_ok"] > 0
    assert report["new_incarnation"] != report["old_incarnation"]
    # The upgraded head serves fresh work and the actor survived.
    assert rt.get(e.hit.remote(99), timeout=60) == 99
    assert rt.get([ping.remote(i) for i in range(4)],
                  timeout=90) == list(range(4))
    _assert_leases_drain(runtime, allowed_actor_hosts=1)


def _assert_leases_drain(runtime, allowed_actor_hosts: int,
                         timeout_s: float = 45.0) -> None:
    """Post-scenario invariant: once the workload drains, every
    non-actor lease is returned (nothing leaked through the faults)."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            census = runtime.head.retrying_call("cluster_leases",
                                                timeout=15)
        except Exception:
            time.sleep(0.5)
            continue
        entries = [v for v in census.values() if isinstance(v, dict)]
        # An unreachable node's census entry is MISSING data, not zero
        # leases: the pass requires every alive node to have answered.
        errors = [v["error"] for v in entries if "error" in v]
        leases = [l for v in entries for l in v.get("leases", ())]
        last = (leases, errors)
        non_actor = [l for l in leases if not l.get("is_actor_host")]
        hosts = [l for l in leases if l.get("is_actor_host")]
        if not errors and not non_actor \
                and len(hosts) <= allowed_actor_hosts:
            return
        time.sleep(0.5)
    raise AssertionError(f"leases leaked after drain: {last}")
