"""SLO admission control: unit tier on AdmissionController, e2e tier
through the HTTP proxy over the tiny-cpu LLM engine (2 replicas).
"""

import concurrent.futures as cf
import json
import math
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
import ray_tpu.serve as serve
from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.serve._private.slo import (AdmissionController,
                                        DeploymentOverloadedError)

# ------------------------------------------------------------------ unit


def make_ac(**kw):
    base = dict(budget_ms=100.0, queue_depth=4, queue_timeout_s=1.0,
                window=32, min_samples=4, probe_inflight=1)
    base.update(kw)
    return AdmissionController(**base)


def test_cold_estimator_admits_freely():
    ac = make_ac()
    for _ in range(8):
        ac.acquire("d")
    assert ac.snapshot()["d"]["admitted_total"] == 8


def test_min_samples_zero_empty_window_admits():
    # Regression: min_samples=0 with a budget set used to reach _p99
    # on an empty window (IndexError) and permanently 500 the
    # deployment before a single sample could ever arrive.
    ac = make_ac(min_samples=0)
    ac.acquire("d")
    assert ac.snapshot()["d"]["admitted_total"] == 1


def test_forget_drops_idle_state_only():
    ac = make_ac()
    ac.acquire("scanned-path")
    ac.forget("scanned-path")  # inflight: kept
    assert "scanned-path" in ac.snapshot()
    ac.release("scanned-path")
    ac.forget("scanned-path")  # idle: dropped (404-path leak guard)
    assert "scanned-path" not in ac.snapshot()
    ac.release("never-seen")  # release of unknown name must not create


def test_budget_zero_disables_gating():
    ac = make_ac(budget_ms=0.0)
    for _ in range(4):
        ac.record_ttft("d", 10_000.0)
    ac.acquire("d")
    assert ac.snapshot()["d"]["shed_total"] == 0


def _saturate(ac, name="d", ttft_ms=500.0, n=8):
    for _ in range(n):
        ac.record_ttft(name, ttft_ms)


def test_over_budget_admits_probe_then_sheds_on_full_queue():
    ac = make_ac(queue_depth=0)
    _saturate(ac)
    ac.acquire("d")  # the probe slot keeps samples flowing
    with pytest.raises(DeploymentOverloadedError):
        ac.acquire("d")  # probe busy + queue depth 0 -> immediate shed
    snap = ac.snapshot()["d"]
    assert snap["shed_total"] == 1 and snap["admitted_total"] == 1


def test_queue_timeout_sheds():
    ac = make_ac(queue_depth=4, queue_timeout_s=0.2)
    _saturate(ac)
    ac.acquire("d")  # probe
    t0 = time.monotonic()
    with pytest.raises(DeploymentOverloadedError):
        ac.acquire("d")
    assert 0.15 <= time.monotonic() - t0 <= 2.0
    assert ac.snapshot()["d"]["shed_total"] == 1


def test_queued_request_admitted_on_recovery():
    ac = make_ac(queue_timeout_s=10.0)
    _saturate(ac)
    ac.acquire("d")  # probe occupies the over-budget slot
    admitted = threading.Event()

    def waiter():
        ac.acquire("d")
        admitted.set()

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.2)
    assert not admitted.is_set()  # parked: over budget, probe busy
    # Backlog drains: fresh fast samples slide the window under budget.
    for _ in range(32):
        ac.record_ttft("d", 5.0)
    assert admitted.wait(2.0)
    t.join(timeout=2.0)
    snap = ac.snapshot()["d"]
    assert snap["queued_total"] == 1 and snap["shed_total"] == 0


def test_release_unblocks_next_probe():
    ac = make_ac(queue_timeout_s=10.0)
    _saturate(ac)
    ac.acquire("d")
    admitted = threading.Event()

    def waiter():
        ac.acquire("d")
        admitted.set()

    threading.Thread(target=waiter, daemon=True).start()
    time.sleep(0.1)
    ac.release("d")  # probe finished -> next queued request probes
    assert admitted.wait(2.0)


def _capacity_workload(ac, name, *, clients=16, rounds=20,
                       capacity=2, service_s=0.03):
    """Closed-loop offered load far past a semaphore-capacity server:
    waiting for capacity IS the ttft (plus service)."""
    sem = threading.Semaphore(capacity)

    def client(i):
        for _ in range(rounds):
            try:
                ac.acquire(name)
            except DeploymentOverloadedError:
                continue
            t0 = time.monotonic()
            with sem:
                ttft = ((time.monotonic() - t0) + service_s) * 1e3
                time.sleep(service_s)
            ac.record_ttft(name, ttft)
            ac.release(name)

    with cf.ThreadPoolExecutor(clients) as pool:
        list(pool.map(client, range(clients)))


def test_admitted_ttft_bounded_under_overload():
    """The acceptance property, isolated from engine noise: 16
    closed-loop clients against capacity 2 at 30 ms service sit at
    ~240 ms per request un-gated; with admission the steady-state
    ADMITTED requests run at probe concurrency, overflow sheds, and
    the recorded-TTFT distribution stays near the budget."""
    budget = 120.0
    gated = make_ac(budget_ms=budget, queue_depth=3, queue_timeout_s=0.3,
                    window=64, min_samples=4)
    _capacity_workload(gated, "svc")
    snap = gated.snapshot()["svc"]
    assert snap["shed_total"] > 0, "overload never shed"
    assert snap["admitted_total"] > 0
    # Steady state (the window slid past the cold-start wave — those
    # requests are admitted by definition, the estimator had no samples
    # yet): the median admitted request stays within budget, the tail
    # bounded by the breach samples that close the gate.
    assert snap["p50_ttft_ms"] <= budget, snap
    assert snap["p99_ttft_ms"] <= budget * 3.0, snap

    # Comparative control: the identical workload with the gate off
    # runs its p99 MANY multiples over budget (semaphore barging keeps
    # the un-gated median at pure service time while starved threads
    # rack up second-scale waits — exactly the runaway tail the gate
    # exists to cut).
    ungated = make_ac(budget_ms=0.0)
    _capacity_workload(ungated, "svc", rounds=8)
    usnap = ungated.snapshot()["svc"]
    assert usnap["shed_total"] == 0
    assert usnap["p99_ttft_ms"] > budget * 3.0, (snap, usnap)
    assert usnap["p99_ttft_ms"] > snap["p99_ttft_ms"] * 2.0, (snap, usnap)


# ------------------------------------------------------------------- e2e

BUDGET_MS = 60.0


@pytest.fixture(scope="module")
def llm_app(native_store):
    from ray_tpu.serve.llm import build_llm_deployment

    rt = ray_tpu.init(num_cpus=12, _system_config={
        "serve_slo_ttft_budget_ms": BUDGET_MS,
        "serve_slo_queue_depth": 2,
        "serve_slo_queue_timeout_s": 1.0,
        "serve_slo_min_samples": 6,
        "serve_slo_window": 32,
    })
    handle = serve.run(build_llm_deployment(
        name="slollm", num_replicas=2,
        engine_kwargs={"max_batch": 2, "max_len": 64,
                       "prompt_buckets": [16]}),
        name="slollm")
    # Warm every replica's prefill/decode compile OFF the measured path
    # (and off the admission window): direct replica RPCs.
    controller = ray_tpu.get_actor("rtpu-serve-controller")
    replicas = ray_tpu.get(controller.get_replicas.remote("slollm"),
                           timeout=30)
    warm = {"prompt_ids": [3, 1, 4, 1, 5, 9, 2, 6], "max_new_tokens": 2}
    ray_tpu.get([r.handle_request.remote("__call__", (warm,), {})
                 for r in replicas], timeout=600)
    _proxy, port = serve.start_http()
    yield handle, f"http://127.0.0.1:{port}"
    serve.shutdown()
    ray_tpu.shutdown()


def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_routing_policy_does_not_change_outputs(llm_app):
    """Greedy engine outputs are a function of the request, never of
    the replica the router picked (same seed -> same weights)."""
    handle, _url = llm_app
    prompt = {"prompt_ids": [7, 7, 2, 9, 7, 7, 2], "max_new_tokens": 8}
    outs = {}
    old = cfg.serve_router_policy
    try:
        for policy in ("scored", "pow2", "random"):
            cfg.set("serve_router_policy", policy)
            outs[policy] = [
                handle.remote(dict(prompt)).result(timeout=120)
                ["token_ids"] for _ in range(3)]
    finally:
        cfg.set("serve_router_policy", old)
    assert outs["scored"] == outs["pow2"] == outs["random"]


def test_overload_sheds_503_and_bounds_admitted_ttft(llm_app):
    """Closed-loop clients past capacity: the gate sheds observably, and
    the typical ADMITTED request is served inside the budget, where
    un-gated every request would sit at the saturation latency.

    The load is sized from the service time measured here, not from a
    machine remembered: the tiny engine answers in ~15 ms on today's
    CPU, and the 24 clients x 24 tokens this test was written with
    (PR 9, "second-plus scale") saturate at ~50 ms — under the old
    300 ms budget nothing was ever shed."""
    _handle, url = llm_app
    payload = {"prompt_ids": [1, 2, 3, 4, 5, 6], "max_new_tokens": 56}
    unloaded = []
    for _ in range(5):
        t0 = time.perf_counter()
        assert _post(f"{url}/slollm", payload)[0] == 200
        unloaded.append((time.perf_counter() - t0) * 1e3)
    l1_ms = sorted(unloaded)[2]
    # Un-gated, N closed-loop clients over the 2x2 engine slots sit at
    # N/4 service times: size N so that is at least 4x the budget.
    slots = 4
    clients = max(24, min(96, math.ceil(4 * BUDGET_MS * slots / l1_ms)))
    statuses = []
    lock = threading.Lock()

    def client(i):
        mine = dict(payload, prompt_ids=[1 + (i % 7), 2, 3, 4, 5, 6])
        for _ in range(12):
            status, _body = _post(f"{url}/slollm", mine)
            with lock:
                statuses.append(status)
            if status == 503:
                time.sleep(0.02)  # a shed client backs off

    with cf.ThreadPoolExecutor(clients) as pool:
        list(pool.map(client, range(clients)))
    with urllib.request.urlopen(f"{url}/-/slo", timeout=10) as r:
        slo = json.load(r)["slollm"]
    assert set(statuses) <= {200, 503}, (set(statuses), slo)
    assert statuses.count(200) > 0, (statuses, slo)
    # Past-capacity offered load must be OBSERVABLY shed (503 + counter),
    # not absorbed as unbounded queueing.
    assert statuses.count(503) > 0, (clients, l1_ms, slo)
    assert slo["shed_total"] >= statuses.count(503)
    assert slo["shed_total"] + slo["admitted_total"] >= len(statuses)
    assert slo["inflight"] == 0 and slo["queued"] == 0, slo
    # The typical admitted request is served inside the budget (or, on a
    # machine slower than the budget, near its unloaded time) — un-gated
    # it would sit at clients/slots service times, 4x the budget or
    # more. No bound on p99: the gate is bang-bang, a reopen admits one
    # burst, and the window may hold it; the steady-state p99 property
    # is the unit tier's (test_admitted_ttft_bounded_...).
    assert slo["p50_ttft_ms"] <= max(BUDGET_MS, 3 * l1_ms), (l1_ms, slo)
