"""Per-tenant QoS: WFQ ordering, token budgets, priority preemption.

Unit tier drives WFQQueue and AdmissionController directly with an
explicit clock (no cluster, no jax). Engine tier (jax, still
cluster-free) proves the preemption park/resume KV round-trip keeps
BOTH the preemptor and the victim token-identical to their solo runs.
"""

import threading
import time

import pytest

from ray_tpu.serve._private.qos import TenantConfig, WFQQueue
from ray_tpu.serve._private.slo import (AdmissionController,
                                        DeploymentOverloadedError)

# ------------------------------------------------------------- WFQ units


def _drain(q, n, now=0.0):
    """Admit up to n heads at a fixed virtual time; returns tenant ids
    in admission order."""
    order = []
    for _ in range(n):
        tk = q.head(now)
        if tk is None:
            break
        q.admit(tk, now)
        order.append(tk.tenant)
    return order


def test_wfq_weighted_ordering():
    """Equal-cost backlogs from a weight-3 and a weight-1 tenant admit
    3:1 — classic WFQ virtual-finish ordering, not arrival order."""
    q = WFQQueue()
    q.configure("a", TenantConfig(weight=3.0), 0.0)
    q.configure("b", TenantConfig(weight=1.0), 0.0)
    for _ in range(12):
        q.submit("a", 10.0, 0.0)
    for _ in range(12):
        q.submit("b", 10.0, 0.0)
    order = _drain(q, 8)
    assert order.count("a") == 6 and order.count("b") == 2, order


def test_wfq_priority_class_strictly_first():
    """A higher priority class admits before ANY lower-class ticket,
    regardless of how favorable the lower class's WFQ tags are."""
    q = WFQQueue()
    q.configure("bulk", TenantConfig(weight=100.0, priority=0), 0.0)
    q.configure("inter", TenantConfig(weight=0.01, priority=5), 0.0)
    for _ in range(4):
        q.submit("bulk", 1.0, 0.0)
    for _ in range(2):
        q.submit("inter", 1000.0, 0.0)
    assert _drain(q, 3) == ["inter", "inter", "bulk"]


def test_wfq_budget_exhaustion_and_refill():
    """A tenant past its token budget goes ineligible (head() skips it)
    until the bucket refills on the clock; other tenants are
    unaffected."""
    q = WFQQueue()
    q.configure("metered", TenantConfig(tokens_per_s=10.0,
                                        burst_tokens=20.0), 0.0)
    q.configure("free", TenantConfig(), 0.0)
    tk = q.submit("metered", 15.0, 0.0)
    assert q.head(0.0) is tk
    q.admit(tk, 0.0)  # bucket: 20 -> 5
    blocked = q.submit("metered", 15.0, 0.0)
    assert q.head(0.0) is None  # 5 < 15: budget-blocked
    # The gate's bounded park: refill ETA = (15 - 5) / 10 tokens/s.
    assert q.next_refill_wait(0.0) == pytest.approx(1.0)
    # An unmetered tenant admits right past the blocked one.
    free = q.submit("free", 50.0, 0.0)
    assert q.head(0.0) is free
    q.admit(free, 0.0)
    # ...and the clock refill makes the blocked head eligible again.
    assert q.head(1.05) is blocked


def test_wfq_oversized_request_needs_full_bucket_only():
    """cost > burst capacity must not deadlock: eligibility is capped
    at the bucket size, so a full bucket admits the oversized request
    (and clamps to zero) instead of blocking it forever."""
    q = WFQQueue()
    q.configure("m", TenantConfig(tokens_per_s=10.0, burst_tokens=20.0),
                0.0)
    tk = q.submit("m", 500.0, 0.0)
    assert q.head(0.0) is tk
    q.admit(tk, 0.0)
    assert q.tenant("m", 0.0).bucket == 0.0


# ------------------------------------------------- admission gate (QoS)


def test_gate_flooding_tenant_sheds_alone():
    """A tenant past its token budget parks and sheds on its own queue
    timeout while an unmetered tenant keeps admitting instantly — the
    flood-isolation contract."""
    ac = AdmissionController(budget_ms=0.0, queue_depth=64,
                             queue_timeout_s=0.3, window=16,
                             min_samples=1, probe_inflight=1)
    ac.configure_tenant("flood", tokens_per_s=1.0, burst_tokens=5.0)
    ac.acquire("d", tenant="flood", cost=5.0)  # burst covers the first
    t0 = time.monotonic()
    with pytest.raises(DeploymentOverloadedError):
        ac.acquire("d", tenant="flood", cost=5.0)  # blocked -> shed
    assert time.monotonic() - t0 >= 0.25
    # The victim tenant is untouched while the flooder is blocked.
    t0 = time.monotonic()
    ac.acquire("d", tenant="good", cost=5.0)
    assert time.monotonic() - t0 < 0.2
    ac.release("d", tenant="good")
    ac.release("d", tenant="flood")
    snap = ac.snapshot()["d"]["tenants"]
    assert snap["flood"]["shed"] == 1
    assert snap["good"]["shed"] == 0


def test_gate_handoff_admission_wakes_parked_winner():
    """Over-budget gate at the probe limit: a parked waiter must be
    admitted IN PLACE by the releasing thread (handoff admission), not
    shed while capacity sits free."""
    ac = AdmissionController(budget_ms=50.0, queue_depth=8,
                             queue_timeout_s=5.0, window=8,
                             min_samples=1, probe_inflight=1)
    ac.record_ttft("d", 500.0)  # p99 over budget: probe trickle only
    ac.acquire("d", tenant="t")  # takes the probe slot
    done = threading.Event()

    def waiter():
        ac.acquire("d", tenant="t")
        done.set()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.15)
    assert not done.is_set()  # parked behind the probe limit
    ac.release("d", tenant="t")  # handoff: the release admits the waiter
    assert done.wait(2.0)
    ac.release("d", tenant="t")
    t.join(5)


def test_gate_per_tenant_queue_depth_bounds_backlog():
    """The park queue is bounded PER TENANT: a flooder filling its own
    line sheds immediately without consuming the shared queue."""
    ac = AdmissionController(budget_ms=0.0, queue_depth=1,
                             queue_timeout_s=0.4, window=8,
                             min_samples=1, probe_inflight=1)
    ac.configure_tenant("flood", tokens_per_s=0.5, burst_tokens=1.0)
    ac.acquire("d", tenant="flood", cost=1.0)
    errs = []

    def blocked():
        try:
            ac.acquire("d", tenant="flood", cost=1.0)
        except DeploymentOverloadedError as e:
            errs.append(str(e))

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.1)  # the first over-budget waiter is parked
    t0 = time.monotonic()
    with pytest.raises(DeploymentOverloadedError, match="queue"):
        ac.acquire("d", tenant="flood", cost=1.0)
    assert time.monotonic() - t0 < 0.2  # shed on arrival, not on timeout
    t.join(5)
    assert len(errs) == 1  # the parked one timed out on its own clock


# ------------------------------------- engine preemption (park/resume)

jax = pytest.importorskip("jax")


@pytest.fixture(scope="module")
def tiny_model():
    from ray_tpu.models import llama

    cfg = llama.tiny_config(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(tiny_model, **kw):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    kw.setdefault("max_batch", 1)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", [8, 16, 32])
    kw.setdefault("decode_chunk", 4)
    return LLMEngine(cfg, params, **kw)


def _ref(tiny_model, prompt, n):
    eng = make_engine(tiny_model)
    try:
        return eng.generate(prompt, max_new_tokens=n)["token_ids"]
    finally:
        eng.close()


def test_priority_preemption_park_resume_token_identity(tiny_model):
    """A slot-starved higher-priority arrival preempts the active
    low-priority request: the victim parks its KV pages and resumes as
    a continuation. BOTH outputs must equal their solo runs — the
    preemptor must not inherit the victim's in-flight decode chunk or
    KV rows (the recycled-slot delivery hazard), and the victim's
    resume replays its remaining budget token-identically."""
    lo_p, hi_p = [5, 9, 2, 7, 7, 1], list(range(1, 17))
    ref_lo = _ref(tiny_model, lo_p, 40)
    ref_hi = _ref(tiny_model, hi_p, 8)
    eng = make_engine(tiny_model)
    try:
        lo = eng._make_request(lo_p, 40, None, priority=0)
        eng._queue.put(lo)
        deadline = time.time() + 120
        # Submit hi the moment lo holds the slot (activation): the
        # widest decode window for the preemption to land in.
        while not any(r is lo for r in eng.scheduler.active):
            assert time.time() < deadline, "lo never activated"
            time.sleep(0.001)
        hi = eng._make_request(hi_p, 8, None, priority=5)
        eng._queue.put(hi)
        out_hi = hi.future.result(timeout=120)
        out_lo = lo.future.result(timeout=120)
    finally:
        eng.close()
    assert eng.preemption.preempts >= 1 and eng.preemption.resumes >= 1
    assert out_hi["token_ids"] == ref_hi
    assert out_lo["token_ids"] == ref_lo
    assert out_lo.get("preempted", 0) >= 1


def test_preemption_parked_kv_witness_balanced(tiny_model, monkeypatch):
    """RTPU_DEBUG_RES: the parked_kv ledger balances across a real
    preempt + resume cycle — every park settles on resume (or on a
    deliberate engine close), so a drained run leaves nothing open."""
    from ray_tpu.devtools import res_debug

    monkeypatch.setenv("RTPU_DEBUG_RES", "1")
    res_debug.reset()
    try:
        eng = make_engine(tiny_model)
        try:
            lo = eng._make_request([5, 9, 2, 7, 7, 1], 40, None,
                                   priority=0)
            eng._queue.put(lo)
            deadline = time.time() + 120
            while not any(r is lo for r in eng.scheduler.active):
                assert time.time() < deadline, "lo never activated"
                time.sleep(0.001)
            hi = eng._make_request(list(range(1, 17)), 8, None,
                                   priority=5)
            eng._queue.put(hi)
            hi.future.result(timeout=120)
            lo.future.result(timeout=120)
        finally:
            eng.close()
        assert eng.preemption.preempts >= 1 and eng.preemption.resumes >= 1
        assert res_debug.outstanding("parked_kv") == {}
        bad = [v for v in res_debug.violations()
               if "parked_kv" in v.get("outstanding", {})]
        assert not bad, bad
    finally:
        res_debug.reset()


def test_preemption_streams_survive_park_resume(tiny_model):
    """The victim's token stream spans the park: stream consumers see
    one uninterrupted, token-identical sequence across preempt +
    resume (the continuation shares the original stream queue)."""
    lo_p, hi_p = [5, 9, 2, 7, 7, 1], list(range(1, 17))
    ref_lo = _ref(tiny_model, lo_p, 40)
    eng = make_engine(tiny_model)
    try:
        lo = eng._make_request(lo_p, 40, None, stream=True, priority=0)
        eng._queue.put(lo)
        got = []
        hi = None
        deadline = time.time() + 240
        while True:
            kind, val = lo.stream_queue.get(timeout=120)
            if kind == "done":
                break
            if kind == "error":
                raise val
            got.append(val)
            if hi is None:  # first streamed token = lo just activated
                hi = eng._make_request(hi_p, 8, None, priority=5)
                eng._queue.put(hi)
            assert time.time() < deadline
        assert hi is not None
        hi.future.result(timeout=120)
    finally:
        eng.close()
    assert eng.preemption.preempts >= 1
    assert got == ref_lo
