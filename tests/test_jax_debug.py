"""RTPU_DEBUG_JAX runtime witness: recompile counting against declared
program budgets, the one-host-sync-per-chunk invariant (spec on/off,
int8 on/off), transfer-guard-clean engine ticks, and the zero-overhead
flag-off path.
"""

from __future__ import annotations

import numpy as np
import pytest

from ray_tpu.devtools import jax_debug

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture
def debug_jax(monkeypatch):
    monkeypatch.setenv("RTPU_DEBUG_JAX", "1")
    jax_debug.reset()
    yield
    jax_debug.reset()


# ------------------------------------------------------------ unit layer


def test_wrap_jit_passthrough_when_disabled(monkeypatch):
    monkeypatch.delenv("RTPU_DEBUG_JAX", raising=False)
    fn = object()
    assert jax_debug.wrap_jit(fn, "x") is fn
    # Sync notes are dict no-ops when off.
    jax_debug.note_host_sync("x")
    assert jax_debug.host_sync_counts() == {}


def test_recompile_witness_counts_and_budget(debug_jax):
    import jax

    f = jax_debug.wrap_jit(jax.jit(lambda x: x + 1), "t.f", budget=1)
    f(np.zeros(2, np.float32))
    f(np.ones(2, np.float32))          # same signature: cache hit
    assert f.program_count == 1
    assert jax_debug.over_budget_reports() == []
    f(np.zeros(3, np.float32))         # new shape: second program
    assert f.program_count == 2
    reports = jax_debug.over_budget_reports()
    assert len(reports) == 1
    assert reports[0]["name"] == "t.f" and reports[0]["budget"] == 1
    assert jax_debug.program_counts()["t.f"] == 2


def test_signature_tracks_dtype_and_structure(debug_jax):
    import jax

    f = jax_debug.wrap_jit(jax.jit(lambda t: t), "t.sig")
    f((np.zeros(2, np.float32),))
    f((np.zeros(2, np.int32),))            # dtype change
    f((np.zeros(2, np.float32), np.zeros(2, np.float32)))  # structure
    assert f.program_count == 3


def test_registry_does_not_pin_dead_witnesses(debug_jax):
    """The registry holds weakrefs: dropping a witness (engine close +
    GC) releases its trace cache and removes it from program_counts —
    a long debug session must not accumulate one program set per
    engine ever built."""
    import gc

    import jax

    f = jax_debug.wrap_jit(jax.jit(lambda x: x + 1), "t.dead")
    f(np.zeros(2, np.float32))
    assert jax_debug.program_counts()["t.dead"] == 1
    del f
    gc.collect()
    assert "t.dead" not in jax_debug.program_counts()


def test_host_sync_counter(debug_jax):
    jax_debug.note_host_sync("engine.decode")
    jax_debug.note_host_sync("engine.decode")
    jax_debug.note_host_sync("engine.prefill")
    assert jax_debug.host_sync_counts() == {"engine.decode": 2,
                                            "engine.prefill": 1}


def test_transfer_guard_disallow_blocks_implicit(debug_jax):
    import jax

    x = jax.device_put(np.ones(2, np.float32))
    with jax_debug.transfer_guard("disallow"):
        # Explicit placement/fetch is allowed...
        y = jax.device_put(np.zeros(2, np.float32))
        jax.device_get(jax.jit(lambda a, b: a + b)(x, y))
        # ...an implicit host operand is not.
        with pytest.raises(Exception, match="[Dd]isallowed"):
            jax.jit(lambda a, b: a + b)(x, np.zeros(2, np.float32))


def test_tick_guard_null_when_unconfigured(monkeypatch):
    monkeypatch.delenv("RTPU_DEBUG_JAX", raising=False)
    with jax_debug.tick_guard():
        pass  # null context
    monkeypatch.setenv("RTPU_DEBUG_JAX", "1")
    monkeypatch.delenv("RTPU_DEBUG_JAX_TRANSFER_GUARD", raising=False)
    with jax_debug.tick_guard():
        pass  # still null: no guard level requested


# ------------------------------------------------------- engine layer


def _engine(**kw):
    from ray_tpu.models import llama
    from ray_tpu.serve.engine.core import InferenceEngine

    cfg = llama.tiny_config(max_seq_len=256)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 128)
    kw.setdefault("prompt_buckets", [16, 32])
    kw.setdefault("decode_chunk", 4)
    return InferenceEngine(cfg, **kw)


def _drive(eng, reps: int = 2):
    """Steady-state mix: two prompt lengths (both buckets), a
    repetitive prompt (so spec engines actually draft) and a varied
    one, repeated."""
    rng = np.random.default_rng(0)
    total = 0
    for _ in range(reps):
        total += eng.generate([7] * 12, max_new_tokens=16)[
            "num_generated"]
        total += eng.generate(
            [int(t) for t in rng.integers(1, 200, 24)],
            max_new_tokens=8)["num_generated"]
    return total


@pytest.mark.parametrize("workload", ["plain", "spec", "spec_int8"])
def test_steady_state_decode_programs_and_sync_cadence(debug_jax,
                                                       workload):
    """The acceptance sweep — one engine per workload (spec on/off,
    int8 on/off) asserts BOTH invariants at once:

    - the engine compiles EXACTLY its declared programs (one decode
      chunk program, one verify program iff speculation is on, one
      prefill program per prompt bucket used) and never recompiles in
      steady state;
    - every decode dispatch fetches the host EXACTLY once (witness
      decode-tag syncs == the per-chunk metric), and prefill once per
      admission.
    """
    kw = {}
    if workload != "plain":
        kw.update(spec_draft_len=4)
    if workload == "spec_int8":
        kw.update(quantize="int8")
    eng = _engine(**kw)
    try:
        assert _drive(eng) > 0
        first = eng.loop.program_counts()
        assert _drive(eng, reps=1) > 0      # steady state: no growth
        programs = eng.loop.program_counts()
        assert programs == first
        assert programs["decode_chunk"] == 1
        # The tick dispatches the donating program; the functional
        # twin (checks call it on a live cache) never ran here.
        assert programs["prefill_inplace"] == 2  # both buckets exercised
        assert programs["prefill"] == 0
        if workload == "plain":
            assert "verify_chunk" not in programs
        else:
            assert programs["verify_chunk"] == 1
        assert jax_debug.over_budget_reports() == []
        stats = eng.stats()
        assert stats["compiled_programs"] == programs
        # One host sync per decode chunk, exactly.
        syncs = jax_debug.host_sync_counts()
        assert stats["decode_host_syncs"] > 0
        assert syncs.get("engine.decode", 0) == \
            stats["decode_host_syncs"]
        # Prefill syncs once per admission (the first-token fetch).
        assert syncs.get("engine.prefill", 0) == stats["requests"]
        if workload != "plain":
            assert stats["spec_chunks"] > 0  # the verify path ran
    finally:
        eng.close()


def test_chunked_multi_step_engine_declared_schedule(debug_jax):
    """The chunked-prefill + multi-step engine keeps the
    SAME declared budgets: one decode program, prefill programs within the
    per-bucket budget even though a long prompt now dispatches MANY
    chunks (intermediate chunks reuse bucket shapes and fetch nothing),
    exactly one counted prefill sync per ADMISSION (the final chunk's
    first-token fetch), and decode witness syncs == the per-chunk
    metric (multi-step moves the fetch one chunk behind dispatch, it
    never adds or drops one)."""
    eng = _engine(prefill_chunk=16, prefix_block=16, multi_step=True)
    try:
        # 40-token prompt -> chunks (16, 16, 8); short prompt -> one.
        out = eng.generate([3] * 40, max_new_tokens=12)
        assert out["num_generated"] == 12
        assert eng.generate([9, 8, 7], max_new_tokens=9)[
            "num_generated"] == 9
        first = eng.loop.program_counts()
        eng.generate([3] * 40, max_new_tokens=4)  # steady: no growth
        programs = eng.loop.program_counts()
        assert programs == first
        assert programs["decode_chunk"] == 1
        # Chunking NARROWS the prefill shape set: every full chunk is
        # the 16-token bucket and every tail (<= chunk) buckets back
        # into it — one program, under the 2-bucket budget.
        assert programs["prefill_inplace"] == 1
        assert jax_debug.over_budget_reports() == []
        stats = eng.stats()
        syncs = jax_debug.host_sync_counts()
        assert syncs.get("engine.decode", 0) == \
            stats["decode_host_syncs"]
        assert syncs.get("engine.prefill", 0) == stats["requests"] == 3
        # Chunked accounting: 40+3+40 real suffix tokens prefilled
        # (minus any warm prefix reuse on the repeat).
        assert stats["prefill_tokens"] + stats[
            "prefix_tokens_reused"] == 83
    finally:
        eng.close()


def test_transfer_guard_clean_engine_tick(debug_jax, monkeypatch):
    """Under RTPU_DEBUG_JAX_TRANSFER_GUARD=disallow every tick runs
    inside jax.transfer_guard: all device traffic must go through the
    explicit _put/_fetch pair. A stray implicit transfer raises in the
    engine thread and fails the roster — so a clean generate IS the
    assertion (spec path included)."""
    monkeypatch.setenv("RTPU_DEBUG_JAX_TRANSFER_GUARD", "disallow")
    eng = _engine(spec_draft_len=4)
    try:
        assert _drive(eng, reps=1) > 0
        assert jax_debug.host_sync_counts().get("engine.decode", 0) > 0
    finally:
        eng.close()


def test_transfer_guard_clean_pipelined_tick_across_roster_changes(
        debug_jax, monkeypatch):
    """The drafter-free engine merges the next chunk's inputs ON THE
    DEVICE (the carry of the chunk in flight, the host's arrays through
    `_put`, a joining request's first token from its prefill's output):
    no implicit transfer under the guard, one compiled program each for
    the merge and the join whatever the roster does, and the sync
    budget of the serial schedule — one per fetched chunk, one per
    admission."""
    import concurrent.futures as cf

    monkeypatch.setenv("RTPU_DEBUG_JAX_TRANSFER_GUARD", "disallow")
    eng = _engine(max_batch=2)
    try:
        with cf.ThreadPoolExecutor(6) as pool:
            futs = [pool.submit(eng.generate, [i + 1] * (3 + 4 * i), n)
                    for i, n in enumerate((22, 5, 9, 13, 7, 1))]
            assert [f.result(timeout=300)["num_generated"]
                    for f in futs] == [22, 5, 9, 13, 7, 1]
        programs = eng.loop.program_counts()
        assert programs["decode_chunk"] == 1
        assert programs["roster_merge"] == 1
        assert programs["roster_join"] == 1
        assert jax_debug.over_budget_reports() == []
        stats = eng.stats()
        assert stats["decode_chunks_carried"] > 0
        syncs = jax_debug.host_sync_counts()
        assert syncs.get("engine.decode", 0) == stats["decode_host_syncs"]
        assert syncs.get("engine.prefill", 0) == stats["requests"] == 6
    finally:
        eng.close()


def test_flag_off_engine_is_unwrapped(monkeypatch):
    monkeypatch.delenv("RTPU_DEBUG_JAX", raising=False)
    eng = _engine()
    try:
        assert eng.loop.program_counts() == {}
        assert not isinstance(eng.loop.decode_chunk,
                              jax_debug.JitWitness)
        out = eng.generate([5, 6, 7], max_new_tokens=4)
        assert out["num_generated"] == 4
        assert "compiled_programs" not in eng.stats()
    finally:
        eng.close()


# ------------------------------------------------------- trainer layer


def test_train_step_single_program_budget(debug_jax):
    import jax
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh, mesh_context

    cfg = llama.tiny_config(max_seq_len=64)
    mesh = make_mesh(MeshSpec(), jax.devices("cpu")[:1])
    tx = optax.sgd(1e-3)
    with mesh_context(mesh):
        state = spmd.sharded_init(cfg, mesh, jax.random.PRNGKey(0), tx)
        step = spmd.make_train_step(cfg, mesh, tx)
        tokens = np.zeros((2, 64), np.int32)
        for _ in range(3):
            state, metrics = step(state, jax.device_put(tokens))
        assert jax_debug.program_counts()["spmd.train_step"] == 1
        assert jax_debug.over_budget_reports() == []
        # A shape change is a SECOND program — over budget, reported.
        state, metrics = step(state, jax.device_put(
            np.zeros((4, 64), np.int32)))
        assert jax_debug.program_counts()["spmd.train_step"] == 2
        reports = jax_debug.over_budget_reports()
        assert [r["name"] for r in reports] == ["spmd.train_step"]
