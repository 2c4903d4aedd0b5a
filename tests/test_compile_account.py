"""The compile account (`ray_tpu/util/compile_cache.py`): what each
program cost on its way onto the device, by name, from JAX's own
monitoring events; the constructors' phases; the two views. On the
CPU: the seconds here prove bookkeeping, not speed.
"""

from __future__ import annotations

import threading
import time

import pytest

from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.util import compile_cache, tracing

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
HITS = "/jax/compilation_cache/cache_hits"
_SETTINGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")


def _unlisten(account) -> None:
    import jax

    jax.monitoring.unregister_event_listener(account._on_event)
    jax.monitoring.unregister_event_duration_listener(account._on_duration)
    jax.monitoring.unregister_scalar_listener(account._on_scalar)


@pytest.fixture
def jax_cache_settings():
    """JAX's cache settings as they were, after the test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = {k: getattr(jax.config, k) for k in _SETTINGS}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture
def account(tmp_path, jax_cache_settings):
    """An account listening on its own, against an EMPTY cache
    directory that keeps every program."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    acct = compile_cache.CompileCache(str(tmp_path / "cache"))
    acct._listen(jax.monitoring)
    yield acct
    _unlisten(acct)


@pytest.fixture
def spans():
    got = []
    tracing.flush()
    tracing.set_sink(got.extend)
    old = cfg.get("tracing_enabled")
    cfg.set("tracing_enabled", True)
    yield got
    cfg.set("tracing_enabled", old)
    tracing.set_sink(None)


def _program(name: str):
    """A jitted function nobody has compiled, under ``name``."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.tanh(x) * 3.0 + x

    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def test_cold_then_warm_then_a_second_shape(account):
    import jax
    import jax.numpy as jnp

    f = _program("account_probe")
    x = jnp.ones((7,), jnp.float32)     # (its eager programs come first)
    f(x).block_until_ready()
    cold = dict(account.rows["account_probe"])
    assert cold["requests"] == 1 and cold["hits"] == 0
    assert cold["trace_s"] > 0 and cold["lower_s"] > 0
    assert cold["compile_s"] > 0
    assert cold["cache_load_s"] is None and cold["saved_s"] is None
    # The same function, the in-memory caches cleared, the directory
    # now warm: a hit, and nothing more compiled.
    jax.clear_caches()
    f(x).block_until_ready()
    warm = dict(account.rows["account_probe"])
    assert warm["requests"] == 2 and warm["hits"] == 1
    assert warm["cache_load_s"] > 0 and warm["saved_s"] is not None
    assert warm["compile_s"] == cold["compile_s"]
    assert warm["trace_s"] > cold["trace_s"]    # traced again: paid warm too
    # A second signature under the one name.
    f(jnp.ones((9,), jnp.float32)).block_until_ready()
    assert account.rows["account_probe"]["requests"] == 3
    t = account.totals()
    assert t["requests"] == sum(r["requests"] for r in account.rows.values())
    assert t["hits"] == account.hits and t["requests"] == account.requests


def test_a_helper_traced_inside_a_program_is_the_programs(account):
    import jax
    import jax.numpy as jnp

    inner = _program("account_inner")

    def outer(x):
        return inner(x) + 1.0

    outer.__name__ = outer.__qualname__ = "account_outer"
    jax.jit(outer)(jnp.ones((5,), jnp.float32)).block_until_ready()
    assert account.rows["account_outer"]["requests"] == 1
    assert "account_inner" not in account.rows


def _stage(account, event, name, start, secs, hit=None):
    """One stage as JAX reports it, on the calling thread."""
    account._on_scalar(event, start, fun_name=name)
    if hit is not None:
        account._on_event(REQUESTS)
        if hit:
            account._on_event(HITS)
    account._on_duration(event, secs, fun_name=name)


def test_two_threads_at_once_book_to_their_own_program_and_to_one_clock(
        tmp_path):
    """Interleaved by hand: A's backend stage is open while B's whole
    compile runs; each cache event lands on its own thread's program,
    and the totals are the union of the intervals, not their sum."""
    acct = compile_cache.CompileCache(str(tmp_path))
    a_open, b_done = threading.Event(), threading.Event()

    def a():
        acct._on_scalar(BACKEND, 100.0, fun_name="jit(prog_a)")
        acct._on_event(REQUESTS)
        a_open.set()
        assert b_done.wait(10)
        acct._on_duration(BACKEND, 10.0, fun_name="jit(prog_a)")   # a miss

    def b():
        assert a_open.wait(10)
        _stage(acct, TRACE, "prog_b", 101.0, 1.0)
        _stage(acct, LOWER, "jit(prog_b)", 102.0, 1.0)
        _stage(acct, BACKEND, "jit(prog_b)", 103.0, 2.0, hit=True)
        b_done.set()

    threads = [threading.Thread(target=f) for f in (a, b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert not any(t.is_alive() for t in threads)
    ra, rb = acct.rows["prog_a"], acct.rows["prog_b"]
    assert (ra["requests"], ra["hits"], ra["compile_s"]) == (1, 0, 10.0)
    assert ra["cache_load_s"] is None and ra["trace_s"] is None
    assert (rb["requests"], rb["hits"]) == (1, 1)
    assert (rb["trace_s"], rb["lower_s"], rb["cache_load_s"]) == (1., 1., 2.)
    assert rb["compile_s"] is None
    assert (acct.requests, acct.hits) == (2, 1)
    t = acct.totals()
    parts = t["trace_s"] + t["lower_s"] + t["compile_s"] + t["cache_load_s"]
    assert parts == pytest.approx(10.0)         # 100 .. 110 passed, not 14
    assert t["compile_s"] == pytest.approx(6.0)  # what B had not covered
    assert acct._covered == []                  # nothing open: all pruned


def test_a_compile_inside_a_phase_is_subtracted_from_it(account, spans):
    import jax.numpy as jnp

    f = _program("account_in_phase")
    x = jnp.ones((3,), jnp.float32)
    before = account.totals()
    t0 = time.perf_counter()
    with account.phase("outer") as outer:
        with account.phase("inner") as inner:
            f(x).block_until_ready()
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    after = account.totals()
    booked = sum(after[k] - before[k]
                 for k in ("trace_s", "lower_s", "compile_s", "cache_load_s"))
    assert booked > 0
    assert outer.own_s + booked == pytest.approx(wall, abs=1e-3)
    assert outer.own_s >= inner.own_s + 0.01 - 1e-3
    # Only the outermost phase is a part of the whole.
    assert after["init_s"] - before["init_s"] == pytest.approx(outer.own_s)
    assert account.phases["outer"]["wall_s"] == pytest.approx(wall, abs=1e-3)
    assert account.phases["inner"]["n"] == 1
    cfg.set("tracing_enabled", False)
    tracing.flush()
    names = [s["name"] for s in spans]
    assert "setup.inner" in names and "setup.outer" in names
    prog = next(s for s in spans if s["name"] == "compile.account_in_phase")
    assert prog["attrs"]["hit"] is False and prog["end"] > prog["start"]
    assert {"trace_s", "lower_s", "compile_s"} <= set(prog["attrs"])


def test_configure_twice_is_one_account_and_one_listener(
        monkeypatch, tmp_path, jax_cache_settings):
    import jax

    registered = []
    for kind in ("event", "event_duration_secs", "scalar"):
        register = getattr(jax.monitoring, f"register_{kind}_listener")

        def spy(callback, _register=register, _kind=kind):
            registered.append(_kind)
            _register(callback)

        monkeypatch.setattr(jax.monitoring, f"register_{kind}_listener", spy)

    monkeypatch.setattr(compile_cache, "_ACCOUNT", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.account() is None
    with compile_cache.phase("nobody listens") as p:   # timed all the same
        time.sleep(0.002)
    assert p.own_s >= 0.002
    first = compile_cache.configure()
    try:
        assert sorted(registered) == ["event", "event_duration_secs",
                                      "scalar"]
        assert compile_cache.configure() is first
        assert compile_cache.account() is first
        assert len(registered) == 3
        assert first.path == str(tmp_path)
    finally:
        _unlisten(first)


def test_engine_stats_show_the_process_account(monkeypatch, tmp_path):
    from ray_tpu.serve.llm import LLMEngine

    acct = compile_cache.CompileCache(str(tmp_path))
    monkeypatch.setattr(compile_cache, "_ACCOUNT", acct)
    _stage(acct, TRACE, "p", 10.0, 1.5)
    _stage(acct, LOWER, "jit(p)", 12.0, 0.5)
    _stage(acct, BACKEND, "jit(p)", 13.0, 2.0, hit=True)
    eng = LLMEngine(max_batch=2, max_len=64, prompt_buckets=[8],
                    decode_chunk=2)
    try:
        stats = eng.stats()
    finally:
        eng.close()
    assert stats["compile_requests"] == 1 and stats["compile_hits"] == 1
    assert stats["compile_trace_s"] == 1.5 and stats["compile_lower_s"] == 0.5
    assert stats["compile_backend_s"] == 0.0
    assert stats["compile_cache_load_s"] == 2.0
    assert 0 < stats["engine_init_s"] == acct.phases["engine.init"]["own_s"]
    assert {"engine.weights", "engine.cache", "engine.decode_loop"} \
        <= set(acct.phases)
    assert acct.totals()["init_s"] == stats["engine_init_s"]
    monkeypatch.setattr(compile_cache, "_ACCOUNT", None)
    assert "compile_requests" not in eng.stats()
    assert eng.stats()["engine_init_s"] == stats["engine_init_s"]
