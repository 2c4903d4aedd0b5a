"""rtpu-lint + runtime lock witness.

One positive and one negative fixture per static rule, the baseline
mechanics, and the RTPU_DEBUG_LOCKS witness: deliberate lock-order
deadlock detected online, Condition integration, reentrancy, hold-time
reporting, and the no-false-positive cases (consistent order,
same-name sibling instances).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.devtools import lock_debug
from ray_tpu.devtools.lint import (DEFAULT_BASELINE, lint_source,
                                   load_baseline, new_findings,
                                   write_baseline)

NM = "ray_tpu.cluster.node_manager"
WM = "ray_tpu.cluster.worker_main"
PROTO = "ray_tpu.cluster.protocol"


def rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------- retry-without-deadline


def test_unbounded_retrying_call_loop_flagged():
    src = (
        "import time\n"
        "def f(self):\n"
        "    while True:\n"
        "        try:\n"
        "            return self.head.retrying_call('ping', timeout=5)\n"
        "        except Exception as e:\n"
        "            print(e)\n"
        "            time.sleep(0.1)\n"
    )
    fs = lint_source(src, NM, "x.py")
    assert rules(fs) == ["retry-without-deadline"]


def test_unbounded_socket_connect_loop_flagged():
    src = (
        "import socket, time\n"
        "def f(self):\n"
        "    while True:\n"
        "        try:\n"
        "            self.sock.connect(('h', 1))\n"
        "            return\n"
        "        except OSError as e:\n"
        "            print(e)\n"
        "            time.sleep(0.1)\n"
    )
    fs = lint_source(src, NM, "x.py")
    assert rules(fs) == ["retry-without-deadline"]


def test_deadline_bounded_retry_loop_clean():
    src = (
        "import time\n"
        "def f(self):\n"
        "    deadline = time.monotonic() + 30\n"
        "    while True:\n"
        "        try:\n"
        "            return self.head.retrying_call('ping', timeout=5)\n"
        "        except Exception as e:\n"
        "            print(e)\n"
        "            if time.monotonic() > deadline:\n"
        "                raise\n"
    )
    assert lint_source(src, NM, "x.py") == []


def test_attempt_counted_and_stop_event_loops_clean():
    counted = (
        "def f(self):\n"
        "    attempts = 0\n"
        "    while True:\n"
        "        try:\n"
        "            return self.head.retrying_call('ping')\n"
        "        except Exception as e:\n"
        "            print(e)\n"
        "            attempts += 1\n"
        "            if attempts > 5:\n"
        "                raise\n"
    )
    assert lint_source(counted, NM, "x.py") == []
    # Daemon loops that exit on the stop event are bounded by shutdown.
    daemon = (
        "def f(self):\n"
        "    while True:\n"
        "        if self._stop.is_set():\n"
        "            return\n"
        "        try:\n"
        "            self.head.retrying_call('register_node')\n"
        "        except Exception as e:\n"
        "            print(e)\n"
    )
    assert lint_source(daemon, NM, "x.py") == []


def test_success_break_alone_does_not_bound_retry_loop():
    # break on success is the NORMAL exit — the hang case is the one
    # where success never comes; break must not count as a bound.
    src = (
        "def f(self):\n"
        "    while True:\n"
        "        try:\n"
        "            self.head.retrying_call('ping')\n"
        "            break\n"
        "        except Exception as e:\n"
        "            print(e)\n"
    )
    assert rules(lint_source(src, NM, "x.py")) == ["retry-without-deadline"]


def test_retry_rule_ignores_nonretry_while_true_and_nested_defs():
    plain = (
        "def f(self):\n"
        "    while True:\n"
        "        self.queue.append(1)\n"
    )
    assert lint_source(plain, NM, "x.py") == []
    # A retry loop INSIDE a nested def belongs to that def's own visit;
    # the outer while must not inherit its calls.
    nested = (
        "def f(self):\n"
        "    while True:\n"
        "        if self._stop.is_set():\n"
        "            return\n"
        "        def cb():\n"
        "            return self.head.retrying_call('ping')\n"
        "        self.cbs.append(cb)\n"
    )
    assert lint_source(nested, NM, "x.py") == []


def test_retry_rule_suppressable_inline():
    src = (
        "def f(self):\n"
        "    while True:  # rtpu-lint: disable=retry-without-deadline\n"
        "        try:\n"
        "            return self.head.retrying_call('ping')\n"
        "        except Exception as e:\n"
        "            print(e)\n"
    )
    assert lint_source(src, NM, "x.py") == []


# ------------------------------------------------------------ lock-order


def test_lock_order_violation_flagged():
    src = (
        "def f(self):\n"
        "    with self._zygote_lock:\n"
        "        with self._zygote_io_lock:\n"
        "            pass\n")
    fs = lint_source(src, NM, "x.py")
    assert rules(fs) == ["lock-order"]
    assert "_zygote_io_lock" in fs[0].message


def test_lock_order_correct_nesting_clean():
    src = (
        "def f(self):\n"
        "    with self._zygote_io_lock:\n"
        "        with self._zygote_lock:\n"
        "            pass\n")
    assert lint_source(src, NM, "x.py") == []


def test_never_nested_group_flagged_either_order():
    for a, b in (("_seen_lock", "_done_lock"),
                 ("_done_lock", "_seen_lock")):
        src = (
            f"def f(self):\n"
            f"    with self.{a}:\n"
            f"        with self.{b}:\n"
            f"            pass\n")
        fs = lint_source(src, WM, "x.py")
        assert rules(fs) == ["lock-order"], (a, b)
        assert "never-nested" in fs[0].message


def test_acquire_call_under_with_checked():
    src = (
        "def f(self):\n"
        "    with self._zygote_lock:\n"
        "        self._zygote_io_lock.acquire()\n")
    assert rules(lint_source(src, NM, "x.py")) == ["lock-order"]


def test_other_module_pairs_not_declared_clean():
    src = (
        "def f(self):\n"
        "    with self._zygote_lock:\n"
        "        with self._zygote_io_lock:\n"
        "            pass\n")
    assert lint_source(src, "ray_tpu.other", "x.py") == []


# ---------------------------------------------------- blocking-under-lock


def test_blocking_calls_under_lock_flagged():
    src = (
        "import time, subprocess\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        time.sleep(1.0)\n"
        "        self.sock.recv(4)\n"
        "        subprocess.run(['true'])\n")
    fs = lint_source(src, NM, "x.py")
    assert [f.rule for f in fs] == ["blocking-under-lock"] * 3


def test_short_sleep_and_unlocked_io_clean():
    src = (
        "import time\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        time.sleep(0.001)\n"
        "    self.sock.recv(4)\n"
        "    time.sleep(5)\n")
    assert lint_source(src, NM, "x.py") == []


def test_io_serialization_locks_exempt():
    # _zygote_io_lock (node_manager) and send_lock (protocol) exist to
    # serialize blocking I/O: holding them across it is the point.
    src = (
        "def f(self):\n"
        "    with self._zygote_io_lock:\n"
        "        self.z.stdout.readline()\n")
    assert lint_source(src, NM, "x.py") == []
    src = (
        "def g(sock, lock):\n"
        "    with send_lock:\n"
        "        sock.sendmsg([b'x'])\n")
    assert lint_source(src, PROTO, "x.py") == []


def test_closure_defined_under_lock_not_flagged():
    # The closure's body runs LATER on another thread — it is lexically
    # inside the with-block but never executes under the lock.
    src = (
        "def f(self):\n"
        "    with self._lock:\n"
        "        def report():\n"
        "            self._head.retrying_call('x')\n"
        "        spawn(report)\n")
    assert lint_source(src, NM, "x.py") == []


def test_malformed_empty_suppression_comment_does_not_crash():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:  # rtpu-lint: disable=\n"
        "        pass\n")
    # Empty rule list suppresses nothing — and must not IndexError.
    assert rules(lint_source(src, "m", "m.py")) == ["swallowed-exception"]


# -------------------------------------------------- close-without-shutdown


def test_close_without_shutdown_flagged():
    src = (
        "def f(self):\n"
        "    self._sock.close()\n")
    fs = lint_source(src, PROTO, "x.py")
    assert rules(fs) == ["close-without-shutdown"]


def test_shutdown_before_close_clean():
    src = (
        "def f(self):\n"
        "    self._sock.shutdown(2)\n"
        "    self._sock.close()\n"
        "def g(self):\n"
        "    _shutdown_socket(self._sock)\n")
    assert lint_source(src, PROTO, "x.py") == []


def test_close_in_nested_def_reported_once():
    src = (
        "def outer(self):\n"
        "    def inner():\n"
        "        self._sock.close()\n"
        "    return inner\n")
    fs = lint_source(src, PROTO, "x.py")
    assert len(fs) == 1 and fs[0].scope == "outer.inner"


def test_close_rule_scoped_to_socket_modules():
    src = (
        "def f(self):\n"
        "    self._sock.close()\n")
    assert lint_source(src, "ray_tpu.util.queue", "x.py") == []


# ------------------------------------------------------------- banned-api


def test_banned_set_mesh_and_shard_map():
    src = (
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "def f(m):\n"
        "    jax.sharding.set_mesh(m)\n")
    fs = lint_source(src, "ray_tpu.parallel.spmd", "x.py")
    assert [f.rule for f in fs] == ["banned-api"] * 2
    msgs = " ".join(f.message for f in fs)
    assert "mesh_context" in msgs and "ray_tpu.ops" in msgs


@pytest.mark.parametrize("module", ["ray_tpu.ops.ring_attention",
                                    "ray_tpu.ops.attention"])
def test_shard_map_import_allowed_in_owning_ops(module):
    src = "from jax import lax, shard_map\n"
    assert lint_source(src, module, "x.py") == []


def test_inner_html_flagged_in_dashboard_strings_only():
    src = 'PAGE = "<script>el.innerHTML = x;</script>"\n'
    fs = lint_source(src, "ray_tpu.util.dashboard", "d.py")
    assert rules(fs) == ["banned-api"]
    assert lint_source(src, "ray_tpu.util.queue", "d.py") == []


def test_text_content_clean_in_dashboard():
    src = 'PAGE = "<script>el.textContent = x;</script>"\n'
    assert lint_source(src, "ray_tpu.util.dashboard", "d.py") == []


# ---------------------------------------------------- swallowed-exception


def test_silent_broad_except_flagged():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n")
    assert rules(lint_source(src, "m", "m.py")) == ["swallowed-exception"]


def test_logged_raised_or_used_excepts_clean():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        logger.debug('boom: %r', e)\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        raise\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        record(e)\n")
    assert lint_source(src, "m", "m.py") == []


def test_suppression_comments_honored():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:  # rtpu-lint: disable=swallowed-exception\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:  # noqa: BLE001 — audited best-effort\n"
        "        pass\n")
    assert lint_source(src, "m", "m.py") == []


# --------------------------------------------------------- daemon-no-join


def test_daemon_thread_without_join_flagged():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._t = threading.Thread(target=x, daemon=True)\n"
        "        self._t.start()\n")
    assert rules(lint_source(src, "m", "m.py")) == ["daemon-no-join"]


def test_daemon_thread_with_join_clean():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._t = threading.Thread(target=x, daemon=True)\n"
        "    def close(self):\n"
        "        self._t.join(timeout=2)\n")
    assert lint_source(src, "m", "m.py") == []


# ---------------------------------------------------- span-not-closed


def test_span_call_without_with_flagged():
    src = (
        "from ray_tpu.util import tracing\n"
        "def f(name):\n"
        "    tracing.trace('run')\n"            # never closed
        "    h = tracing.span('child')\n"       # assigned, never with-ed
        "    return h\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["span-not-closed"]
    assert len(fs) == 2


def test_span_as_context_manager_clean():
    src = (
        "from ray_tpu.util import tracing\n"
        "import contextlib\n"
        "def f(spec, name):\n"
        "    with tracing.trace('run') as t:\n"
        "        with tracing.span('child'):\n"
        "            pass\n"
        "    cm = tracing.remote_span('task', spec)\n"
        "    with cm as h:\n"                    # assigned-then-with
        "        pass\n"
        "    with contextlib.ExitStack() as stack:\n"
        "        stack.enter_context(tracing.span('s'))\n"
        "    return t\n")
    assert lint_source(src, "m", "m.py") == []


def test_bare_remote_span_and_alias_receiver_flagged():
    src = (
        "from ray_tpu.util import tracing as _tracing\n"
        "from ray_tpu.util.tracing import remote_span\n"
        "def f(spec):\n"
        "    remote_span('task', spec)\n"        # bare-name constructor
        "    _tracing.remote_span('task2', spec)\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["span-not-closed"]
    assert len(fs) == 2


def test_span_rule_ignores_other_receivers_and_emit_api():
    src = (
        "def f(tracer, tracing):\n"
        "    tracer.span('not the module')\n"    # receiver not tracing-like
        "    tracing.emit_span('a', 0, 1)\n"     # manual API: no CM needed
        "    tracing.start_span('b')\n"
        "    tracing.current()\n")
    assert lint_source(src, "m", "m.py") == []


def test_span_rule_nested_def_has_own_scope():
    # The with lives in a NESTED def: the outer call is still unclosed.
    src = (
        "from ray_tpu.util import tracing\n"
        "def outer():\n"
        "    tracing.span('leak')\n"
        "    def inner():\n"
        "        with tracing.span('fine'):\n"
        "            pass\n"
        "    return inner\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["span-not-closed"]
    assert len(fs) == 1


def test_span_rule_suppressable_inline():
    src = (
        "from ray_tpu.util import tracing\n"
        "def f():\n"
        "    tracing.span('x')  # rtpu-lint: disable=span-not-closed\n")
    assert lint_source(src, "m", "m.py") == []


# --------------------------------------------------------------- baseline


def test_baseline_tracks_legacy_and_fails_new(tmp_path):
    legacy = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n")
    findings = lint_source(legacy, "m", "m.py")
    bpath = str(tmp_path / "base.json")
    write_baseline(bpath, findings)
    baseline = load_baseline(bpath)
    assert new_findings(findings, baseline) == []
    # A SECOND swallow in the same scope exceeds the baselined count.
    grown = lint_source(legacy + (
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"), "m", "m.py")
    assert len(new_findings(grown, baseline)) == 1


def test_baseline_survives_line_drift(tmp_path):
    legacy = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n")
    bpath = str(tmp_path / "base.json")
    write_baseline(bpath, lint_source(legacy, "m", "m.py"))
    shifted = "import os\nX = 1\n\n\n" + legacy
    assert new_findings(lint_source(shifted, "m", "m.py"),
                        load_baseline(bpath)) == []


def test_cli_end_to_end(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n"
                   "    try:\n"
                   "        g()\n"
                   "    except Exception:\n"
                   "        pass\n")
    bpath = tmp_path / "base.json"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    cmd = [sys.executable, "-m", "ray_tpu.devtools.lint", str(bad),
           "--baseline", str(bpath)]
    r = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                       text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "swallowed-exception" in r.stdout
    r = subprocess.run(cmd + ["--write-baseline"], env=env, cwd=repo,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(bpath.read_text())
    assert data["version"] == 2
    assert data["families"]["concurrency"]["findings"]
    r = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_write_baseline_refuses_partial_scan_of_packaged_baseline(
        tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("x = 1\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    before = open(DEFAULT_BASELINE, "rb").read()
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.devtools.lint", str(bad),
         "--write-baseline"],
        env=env, cwd=repo, capture_output=True, text=True)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "refusing" in r.stderr
    assert open(DEFAULT_BASELINE, "rb").read() == before


# --------------------------------------------------------- lock witness


@pytest.fixture
def debug_locks(monkeypatch):
    monkeypatch.setenv("RTPU_DEBUG_LOCKS", "1")
    lock_debug.reset()
    yield
    lock_debug.reset()


def test_make_lock_plain_when_disabled(monkeypatch):
    monkeypatch.delenv("RTPU_DEBUG_LOCKS", raising=False)
    lk = lock_debug.make_lock("x")
    assert not isinstance(lk, lock_debug.DebugLock)


def test_witness_reports_deliberate_deadlock(debug_locks):
    """Two threads acquire A/B in opposite orders and genuinely contend
    (held-while-wanting on both sides). The witness must report the
    cycle ONLINE even though neither inner acquire ever succeeds —
    edges are recorded on the attempt, lockdep-style."""
    A = lock_debug.make_lock("dl.A")
    B = lock_debug.make_lock("dl.B")
    barrier = threading.Barrier(2, timeout=5)

    def t1():
        with A:
            barrier.wait()
            if B.acquire(timeout=1.0):
                B.release()

    def t2():
        with B:
            barrier.wait()
            if A.acquire(timeout=1.0):
                A.release()

    threads = [threading.Thread(target=t1), threading.Thread(target=t2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    cycles = lock_debug.get_report()["cycles"]
    assert cycles, "deadlock cycle not reported"
    assert {"dl.A", "dl.B"} <= set(cycles[0]["chain"])


def test_consistent_order_no_cycle(debug_locks):
    A = lock_debug.make_lock("ok.A")
    B = lock_debug.make_lock("ok.B")
    for _ in range(3):
        with A:
            with B:
                pass
    assert lock_debug.get_report()["cycles"] == []
    assert lock_debug.get_report()["edges"].get("ok.A") == ["ok.B"]


def test_same_name_sibling_instances_no_self_cycle(debug_locks):
    # Two connections' send locks share a NAME; nesting two instances
    # is not an ordering fact and must not report a self-cycle.
    L1 = lock_debug.make_lock("conn.send_lock")
    L2 = lock_debug.make_lock("conn.send_lock")
    with L1:
        with L2:
            pass
    assert lock_debug.get_report()["cycles"] == []


def test_self_deadlock_probes_not_reported(debug_locks):
    # Timeout/non-blocking re-acquire probes and RLock re-entry are NOT
    # self-deadlocks and must stay silent.
    L = lock_debug.make_lock("self.L")
    with L:
        assert not L.acquire(timeout=0.05)
        L.acquire(blocking=False)
    rl = lock_debug.make_rlock("self.RL")
    with rl:
        with rl:
            pass
    assert lock_debug.get_report()["cycles"] == []


def test_blocking_self_deadlock_reported_pre_block(debug_locks):
    # A genuine blocking re-acquire of a non-reentrant lock can never
    # succeed: the witness must report it BEFORE parking the thread.
    L = lock_debug.make_lock("selfdl.L")
    done = []

    def victim():
        L.acquire()
        L.acquire()  # reported pre-block, then parks
        done.append(1)

    t = threading.Thread(target=victim, daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and \
            not lock_debug.get_report()["cycles"]:
        time.sleep(0.01)
    cycles = lock_debug.get_report()["cycles"]
    assert cycles and cycles[0]["chain"] == ["selfdl.L", "selfdl.L"]
    assert "self-deadlock" in cycles[0]["message"]
    # Unpark the victim (threading.Lock may be released by any thread)
    # so the test leaves no thread blocked forever.
    L._inner.release()
    t.join(5)
    assert done == [1]


def test_condition_integration_and_wait_clears_hold(debug_locks):
    lk = lock_debug.make_rlock("cv.L")
    cv = threading.Condition(lk)
    got = []

    def waiter():
        with cv:
            cv.wait(5)
            got.append(1)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.2)
    with cv:
        cv.notify_all()
    t.join(5)
    assert got == [1]
    assert lock_debug.get_report()["cycles"] == []


def test_hold_time_reported(debug_locks, monkeypatch):
    monkeypatch.setenv("RTPU_DEBUG_LOCKS_HOLD_S", "0.05")
    L = lock_debug.make_lock("hold.L")
    with L:
        time.sleep(0.1)
    holds = lock_debug.get_report()["long_holds"]
    assert holds and holds[0]["lock"] == "hold.L"
    assert holds[0]["seconds"] >= 0.05
    from ray_tpu.util import metrics as _metrics

    m = _metrics.get_metric("rtpu_debug_lock_hold_exceeded")
    assert m is not None
    assert any(lbl.get("lock") == "hold.L" and v >= 1
               for lbl, v in m.items())


def test_repo_baseline_file_checked_in():
    assert os.path.exists(DEFAULT_BASELINE)
    data = json.load(open(DEFAULT_BASELINE))
    assert data["version"] == 2
    fams = data["families"]
    # Every rule family has a section with a schema version; the
    # concurrency section carries the legacy debt, the jax, dist, res,
    # and chan sections start (and should stay) empty — their findings
    # are fixed or allow-commented, not baselined.
    assert set(fams) == {"concurrency", "jax", "dist", "res", "chan"}
    for sec in fams.values():
        assert isinstance(sec["schema"], int)
    assert fams["concurrency"]["findings"]
