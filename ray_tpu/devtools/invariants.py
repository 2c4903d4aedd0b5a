"""Codified concurrency + compatibility invariants for rtpu-lint.

Each table below is an invariant mined from a post-review finding in an
earlier PR; the linter (``lint.py``) enforces them, the README's
"Concurrency invariants & lint" section documents them for humans. Keep
the two in sync: a new invariant lands here FIRST, then in prose.

Module keys are dotted module names (``ray_tpu.cluster.node_manager``).
Lock names are the attribute/variable names as they appear in source
(``_zygote_lock`` matches ``self._zygote_lock`` and a bare
``_zygote_lock``).
"""

from __future__ import annotations

import re

# ---------------------------------------------------------------- locks

#: What counts as "a lock" when the linter sees ``with <expr>:`` or
#: ``<expr>.acquire()``. Condition variables count too: entering one
#: acquires its underlying lock.
LOCK_NAME_RE = re.compile(r"(lock|mutex|_cv|_cond|cond)$", re.IGNORECASE)

#: Declared acquisition order per module: within one chain, a lock may
#: only be acquired while holding locks that appear EARLIER in the
#: chain. Acquiring chain[i] while holding chain[j] (j > i) is a
#: lock-order violation. (PR 2: the zygote lock split — the fork
#: round-trip's pipe I/O runs under ``_zygote_io_lock`` with
#: ``_zygote_lock`` taken briefly inside it for handle lifecycle;
#: nesting them the other way re-creates the stop()-wedged-behind-a-
#: 60s-fork hang the split fixed.)
LOCK_ORDER: dict[str, list[list[str]]] = {
    "ray_tpu.cluster.node_manager": [
        ["_zygote_io_lock", "_zygote_lock"],
    ],
}
# (protocol's send-vs-pending rule lives in NEVER_NESTED below — an
# ordering chain needs two members to enforce anything.)

#: Lock groups that must NEVER be held together (any nesting, either
#: order). The Python-side analog of shm layout v2's "no op ever holds
#: two shard locks" rule (PR 4).
NEVER_NESTED: dict[str, list[set[str]]] = {
    "ray_tpu.cluster.worker_main": [
        {"_seen_lock", "_done_lock", "_hosted_lock", "order_lock"},
    ],
    "ray_tpu.cluster.protocol": [
        {"_send_lock", "_pending_lock"},
        {"send_lock", "_pending_lock"},
    ],
    "ray_tpu.core.cluster_core": [
        # Owner-side bookkeeping locks are leaves: holding two at once
        # is how the single-flusher/outbox races of PR 4 started.
        {"_obj_loc_lock", "_inflight_lock", "_lease_lock",
         "_obj_notify_flush_lock"},
    ],
    "ray_tpu.cluster.node_manager": [
        {"_lock", "_pull_lock"},
    ],
}

#: Locks that exist to SERIALIZE blocking I/O — the blocking-under-lock
#: rule does not apply to them (holding them during recv/sendmsg is the
#: point). Everything else holding a lock across the calls in
#: BLOCKING_METHODS/BLOCKING_FUNCS is a finding.
IO_LOCKS: dict[str, set[str]] = {
    "ray_tpu.cluster.protocol": {"send_lock", "_send_lock"},
    "ray_tpu.cluster.node_manager": {"_zygote_io_lock"},
}

#: Method names whose call under a (non-IO) lock blocks on the network,
#: a pipe, or a subprocess. ``.wait``/``.join`` are deliberately absent:
#: Condition.wait releases its lock and Thread.join under a lock is a
#: separate (ordering) problem.
BLOCKING_METHODS = {
    "recv", "recv_into", "recvmsg", "recvmsg_into", "recvfrom",
    "sendmsg", "sendall", "accept", "connect", "readline", "select",
    "retrying_call",
}

#: Dotted function names that block (subprocess round-trips, fork pipe
#: I/O). Matched against the full dotted call target.
BLOCKING_FUNCS = {
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output", "subprocess.Popen",
    "os.fork", "os.forkpty",
}

#: ``time.sleep(x)`` with a constant ``x`` strictly greater than this
#: (seconds) inside a ``with <lock>`` body is a finding.
SLEEP_UNDER_LOCK_MAX_S = 0.05

# ------------------------------------------------------------- sockets

#: Modules whose sockets feed ``recv_into`` sinks (caller-owned shm
#: views): a bare ``close()`` leaves a blocked reader alive and writing
#: into freed/reallocated memory — ``shutdown()`` is what wakes it
#: (PR 4 review rounds 1+2). Any ``<x>.close()`` where ``x`` looks like
#: a socket and has no earlier ``shutdown``/``_shutdown_socket`` in the
#: same function is flagged in these modules.
SOCKET_SHUTDOWN_MODULES = {
    "ray_tpu.cluster.protocol",
    "ray_tpu.cluster.node_manager",
    "ray_tpu.cluster.head",
    "ray_tpu.cluster.worker_main",
}

#: Variable-name heuristic for "this is a socket".
SOCKET_NAME_RE = re.compile(r"sock", re.IGNORECASE)

# ---------------------------------------------------------- banned APIs

#: One seam per jax mesh API (written for the installed jax, 0.9):
#: ambient meshes are entered through ``mesh_context`` and manual
#: partitioning (``shard_map``) lives in the ops modules that own
#: a per-shard kernel — model and parallel code calls those ops.
#: dotted-call-suffix -> replacement hint.
BANNED_CALLS = {
    "jax.sharding.set_mesh":
        "use ray_tpu.parallel.mesh.mesh_context() (the one place the "
        "ambient mesh is entered)",
    "sharding.set_mesh":
        "use ray_tpu.parallel.mesh.mesh_context() (the one place the "
        "ambient mesh is entered)",
}

#: Module paths whose import is banned outside the exempt modules.
#: import-path -> (replacement hint, exempt modules).
_SHARD_MAP_OWNERS = {"ray_tpu.ops.ring_attention", "ray_tpu.ops.attention",
                     "ray_tpu.ops.fused"}
BANNED_IMPORTS = {
    "jax.experimental.shard_map": (
        "shard_map belongs to ray_tpu.ops (ring_attention, attention, "
        "fused); "
        "the jax.experimental path is deprecated — they import "
        "jax.shard_map",
        _SHARD_MAP_OWNERS,
    ),
    "jax.shard_map": (
        "shard_map belongs to ray_tpu.ops (ring_attention, attention, "
        "fused): call those ops instead of partitioning by hand",
        _SHARD_MAP_OWNERS,
    ),
}

#: Modules that embed browser JS in Python strings: every occurrence of
#: these substrings in a string constant is flagged (the dashboard XSS
#: was fixed twice — PR 1 and PR 3 — before it became a rule).
#: substring -> hint.
DASHBOARD_MODULES = {"ray_tpu.util.dashboard"}
BANNED_JS_SUBSTRINGS = {
    "innerHTML":
        "prefer textContent; innerHTML is allowed only for fully "
        "esc()-disciplined markup (tracked in the baseline)",
    "document.write": "document.write executes markup; build nodes or "
                      "use textContent",
}

# ------------------------------------------------- unbounded retry loops

#: Call attributes that mark a ``while True:`` body as a RETRY loop for
#: the retry-without-deadline rule: a chaos run (dead peer, dropped
#: frames) hangs exactly in an unbounded loop around these.
RETRY_CALL_ATTRS = {"retrying_call"}
#: Dotted-call suffixes that open connections (retried connects are the
#: other unbounded-loop shape).
RETRY_CONNECT_SUFFIXES = {"create_connection"}
#: Socket-looking ``<x>.connect()`` also counts (SOCKET_NAME_RE on x).

#: Escape hatches: ANY of these anywhere in the loop subtree makes it
#: bounded. Clock reads / deadline-ish names / attempt counters, or a
#: stop-event check (daemon loops that exit on shutdown).
RETRY_DEADLINE_CALLS = {"time.monotonic", "time.time",
                        "time.perf_counter"}
RETRY_DEADLINE_NAME_RE = re.compile(
    r"(deadline|attempt|tries|retries|budget|remaining|elapsed)",
    re.IGNORECASE)
RETRY_STOP_NAME_RE = re.compile(r"(stop|shutdown|closed|done|exit)",
                                re.IGNORECASE)
RETRY_STOP_ATTRS = {"is_set", "wait"}

# ------------------------------------------------- unclosed tracing spans

#: util/tracing context-manager constructors: calling one WITHOUT using
#: it as a context manager (``with tracing.span(...)``, a name later
#: with-ed, or ``stack.enter_context(...)``) leaks the ContextVar
#: parentage — the span never ends, and every later span in the thread/
#: task silently parents under it. Attribute calls are matched when the
#: receiver looks like the tracing module (``tracing`` / ``_tracing``);
#: ``remote_span`` is unambiguous enough to match as a bare name too.
TRACING_SPAN_ATTRS = {"trace", "span", "remote_span"}
TRACING_SPAN_NAMES = {"remote_span"}
TRACING_RECEIVER_RE = re.compile(r"(^|_)tracing$")

# --------------------------------------------------------- bare excepts

#: Logging-ish call names that make a broad except "handled".
LOGGING_CALL_NAMES = {
    "debug", "info", "warning", "warn", "error", "exception",
    "critical", "log", "print_exc", "print_exception", "print",
    "capture_exception", "zlog",
}

#: Comment tokens that suppress a finding on their line.
SUPPRESS_TOKEN = "rtpu-lint: disable="
#: Existing `# noqa: BLE001` annotations mark audited broad excepts.
NOQA_BROAD_EXCEPT = "noqa: BLE001"

# ======================================================================
# JAX/XLA tracing-safety invariants (rule family "jax", jaxlint.py).
#
# Each table encodes a bug found BY HAND in post-review: PR 6's int8
# bench closed over a weight and jit constant-folded it to full width
# (the int8 win was unmeasurable); its dryrun read a donated buffer
# after the step; PR 3's verify window needed scratch rows because XLA
# CLAMPS out-of-range dynamic_update_slice starts; and the engine's
# one-host-sync-per-chunk discipline was asserted nowhere.
# ======================================================================

#: Call targets whose result is "an array" for the closure-capture rule:
#: a local/module binding whose RHS contains one of these is array-like,
#: and referencing it FREE inside a jitted function bakes it into the
#: program as a constant (PR 6: `jax.jit(lambda s: s @ wq.astype(...))`
#: constant-folded the int8 weight to full width — pass arrays as jit
#: ARGUMENTS). Prefixes match the start of the dotted call target,
#: suffixes its last component.
ARRAY_FACTORY_PREFIXES = (
    "jnp.", "np.", "numpy.", "jax.numpy.", "jax.random.", "lax.",
    "jax.lax.",
)
ARRAY_FACTORY_CALLS = {
    "jax.device_put", "jax.device_get",
}
ARRAY_FACTORY_SUFFIXES = {
    "astype", "reshape", "init_params", "init_kv_cache",
    "quantize_params",
}

#: Attribute-name heuristic for "self.<attr> is a weight/cache" when a
#: jitted closure captures ``self`` (a class-level array referenced
#: inside jit is the same constant-folding hazard as a local one).
ARRAY_ATTR_RE = re.compile(
    r"(param|weight|cache|table|embed|scale|buf)s?", re.IGNORECASE)

#: Host-sync rule scope: module -> root functions of its device hot
#: path. Every function reachable from a root through same-module calls
#: is "hot": `.item()`, float()/int()/np.asarray on a value produced by
#: a device program, `device_get`, and python if/while branching on a
#: device value are findings there (the intended once-per-chunk syncs
#: carry an inline allow-comment).
JAX_HOT_PATH_ROOTS: dict[str, set[str]] = {
    "ray_tpu.serve.engine.core": {"_decode_tick", "_admit",
                                  "_engine_loop"},
    # The tick's four mechanisms (engine/README.md), by entry method.
    "ray_tpu.serve.engine.drafter": {"drafts", "tick"},
    "ray_tpu.serve.engine.kv_fleet": {"extend", "spill_evicted",
                                      "note_prefill_cost"},
    "ray_tpu.serve.engine.handoff": {"finish", "tick"},
    "ray_tpu.serve.engine.preempt": {"park", "resume"},
    "ray_tpu.serve.engine.decode_loop": {"__init__"},
    "ray_tpu.parallel.spmd": {"make_train_step", "make_eval_step"},
}

#: Dotted-call suffixes whose RESULT lives on device (a jit program or
#: a jnp op) — used by the hot-path rule to track which locals are
#: device values; syncing one of them is a finding.
DEVICE_PRODUCER_SUFFIXES = {
    "decode_chunk", "verify_chunk", "prefill", "prefill_inplace",
    "decode_step",
}
DEVICE_PRODUCER_PREFIXES = ("jnp.", "jax.numpy.")

#: Dotted-call suffixes that move device values to HOST (their results
#: are safe to float()/int()/branch on). ``_fetch`` is the engine's one
#: counted sync point.
HOST_FETCH_SUFFIXES = {"_fetch", "device_get", "block_until_ready"}

#: Call names that synchronize device->host. Flagged in hot-path
#: functions regardless of operand tracking (the single allowed site
#: carries the inline allow-comment).
HOST_SYNC_CALL_SUFFIXES = {"device_get", "item"}

#: Clamp/bound call names: a dynamic_update_slice start expression
#: containing one of these counts as "provably bounded". Anything else
#: non-constant is a finding — XLA silently CLAMPS an out-of-range
#: start, so an unbounded traced start can slide a window backwards
#: over valid rows (the PR 3 scratch-row hazard).
DUS_CLAMP_CALLS = {"clip", "minimum", "maximum", "where", "min", "max",
                   "mod", "remainder"}

#: Reductions that produce a sub-2D intermediate inside a Pallas TPU
#: kernel body unless keepdims=True — plus 1D iota and cross-lane
#: reshapes, the classic Mosaic lowering failures (use
#: lax.broadcasted_iota and >=2D intermediates; PR 6 worked around
#: each of these by hand before they became rules).
PALLAS_REDUCTIONS = {"sum", "max", "min", "mean", "prod", "any", "all"}

#: Modules whose sharded-equivalence paths must initialize RNG ONCE on
#: host and ``device_put`` the result: with jax<0.5 non-partitionable
#: threefry, jitted RNG VALUES depend on out_shardings, so a
#: ``jax.random.PRNGKey`` re-init inside a mesh context makes
#: "sharded == unsharded" comparisons vacuously flaky (PR 6 dryrun).
RNG_SINGLE_INIT_MODULES = {"__graft_entry__", "bench"}

#: With-context markers for "inside a mesh scope" (rng-reinit rule):
#: matched case-insensitively as substrings of the unparsed context
#: expression, so ``with mesh_context(m)``, ``with mesh:`` and
#: ``with use_abstract_mesh(...)`` all count.
MESH_CONTEXT_MARKERS = ("mesh",)

# ======================================================================
# Distributed RPC-contract invariants (rule family "dist", distlint.py).
#
# Each table encodes a protocol bug shipped BY HAND in an earlier PR:
# PR 4's round-2 review found a direct head notify overtaking the same
# process's still-queued batched object_added (permanent stale
# directory); PR 8's first cut of rpc_cluster_leases fanned out
# serially and outran its caller's deadline on mid-death nodes, and its
# retry windows were exhausted before a SIGKILLed head respawned; PRs
# 8-10 each appended to RETRY_SAFE_RPCS as a review afterthought — or
# forgot to.
# ======================================================================

#: Modules that own a BATCHED object-directory outbox, mapped to the
#: only functions allowed to send directory frames on the wire. Any
#: other ``notify``/``call`` of an OUTBOX_METHODS method from these
#: modules bypasses the ordered stream — the frame can overtake (or be
#: overtaken by) a still-queued add/remove of the same object.
OUTBOX_OWNER_MODULES: dict[str, set[str]] = {
    "ray_tpu.core.cluster_core": {"_flush_object_notifies"},
    "ray_tpu.cluster.node_manager": {"_head_object_batch"},
}
#: Object-directory update methods that must ride the outbox stream.
OUTBOX_METHODS = {"object_added", "object_removed", "object_batch"}

#: Modules whose loops fan RPCs out per node / replica / worker. A
#: SERIAL loop of blocking calls with only per-call timeouts has an
#: unbounded total: N mid-death peers x one control timeout each
#: outruns every caller's own deadline (the PR 8 cluster_leases bug).
DIST_FANOUT_MODULES = {
    "ray_tpu.cluster.head",
    "ray_tpu.cluster.node_manager",
    "ray_tpu.core.cluster_core",
    "ray_tpu.cluster.worker_main",
    "ray_tpu.serve._private.controller",
    "ray_tpu.autoscaler.autoscaler",
}
#: Blocking client-call attribute names the fan-out rule looks for
#: inside a loop body.
FANOUT_RPC_ATTRS = {"call", "retrying_call", "call_into"}
#: Concurrency evidence INSIDE the loop body: pipelined/async dispatch
#: or per-item threads make a serial-total bound irrelevant.
FANOUT_CONCURRENCY_ATTRS = {"call_async", "submit", "start"}
FANOUT_THREAD_SUFFIXES = ("Thread",)

#: Names that read as wall-clock deadline/timeout state for the
#: wall-clock-deadline rule: ``time.time()`` feeding arithmetic or
#: comparisons against one of these must be ``time.monotonic()`` (an
#: NTP step mid-wait stretches or collapses the deadline). Plain
#: timestamping (span starts, cross-process freshness stamps) is
#: exempt — those NEED the epoch clock.
WALLCLOCK_DEADLINE_NAME_RE = re.compile(
    r"(deadline|timeout|timeout_s|expire|expiry|expires)", re.IGNORECASE)

#: Base classes known (from their own module) to set ``chaos_role`` in
#: ``__init__`` — AST analysis is per-file, so subclasses of these are
#: exempt from missing-chaos-role.
CHAOS_ROLE_BASES = {"ClusterCore", "WorkerRuntime"}

# ======================================================================
# Resource-lifetime invariants (rule family "res", reslint.py).
#
# The single most recurring post-review bug class across PRs 1-11:
# PR 8's lease-table leak (head-driven creations' leases had no owner
# to return them), PR 2's forever-pinned borrows (the release half of
# the borrow protocol was simply missing), PR 4's dead-creator PENDING
# placeholders and the leaking _local_objects mirror, unjoined daemon
# threads re-fixed in three different PRs, and unbounded memo/registry
# dicts (the PR 11 return-lease memo needed a hand-picked 4096 cap in
# review). Each table below feeds a reslint rule; the runtime half is
# devtools/res_debug.py (RTPU_DEBUG_RES=1).
# ======================================================================

#: Constructor names whose result is a RELEASABLE handle for the
#: acquire-without-release rule (matched on the dotted call target's
#: last component). ``BufferLease`` wraps pinned shm views — dropping
#: one on an error path pins the arena slot forever (PR 2's borrow-pin
#: shape).
RES_ACQUIRE_CONSTRUCTORS = {"BufferLease"}

#: Attribute-call names that acquire a releasable resource
#: (``store.pin(...)``, ``buf.pin()``). Kept separate from the
#: constructors so fixtures can exercise both shapes.
RES_ACQUIRE_ATTRS = {"pin"}

#: Attribute-call names that release a tracked resource. ``seal`` and
#: ``abort`` resolve a store create; ``return_lease`` resolves a grant.
RES_RELEASE_ATTRS = {"release", "close", "unpin", "free", "abort",
                     "seal", "return_lease", "cancel"}

#: Failure-arm cleanup evidence for the begin-without-commit rule: a
#: handler that calls one of these attrs — or a same-class helper whose
#: NAME matches RES_CLEANUP_NAME_RE — resolves the in-flight
#: reservation (``_fail_roster`` releases every active slot, which
#: clears the pending speculation).
RES_COMMIT_ATTRS = {"commit_speculation", "release"}
RES_CLEANUP_NAME_RE = re.compile(
    r"(fail|abort|rollback|release|clean|reset|clear)", re.IGNORECASE)

#: Modules whose classes hold long-lived registries fed by RPC handlers
#: or daemon loops — the unbounded-registry-growth rule only scans
#: these (a dataclass accumulating in a batch script is not the bug
#: class; a server-side dict that grows per request forever is).
RES_REGISTRY_MODULES = {
    "ray_tpu.cluster.head",
    "ray_tpu.cluster.node_manager",
    "ray_tpu.cluster.worker_main",
    "ray_tpu.cluster.protocol",
    "ray_tpu.core.cluster_core",
    "ray_tpu.serve._private.controller",
    "ray_tpu.serve._private.router",
    "ray_tpu.serve._private.proxy",
    "ray_tpu.serve._private.slo",
    # PR 19 serving state: per-tenant WFQ lanes (idle-reaped unless
    # pinned by configure) and streaming cursor slots (settled on
    # done/error/cancel or the TTL reaper).
    "ray_tpu.serve._private.qos",
    "ray_tpu.serve._private.replica",
    "ray_tpu.serve.engine.core",
    "ray_tpu.serve.engine.drafter",
    "ray_tpu.serve.engine.kv_fleet",
    "ray_tpu.serve.engine.handoff",
    "ray_tpu.serve.engine.preempt",
    "ray_tpu.devtools.rpc_debug",
    "ray_tpu.devtools.res_debug",
    "ray_tpu.util.tracing",
    "ray_tpu.util.metrics",
}

#: Method-name heuristics for the registry rule: growth sites are RPC
#: handlers and long-lived loops (plus same-class helpers they call);
#: a method whose name matches the reaper RE counts as eviction
#: evidence for every attr it touches.
RES_LOOP_NAME_RE = re.compile(r"(_loop$|_forever$|_main$)")
RES_REAPER_NAME_RE = re.compile(
    r"(reap|evict|prune|sweep|expire|trim|clean|drain|gc|invalidate|"
    r"remove|forget|scrub)", re.IGNORECASE)

#: Attribute-call names that shrink a container (eviction evidence),
#: checked class-wide on the same ``self.<attr>``.
RES_EVICT_ATTRS = {"pop", "popleft", "popitem", "clear", "discard",
                   "remove", "popright"}

#: Thread-lifecycle rule: a class exposing one of these methods owns
#: its threads' teardown; every daemon ``Thread``/``Timer`` attr must
#: be joined/cancelled — or a stop-event set — somewhere REACHABLE from
#: one of them through same-class helper calls (PR 5's daemon-no-join
#: only required a join *somewhere in the class*; the lease-reaper
#: regression showed the join has to be on the stop path to matter).
RES_STOP_METHOD_NAMES = {"stop", "close", "shutdown", "__exit__",
                         "__del__"}
RES_STOP_EVENT_NAME_RE = re.compile(
    r"(stop|shutdown|close|done|exit|quit)", re.IGNORECASE)

#: fd-leak-on-error: calls that open an OS-level handle. Dotted-suffix
#: match for the socket forms; exact Name match for builtins.
RES_OPEN_CALL_SUFFIXES = {"socket.socket", "socket.create_connection",
                          "socket.fromfd", "os.fdopen", "os.open"}
RES_OPEN_NAME_CALLS = {"open"}
#: Closing attrs for the fd rule (shutdown alone wakes readers but the
#: fd still needs close; either counts as "handled" here — the
#: close-without-shutdown rule owns the pairing).
RES_CLOSE_ATTRS = {"close", "shutdown", "detach"}

# ======================================================================
# Channel-protocol invariants (rule family "chan", chanlint.py).
#
# PRs 15-19 made pre-negotiated channels (shm SPSC rings, peer
# sockets, pickle-5 scatter frames) the hot data plane — and every
# recent real bug lived there: the PR 19 ``ring.py _spill_in``
# spill-reclaim race (writer close unlinked a side-file the reader was
# still opening), seq inversions on the peer socket, credit-window
# stalls, and mutate-after-send aliasing on zero-copy frames. Each
# table below feeds a chanlint rule; the runtime half is
# devtools/chan_debug.py (RTPU_DEBUG_CHAN=1).
# ======================================================================

#: Receiver-name heuristic: a call like ``X.write(v, seq)`` /
#: ``X.read(seq)`` is only treated as a CHANNEL op when the receiver
#: name looks channel-ish — bare ``.write``/``.read`` on files and
#: sockets must not light the seq/deadline rules up repo-wide.
CHAN_RECEIVER_RE = re.compile(
    r"(^|_)(chan|channel|ring|edge|lane)(nel|s)?($|_)", re.IGNORECASE)

#: Ring cursor publish evidence: storing the write cursor via the
#: ``_set_u64(_O_WPOS, ...)`` idiom (or any *pos-named helper). The
#: publish must come AFTER the payload memcpy into the mmap — a
#: publish that precedes the fill hands the reader a cursor over
#: garbage bytes.
CHAN_CURSOR_PUBLISH_RE = re.compile(r"(wpos|write_pos|_O_WPOS)")
#: The mmap/buffer objects whose subscript-store is "the payload fill".
CHAN_MM_NAME_RE = re.compile(r"(^|_)(mm|mmap|buf|shm)($|_)")

#: Spill-ledger attr names (the pin side of the PR 19 race) and the
#: evidence that a teardown path OBSERVES consumption before
#: reclaiming (settle helper, rpos check, or the reclaim grace poll).
CHAN_SPILL_ATTR_RE = re.compile(r"spill", re.IGNORECASE)
CHAN_SETTLE_EVIDENCE_RE = re.compile(
    r"(settle|rpos|_O_RPOS|reclaim_grace|\.rd\b|claim)")

#: Reader-side inbox queues for the ack-before-consume rule: the ack
#: must FOLLOW the application-side ``q.get`` (acking on socket
#: receipt re-opens the credit window before the app consumed).
CHAN_INBOX_NAME_RE = re.compile(r"(^|_)(q|queue|inbox)($|_)")

#: Modules allowed to pass raw seqs into channel write/read — the
#: auto-seq facades themselves and the transports under them. Anyone
#: else routing a literal/derived seq into ``.write(v, seq)`` can mint
#: a gap or duplicate the witness then sees as send-seq-gap.
CHAN_SEQ_EXEMPT_MODULES = {
    "ray_tpu.dag.compiled_dag",
    "ray_tpu.dag.channel",
    "ray_tpu.dag.ring",
    "ray_tpu.dag.peer",
    # CpuCommunicator keeps per-peer monotonic counters — it IS an
    # auto-seq facade (one stream per (src, dst) rank pair).
    "ray_tpu.dag.communicator",
}

#: Transport modules whose classes dial peers: every
#: ``socket.create_connection`` there needs a _GONE/liveness handling
#: branch class-wide (a dial with no death branch spins forever on a
#: torn-down reader).
CHAN_TRANSPORT_MODULES = {"ray_tpu.dag.peer"}
CHAN_LIVENESS_RE = re.compile(
    r"(gone|alive|liveness|dead|_GONE)", re.IGNORECASE)

#: Mutating attribute-calls for the mutate-after-send rule: calling
#: one of these on a buffer AFTER it was handed to a zero-copy send
#: races the reader's view of the frame.
CHAN_MUTATING_ATTRS = {"fill", "sort", "resize", "put", "setfield",
                       "partition", "byteswap", "append", "extend",
                       "insert", "update", "clear"}
