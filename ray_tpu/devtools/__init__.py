"""rtpu devtools: project-specific static analysis + runtime checkers.

Every PR so far has shipped post-review fixes for the same bug families
(lock-ordering hazards, blocking I/O while holding a state lock, sockets
closed without shutdown under readers writing into shm, dashboard
innerHTML XSS, stray jax mesh/shard_map seams, swallowed exceptions).
This package codifies those invariants as tooling instead of reviewer
memory — the same move as the reference's lint-enforced C++ status/ID
conventions and TSan wiring:

- ``python -m ray_tpu.devtools.lint``: AST-based, stdlib-only linter
  enforcing the declared invariants against a checked-in baseline
  (``lint_baseline.json``, sectioned per rule family) — legacy
  violations are tracked-not-fatal, NEW violations fail the run. Four
  rule families: ``concurrency`` (tables in ``invariants.py``),
  ``jax`` (``jaxlint.py``: tracing-safety rules codified from the
  model path's post-review bugs — closure constant-folding into jit,
  donation-then-read, hot-path host syncs, unclamped
  dynamic_update_slice, Mosaic kernel shape rules, per-mesh RNG
  re-init), ``dist`` (``distlint.py``: the distributed RPC
  contract — every handler classified in ``protocol.py``'s
  retry/idempotency sets, retrying_call only against retry-safe
  methods, object-directory frames riding their batched outbox,
  fan-out loops deadline-bounded on a monotonic clock, every server
  class chaos-role-targetable), and ``res`` (``reslint.py``: resource
  lifetimes — releasable handles released on every path, KV
  speculation reservations resolved on the failure arm, registries
  fed by handlers/loops carrying eviction evidence, daemon threads
  stopped from the teardown path, fds surviving their error paths).
- ``lock_debug``: ``RTPU_DEBUG_LOCKS=1`` swaps the cluster core's lock
  creation for an ordering witness that records the per-thread lock
  acquisition graph, detects order cycles online, and reports
  excessive hold times via util/metrics.
- ``jax_debug``: ``RTPU_DEBUG_JAX=1`` wraps the engine's and trainer's
  jit entry points in a recompile witness (distinct-signature counts
  vs declared program budgets), counts the engine's device->host
  fetches per tag (one-sync-per-chunk is assertable), and wires
  ``jax.transfer_guard`` around engine ticks
  (``RTPU_DEBUG_JAX_TRANSFER_GUARD=disallow``). Zero overhead off.
- ``rpc_debug``: ``RTPU_DEBUG_RPC=1`` audits the RPC contract at
  dispatch — unclassified methods fail loudly, idempotent requests are
  delivered twice with responses compared (the at-most-once audit),
  and outbox frames carry per-(sender, receiver) sequence checks that
  catch add/remove inversions on arrival. Zero overhead off.
- ``res_debug``: ``RTPU_DEBUG_RES=1`` turns the acquire/release seams
  into a per-process balance registry — BufferLease pin/release, node
  lease grant/return, KV speculation begin/commit/release, store
  seal/delete gauges, tracked threads — asserted drained at
  engine/cluster close, snapshotted into every flight-recorder dump
  (``"res_debug"`` key), and aggregated cluster-wide by
  ``bench.py --chaos`` into ``leaked_resources``. Zero overhead off.
"""
