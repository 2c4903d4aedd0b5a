"""Tier-1 guard: the repo lints clean against its checked-in baseline,
across ALL FIVE rule families.

A NEW violation of any codified invariant — concurrency family (lock
order, blocking-under-lock, close-without-shutdown, banned jax mesh /
dashboard APIs, swallowed exceptions, unjoined daemon threads), jax
family (closure-captured-array-into-jit, donation-then-read,
host-sync-in-hot-path, unclamped-dynamic-update-slice,
pallas-shape-rules, rng-reinit-per-mesh), dist family
(unclassified-rpc-handler, retry-unsafe-call,
direct-notify-bypasses-outbox, serial-fanout-no-deadline,
wall-clock-deadline, missing-chaos-role), res family
(acquire-without-release, begin-without-commit,
unbounded-registry-growth, thread-without-stop, fd-leak-on-error), or
chan family (chan-cursor-publish-order, chan-spill-pin-unreleased,
chan-ack-before-consume, chan-raw-seq-send,
chan-register-without-unregister, chan-dial-without-liveness,
chan-blocking-op-no-deadline, chan-mutate-after-send) —
fails this test, the same check `python -m ray_tpu.devtools.lint` runs
standalone. After an intentional change, regenerate with
``python -m ray_tpu.devtools.lint --write-baseline`` (add
``--family X`` to touch only one family's section).
"""

from __future__ import annotations

from ray_tpu.devtools import lint

_FRESH_ALL = None


def _fresh(families=lint.FAMILIES):
    """New findings restricted to ``families``. ONE repo scan (all
    families — exactly what the CLI default runs) shared across the
    tests here: per-family filtering on the result is equivalent to a
    per-family run, and three full AST passes over the repo would
    triple this module's tier-1 cost."""
    global _FRESH_ALL
    if _FRESH_ALL is None:
        root, paths = lint.default_roots()
        findings = lint.lint_paths(paths, root, families=lint.FAMILIES)
        baseline = lint.load_baseline(lint.DEFAULT_BASELINE)
        _FRESH_ALL = lint.new_findings(findings, baseline)
    want = set(families)
    return [f for f in _FRESH_ALL
            if lint.RULE_FAMILY.get(f.rule, "concurrency") in want]


def test_repo_lints_clean_against_baseline():
    fresh = _fresh()
    assert not fresh, (
        "new rtpu-lint findings (fix, suppress inline, or "
        "--write-baseline):\n" + "\n".join(str(f) for f in fresh))


def test_repo_jax_family_clean_with_empty_baseline_section():
    """The jax family holds a stronger line than the concurrency one:
    its baseline section is EMPTY (every in-tree finding was fixed or
    justified inline), so any jax-rule finding anywhere in the repo is
    new debt. Keep it that way — fix or allow-comment, don't baseline."""
    fresh = _fresh(families=("jax",))
    assert not fresh, (
        "new jax-lint findings (fix or allow-comment with a one-line "
        "justification — the jax baseline section stays empty):\n"
        + "\n".join(str(f) for f in fresh))
    baseline = lint._read_baseline_json(lint.DEFAULT_BASELINE)
    assert baseline["families"]["jax"]["findings"] == {}


def test_repo_res_family_clean():
    """The res family holds the same strong line as jax and dist: its
    baseline section is EMPTY — every releasable handle is released on
    every path, every registry fed by a handler or loop has eviction
    evidence, every daemon thread stops on the teardown path, every fd
    survives its error paths. Resource lifetime is the single most
    re-found bug class across PRs 1-11 (the lease-table leak, the
    forever-pinned borrows, the _local_objects mirror, the unjoined
    threads): fix or allow-comment new findings, never baseline them —
    ROADMAP item 3's durable control plane is only trustworthy if its
    tables provably don't leak."""
    fresh = _fresh(families=("res",))
    assert not fresh, (
        "new res-lint findings (fix or allow-comment with a one-line "
        "justification — the res baseline section stays empty):\n"
        + "\n".join(str(f) for f in fresh))
    baseline = lint._read_baseline_json(lint.DEFAULT_BASELINE)
    assert baseline["families"]["res"]["findings"] == {}


def test_repo_dist_family_clean():
    """Like the jax family, the dist family holds the stronger line:
    its baseline section is EMPTY — every RPC handler is classified,
    every retry path deadline-bounded on a monotonic clock, every
    directory frame rides its outbox, every server has a chaos role.
    Any dist finding anywhere in the repo is new debt: fix it or
    allow-comment with justification, never baseline it (ROADMAP item
    3's replay/re-delivery semantics depend on this contract holding
    machine-checked, not hand-waved)."""
    fresh = _fresh(families=("dist",))
    assert not fresh, (
        "new dist-lint findings (fix or allow-comment with a one-line "
        "justification — the dist baseline section stays empty):\n"
        + "\n".join(str(f) for f in fresh))
    baseline = lint._read_baseline_json(lint.DEFAULT_BASELINE)
    assert baseline["families"]["dist"]["findings"] == {}


def test_repo_chan_family_clean():
    """The chan family holds the same strong line as jax/dist/res: its
    baseline section is EMPTY — ring writers publish after the fill,
    spill reclaims observe consumption, acks follow application
    consume, seqs route through the auto-seq facades, registrations
    have death-scrubs, dials have liveness branches, blocking channel
    ops carry deadlines, and sent buffers are never mutated in place.
    Every recent real data-plane bug (the PR 19 _spill_in race, peer
    seq inversions, credit stalls) lived in this layer: fix or
    allow-comment new findings, never baseline them. The dynamic half
    is chan_debug.py's RTPU_DEBUG_CHAN witness."""
    fresh = _fresh(families=("chan",))
    assert not fresh, (
        "new chan-lint findings (fix or allow-comment with a one-line "
        "justification — the chan baseline section stays empty):\n"
        + "\n".join(str(f) for f in fresh))
    baseline = lint._read_baseline_json(lint.DEFAULT_BASELINE)
    assert baseline["families"]["chan"]["findings"] == {}
