"""Collective (allreduce) nodes in compiled DAGs (reference test model:
python/ray/dag/tests/experimental/test_collective_dag.py — allreduce bound
across per-actor nodes, executed on the channel substrate)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode, allreduce
from ray_tpu.dag.collective_node import CollectiveGroupSpec


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=24, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


@ray_tpu.remote
class Worker:
    def __init__(self, scale):
        self.scale = scale

    def contrib(self, x):
        return np.asarray(x, dtype=np.float64) * self.scale

    def boom(self, x):
        raise RuntimeError("collective peer failure")

    def stamp(self, v):
        return ("w%d" % self.scale, v)


def _workers(n):
    return [Worker.remote(i + 1) for i in range(n)]


@pytest.mark.parametrize("n", [2, 3, 4])
def test_allreduce_sum_all_ranks(cluster, n):
    """Every rank observes the same reduced value: sum_i (x * (i+1))."""
    ws = _workers(n)
    with InputNode() as inp:
        parts = [w.contrib.bind(inp) for w in ws]
        reduced = allreduce.bind(parts, op="sum")
        dag = MultiOutputNode(reduced)
    compiled = dag.experimental_compile()
    try:
        for x in (1.0, 2.0, -3.5):
            outs = compiled.execute(np.array([x])).get()
            expect = x * sum(i + 1 for i in range(n))
            for o in outs:
                np.testing.assert_allclose(o, [expect])
    finally:
        compiled.teardown()


def test_allreduce_max_feeds_downstream(cluster):
    """Reduced values flow into further per-actor binds."""
    ws = _workers(3)
    with InputNode() as inp:
        parts = [w.contrib.bind(inp) for w in ws]
        reduced = allreduce.bind(parts, op="max")
        outs = [w.stamp.bind(r) for w, r in zip(ws, reduced)]
        dag = MultiOutputNode(outs)
    compiled = dag.experimental_compile()
    try:
        results = compiled.execute(np.array([2.0])).get()
        for (tag, v), scale in zip(results, (1, 2, 3)):
            assert tag == f"w{scale}"
            np.testing.assert_allclose(v, [6.0])  # max over 2,4,6
    finally:
        compiled.teardown()


def test_allreduce_peer_error_propagates_everywhere(cluster):
    """One participant raising must surface on every output of that round
    — and the NEXT round still works (no channel slot leaks)."""
    ws = _workers(3)
    with InputNode() as inp:
        parts = [ws[0].contrib.bind(inp), ws[1].boom.bind(inp),
                 ws[2].contrib.bind(inp)]
        reduced = allreduce.bind(parts, op="sum")
        dag = MultiOutputNode(reduced)
    compiled = dag.experimental_compile()
    try:
        ref = compiled.execute(np.array([1.0]))
        with pytest.raises(RuntimeError, match="collective peer failure"):
            ref.get()
        # Round 2 errors again (same boom), proving seqs stayed aligned.
        ref2 = compiled.execute(np.array([2.0]))
        with pytest.raises(RuntimeError, match="collective peer failure"):
            ref2.get()
    finally:
        compiled.teardown()


def test_two_groups_interleaved_bind_order_no_deadlock(cluster):
    """Two concurrent groups whose output nodes are bound in conflicting
    per-actor orders must not deadlock: compilation schedules each group
    atomically at first topo encounter, giving every actor the same
    group order regardless of bind interleaving."""
    ws = _workers(2)
    with InputNode() as inp:
        parts = [w.contrib.bind(inp) for w in ws]
        g1 = allreduce.bind(parts, op="sum")
        parts2 = [w.contrib.bind(inp) for w in ws]
        g2 = allreduce.bind(parts2, op="max")
        # Adversarial output order: w0's g1 before w1's g2 before w0's g2.
        dag = MultiOutputNode([g1[0], g2[1], g2[0], g1[1]])
    compiled = dag.experimental_compile()
    try:
        outs = compiled.execute(np.array([1.0])).get(timeout=30)
        np.testing.assert_allclose(outs[0], [3.0])  # sum of 1,2
        np.testing.assert_allclose(outs[1], [2.0])  # max of 1,2
        np.testing.assert_allclose(outs[2], [2.0])
        np.testing.assert_allclose(outs[3], [3.0])
    finally:
        compiled.teardown()


def test_partial_group_consumption_no_hang(cluster):
    """Binding only one rank's reduced output must still run every
    rank's collective op (a skipped sibling would strand the tree)."""
    ws = _workers(3)
    with InputNode() as inp:
        parts = [w.contrib.bind(inp) for w in ws]
        reduced = allreduce.bind(parts, op="sum")
        dag = reduced[0]  # ranks 1..2 discarded by the driver
    compiled = dag.experimental_compile()
    try:
        out = compiled.execute(np.array([1.0])).get(timeout=30)
        np.testing.assert_allclose(out, [6.0])
    finally:
        compiled.teardown()


def test_allreduce_validation():
    @ray_tpu.remote
    class A:
        def f(self, x):
            return x

    with pytest.raises(ValueError, match=">= 2"):
        CollectiveGroupSpec([object()], "sum")  # too few before type check
    with pytest.raises(ValueError, match="op must be"):
        CollectiveGroupSpec([object(), object()], "avg")


def test_allreduce_rejects_duplicate_actor(cluster):
    ws = _workers(1)
    with InputNode() as inp:
        p1 = ws[0].contrib.bind(inp)
        p2 = ws[0].contrib.bind(inp)
        with pytest.raises(ValueError, match="one node per actor"):
            allreduce.bind([p1, p2], op="sum")
