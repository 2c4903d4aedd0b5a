"""Cross-node compiled-DAG channels (reference test model: multi-node
compiled-graph tests over cross-node mutable-object channels)."""

import time

import pytest

import ray_tpu
from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy
from ray_tpu.dag import InputNode, MultiOutputNode
from ray_tpu.dag.channel import CrossNodeChannel


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=2)
    node = rt.add_node(num_cpus=2)
    deadline = time.time() + 30
    while time.time() < deadline:
        alive = [n for n in rt.nodes() if n["alive"]]
        if len(alive) >= 2:
            break
        time.sleep(0.25)
    yield rt, node
    ray_tpu.shutdown()


def test_dag_spans_nodes(cluster):
    """A DAG whose actors live on DIFFERENT nodes compiles with
    cross-node channels and produces correct pipelined results."""
    rt, node = cluster

    @ray_tpu.remote
    class Stage:
        def __init__(self, bias):
            self.bias = bias

        def apply(self, x):
            return x * 2 + self.bias

    # Stage A on the driver's node, stage B pinned to the second node.
    a = Stage.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=rt.node_id, soft=False)).remote(1)
    b = Stage.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=node.node_id, soft=False)).remote(10)

    with InputNode() as inp:
        mid = a.apply.bind(inp)
        out = b.apply.bind(mid)
    dag = out.experimental_compile()

    # The a->b hop and the b->driver output must be cross-node channels.
    kinds = [type(c).__name__ for c in dag._output_channels]
    assert "CrossNodeChannel" in kinds, kinds

    refs = [dag.execute(i) for i in range(12)]  # pipelined past capacity
    got = [r.get(timeout=60) for r in refs]
    assert got == [(i * 2 + 1) * 2 + 10 for i in range(12)]
    dag.teardown()


def test_dag_same_node_still_uses_shm(cluster):
    rt, _node = cluster

    @ray_tpu.remote
    class S:
        def f(self, x):
            return x + 1

    s = S.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=rt.node_id, soft=False)).remote()
    with InputNode() as inp:
        out = s.f.bind(inp)
    from ray_tpu.dag.compiled_dag import compile_dag

    dag = compile_dag(out)
    assert all(not isinstance(c, CrossNodeChannel)
               for c in dag._output_channels)
    assert dag.execute(41).get(timeout=30) == 42
    dag.teardown()


def test_dag_overlap_comm_subprocess():
    """The sender-thread path (dag_overlap_comm=1) runs the full cross-
    node pipeline correctly — exercised in a subprocess because workers
    read the flag from their spawn environment."""
    import subprocess
    import sys

    code = """
import os, sys, time, collections
sys.path.insert(0, %r)
import ray_tpu
from ray_tpu.core.task_spec import NodeAffinitySchedulingStrategy
from ray_tpu.dag import InputNode
rt = ray_tpu.init(num_cpus=2)
node = rt.add_node(num_cpus=2)
deadline = time.time() + 30
while time.time() < deadline and len(
        [n for n in rt.nodes() if n["alive"]]) < 2:
    time.sleep(0.25)

@ray_tpu.remote
class S:
    def f(self, x):
        return x + 1

a = S.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
    node_id=rt.node_id, soft=False)).remote()
b = S.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
    node_id=node.node_id, soft=False)).remote()
with InputNode() as inp:
    out = b.f.bind(a.f.bind(inp))
dag = out.experimental_compile()
w = collections.deque()
got = []
for i in range(30):
    w.append(dag.execute(i))
    if len(w) >= 4:
        got.append(w.popleft().get(timeout=60))
while w:
    got.append(w.popleft().get(timeout=60))
assert got == [i + 2 for i in range(30)], got[:5]
dag.teardown()
ray_tpu.shutdown()
print("OVERLAP_OK")
"""
    import os as _os

    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    env = dict(_os.environ, RTPU_DAG_OVERLAP_COMM="1",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code % repo],
                         capture_output=True, text=True, timeout=180,
                         env=env)
    assert "OVERLAP_OK" in out.stdout, out.stderr[-800:]
