"""Compiled actor-DAG execution (SURVEY M5; reference test model:
python/ray/dag/tests/experimental/test_accelerated_dag.py).
"""

import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode


# Logical CPUs: every test gangs up 2-3 actors that live for the module
# (handle-scope actor GC is a known gap — reference kills actors when the
# last handle dies).
@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=24)
    yield rt
    ray_tpu.shutdown()


@ray_tpu.remote
class Adder:
    def __init__(self, bias=0):
        self.bias = bias
        self.calls = 0

    def add(self, x):
        self.calls += 1
        return x + self.bias

    def boom(self, x):
        raise ValueError("deliberate")

    def ncalls(self):
        return self.calls


def test_linear_pipeline(cluster):
    a = Adder.remote(bias=1)
    b = Adder.remote(bias=10)
    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    compiled = dag.experimental_compile()
    try:
        for i in range(10):
            assert compiled.execute(i).get() == i + 11
    finally:
        compiled.teardown()


def test_fan_out_multi_output(cluster):
    a = Adder.remote(bias=100)
    b = Adder.remote(bias=200)
    with InputNode() as inp:
        dag = MultiOutputNode([a.add.bind(inp), b.add.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        out = compiled.execute(5).get()
        assert out == [105, 205]
    finally:
        compiled.teardown()


def test_pipelined_rounds_overlap(cluster):
    """Submitting several rounds before reading any must work (channel
    capacity pipelining)."""
    a = Adder.remote(bias=2)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    compiled = dag.experimental_compile()
    try:
        refs = [compiled.execute(i) for i in range(6)]
        assert [r.get() for r in refs] == [i + 2 for i in range(6)]
    finally:
        compiled.teardown()


def test_error_propagates_to_driver(cluster):
    a = Adder.remote()
    b = Adder.remote(bias=1)
    with InputNode() as inp:
        dag = b.add.bind(a.boom.bind(inp))
    compiled = dag.experimental_compile()
    try:
        with pytest.raises(ValueError, match="deliberate"):
            compiled.execute(1).get()
        # The DAG survives an error round: next round still works...
        with pytest.raises(ValueError, match="deliberate"):
            compiled.execute(2).get()
    finally:
        compiled.teardown()


def test_actor_still_serves_normal_calls(cluster):
    a = Adder.remote(bias=3)
    with InputNode() as inp:
        dag = a.add.bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(1).get() == 4
        # Regular RPC path unaffected by the resident DAG loop.
        assert ray_tpu.get(a.ncalls.remote(), timeout=30) >= 1
    finally:
        compiled.teardown()


def test_dag_faster_than_rpc_per_call(cluster):
    """The whole point: a compiled round trip must beat two scheduled actor
    calls (channel hop vs RPC/scheduling)."""
    a = Adder.remote(bias=1)
    b = Adder.remote(bias=1)
    # RPC chain timing
    ray_tpu.get(b.add.remote(ray_tpu.get(a.add.remote(0))))  # warm
    t0 = time.perf_counter()
    n = 30
    for i in range(n):
        ray_tpu.get(b.add.remote(ray_tpu.get(a.add.remote(i))))
    rpc_dt = time.perf_counter() - t0

    with InputNode() as inp:
        dag = b.add.bind(a.add.bind(inp))
    compiled = dag.experimental_compile()
    try:
        compiled.execute(0).get()  # warm
        t0 = time.perf_counter()
        for i in range(n):
            compiled.execute(i).get()
        dag_dt = time.perf_counter() - t0
    finally:
        compiled.teardown()
    assert dag_dt < rpc_dt, (dag_dt, rpc_dt)


def test_cpu_communicator_ring(cluster):
    from ray_tpu.dag import CpuCommunicator

    comms = CpuCommunicator.create_group(3)

    @ray_tpu.remote
    class RingNode:
        def __init__(self, comm):
            self.comm = comm

        def exchange(self, value):
            nxt = (self.comm.rank() + 1) % self.comm.world_size()
            prv = (self.comm.rank() - 1) % self.comm.world_size()
            self.comm.send(value, nxt)
            return self.comm.recv(prv)

    nodes = [RingNode.remote(c) for c in comms]
    out = ray_tpu.get([n.exchange.remote(i) for i, n in enumerate(nodes)],
                      timeout=60)
    assert out == [2, 0, 1]  # each received its predecessor's value
