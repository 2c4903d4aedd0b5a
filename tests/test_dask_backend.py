"""Dask-graph scheduler over ray_tpu tasks (reference analog:
python/ray/util/dask/tests — scheduler semantics on the raw graph
protocol; runs without dask installed)."""

from operator import add, mul

import pytest

import ray_tpu
from ray_tpu.util.dask_backend import ray_tpu_dask_get


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_diamond_graph(cluster):
    dsk = {
        "a": 1,
        "b": (add, "a", 10),       # 11
        "c": (mul, "a", 3),        # 3
        "d": (add, "b", "c"),      # 14
    }
    assert ray_tpu_dask_get(dsk, "d") == 14
    # Nested key lists per the dask get contract.
    assert ray_tpu_dask_get(dsk, ["b", ["c", "d"]]) == [11, [3, 14]]


def test_nested_task_expressions(cluster):
    dsk = {
        "x": 4,
        # task nested INSIDE a task arg, and a list arg mixing keys/values
        "y": (add, (mul, "x", "x"), 1),       # 17
        "z": (sum, [(mul, "x", 2), "y", 5]),  # 8 + 17 + 5 = 30
    }
    assert ray_tpu_dask_get(dsk, "z") == 30


def test_alias_and_literals(cluster):
    dsk = {"a": 7, "b": "a", "c": (add, "b", 1)}
    assert ray_tpu_dask_get(dsk, "c") == 8
    assert ray_tpu_dask_get(dsk, "b") == 7


def test_parallel_fanout_runs_as_tasks(cluster):
    import os

    def pid_of(_):
        import os as _os

        return _os.getpid()

    dsk = {f"p{i}": (pid_of, i) for i in range(4)}
    pids = ray_tpu_dask_get(dsk, [f"p{i}" for i in range(4)])
    assert all(isinstance(p, int) for p in pids)
    assert all(p != os.getpid() for p in pids)  # ran in workers


def test_unhashable_tuple_literal(cluster):
    """A non-task tuple containing a list is a LITERAL, not a key probe
    (hashing it must not crash the scheduler)."""
    dsk = {"x": (len, ("a", [1, 2]))}
    assert ray_tpu_dask_get(dsk, "x") == 2


def _chain(n):
    dsk = {"k0": 0}
    for i in range(1, n):
        dsk[f"k{i}"] = (add, f"k{i-1}", 1)
    return dsk


def test_deep_chain_no_recursion_limit(cluster):
    """Generated graphs chain thousands of tasks; the graph walk must not
    recurse. The walk is held to that directly, at 3,000 links and well
    past any recursion limit; the cluster then runs a chain (one task per
    link, each waiting for the one before) long enough to be deep and
    short enough to take seconds.

    The cluster used to run all 3,000, in 535 s: every pending link
    parks a worker's argument fetch on the owner, and every completion
    woke every one of them (PR 31: core/memory_store.py,
    tests/test_memory_store.py). 3,000 now take about 7 s; 1,000 are
    kept here so that a runtime that loses this again costs the suite
    half a minute, not nine."""
    import sys

    from ray_tpu.util.dask_backend import _toposort

    for n in (3000, 20 * sys.getrecursionlimit()):
        order = _toposort(_chain(n))
        assert order == [f"k{i}" for i in range(n)]
    n = 1000
    assert n > sys.getrecursionlimit() - 100
    assert ray_tpu_dask_get(_chain(n), f"k{n-1}") == n - 1


def test_cycle_detection(cluster):
    dsk = {"a": (add, "b", 1), "b": (add, "a", 1)}
    with pytest.raises(ValueError, match="cycle"):
        ray_tpu_dask_get(dsk, "a")


def test_string_values_not_confused_with_keys(cluster):
    """Only hashables PRESENT in the graph are key references; other
    strings stay literals."""
    dsk = {"greet": (str.upper, "hello")}
    assert ray_tpu_dask_get(dsk, "greet") == "HELLO"
