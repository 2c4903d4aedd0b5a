"""Test fixtures.

JAX tests run on a virtual 8-device CPU mesh (the reference's analog is the
fake multi-node cluster in python/ray/cluster_utils.py + mocked accelerator
detection in tests/accelerators/test_tpu.py): real TPU hardware is never
required for the suite.
"""

import os

# Must be set before jax (imported transitively) initializes its backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("RTPU_TPU_CHIPS", "0")

import jax  # noqa: E402

# Pin the platform list before any backend is initialized: the suite
# never runs on a chip, whatever the environment it is started from.
jax.config.update("jax_platforms", "cpu")
# And the device count, where a later edit of XLA_FLAGS cannot undo it:
# a benchmark rehearsal run in-process (`benchmark/harness/context.py`,
# `rehearse=True`) appends `--xla_force_host_platform_device_count=<the
# cell's chips>`, and the last flag wins — the xdist worker whose FIRST
# jax user was tests/benchmark/test_routed_controls.py came up with one
# CPU device, and `cpu_mesh8` failed for whichever file landed there
# next (about one whole run in six: PR 31).
jax.config.update("jax_num_cpu_devices", 8)

import glob  # noqa: E402

import pytest  # noqa: E402

# Reap object-store segments leaked by SIGKILL'd clusters of previous
# runs — but ONLY segments no live process has mapped: a concurrently
# running cluster (e.g. a benchmark capture on the same host) must not
# lose its store to a test session starting next to it.
def _mapped_segments() -> set:
    mapped = set()
    for _pid in os.listdir("/proc"):
        if not _pid.isdigit():
            continue
        try:
            with open(f"/proc/{_pid}/maps") as _f:
                for _line in _f:
                    if "/dev/shm/rtpu_store_" in _line:
                        mapped.add(_line.rsplit("/", 1)[-1].strip())
        except OSError:
            continue
    return mapped


_live = _mapped_segments()
for _stale in glob.glob("/dev/shm/rtpu_store_*"):
    if os.path.basename(_stale) in _live:
        continue
    try:
        os.unlink(_stale)
    except OSError:
        pass


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'` on six xdist workers inside 1,470 s
    # (ROADMAP, "Tier-1 verify"): long sweeps opt out of that budget
    # with this marker and run in the full suite only.
    config.addinivalue_line(
        "markers", "slow: long-running sweep excluded from tier-1")


@pytest.fixture(scope="session")
def native_store():
    """Every fixture that boots a cluster asks for this first: where the
    store library can neither be found nor built, the session says so
    once and by name instead of once per cluster test."""
    from ray_tpu.core import shm_store

    try:
        return shm_store._load_lib()
    except OSError as e:
        pytest.fail(f"native store cannot be built: {e}", pytrace=False)


@pytest.fixture
def local_init():
    import ray_tpu

    ray_tpu.init(local_mode=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def cluster_init(native_store):
    import ray_tpu

    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def cpu_mesh8():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 host devices"
    yield devices[:8]
