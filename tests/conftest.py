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
    # Tier-1 CI runs `-m 'not slow'` (ROADMAP): long sweeps opt out of
    # the 870s budget with this marker and run in the full suite only.
    config.addinivalue_line(
        "markers", "slow: long-running sweep excluded from tier-1")


@pytest.fixture
def local_init():
    import ray_tpu

    ray_tpu.init(local_mode=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def cluster_init():
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
