"""Accelerator managers: detection, slice topology, process isolation.

Parity target: the reference's pluggable accelerator managers
(reference: python/ray/_private/accelerators/accelerator.py ABC;
tpu.py:70 TPUAcceleratorManager — GCE/GKE metadata probing :14-47,
TPU_VISIBLE_CHIPS isolation :154, pod-type detection :197, and the
``TPU-<type>-head`` slice resources used for gang placement). TPU-first
here: the TPU manager is the real one, the ABC keeps the door open for
other vendors without multi-vendor code paths in the core.

All probing is env-mockable (the reference mocks GCE metadata the same
way in tests/accelerators/test_tpu.py): set ``RTPU_TPU_CHIPS``,
``RTPU_TPU_ACCELERATOR_TYPE`` and ``RTPU_TPU_WORKER_ID`` to simulate any
slice shape on CPU machines.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

# GCE instance metadata endpoints (reference: tpu.py:14-21).
_GCE_METADATA_URL = ("http://metadata.google.internal/computeMetadata"
                     "/v1/instance/attributes/{}")
_METADATA_HEADERS = {"Metadata-Flavor": "Google"}

# chips per host by generation (reference: tpu.py pod-shape math — v2/v3
# host = 8 cores / 4 chips; v4/v5p host = 4 chips; v5e/v6e host = up to 8
# single-core chips).
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8,
                   "v5e": 8, "v6e": 8}
# Accelerator-type chip counts count CORES for v2-v4 (v3-8 = 8 cores = 4
# chips) and CHIPS for v5e onward (reference: tpu.py:197 pod detection).
_CORES_PER_CHIP = {"v2": 2, "v3": 2, "v4": 1, "v5p": 1, "v5litepod": 1,
                   "v5e": 1, "v6e": 1}


class AcceleratorManager:
    """ABC (reference: accelerator.py): one per vendor."""

    @staticmethod
    def get_resource_name() -> str:
        raise NotImplementedError

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        raise NotImplementedError

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        raise NotImplementedError

    @staticmethod
    def set_visible_accelerators(ids: list) -> None:
        raise NotImplementedError


def _gce_metadata(key: str, timeout: float = 1.0) -> Optional[str]:
    """One GCE metadata attribute, or None off-GCE. Env overrides first —
    tests and non-GCE deployments never hit the network."""
    env = os.environ.get(f"RTPU_TPU_{key.upper().replace('-', '_')}")
    if env is not None:
        return env
    try:  # pragma: no cover — requires GCE
        import urllib.request

        req = urllib.request.Request(_GCE_METADATA_URL.format(key),
                                     headers=_METADATA_HEADERS)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read().decode()
    except Exception:
        return None


def local_chip_nodes() -> list:
    """Device nodes of the TPU chips attached to this host, without
    loading a TPU runtime: ``/dev/accel*``, or on hosts that expose the
    chips through VFIO the numbered group nodes of ``/dev/vfio`` (its
    ``vfio`` entry is the container control node, not a chip)."""
    import glob

    return sorted(glob.glob("/dev/accel*")
                  or glob.glob("/dev/vfio/[0-9]*"))


class TPUAcceleratorManager(AcceleratorManager):
    """TPU detection + slice topology (reference: tpu.py:70)."""

    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        env = os.environ.get("RTPU_TPU_CHIPS")
        if env is not None:
            try:
                return int(float(env))
            except ValueError:
                return 0
        return len(local_chip_nodes())

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        """e.g. "v5p-8" — from env override or GCE metadata
        (reference: tpu.py accelerator-type probing)."""
        return _gce_metadata("accelerator-type")

    @staticmethod
    def get_current_node_tpu_worker_id() -> Optional[int]:
        """This host's index within its slice (reference: tpu.py
        agent-worker-number metadata)."""
        v = _gce_metadata("agent-worker-number")
        try:
            return int(v) if v is not None else None
        except ValueError:
            return None

    @staticmethod
    def set_visible_accelerators(ids: list) -> None:
        """Restrict this process to the given chip indices (reference:
        TPU_VISIBLE_CHIPS isolation, tpu.py:154)."""
        os.environ["TPU_VISIBLE_CHIPS"] = ",".join(str(i) for i in ids)
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def parse_slice_shape(accelerator_type: str) -> Tuple[str, int, int]:
    """"v5p-16" -> (generation, total_chips, num_hosts).

    Mirrors the reference's pod-shape math (tpu.py:197): the numeric
    suffix counts CORES for v2-v4 generations and CHIPS from v5e on;
    hosts = ceil(chips / chips_per_host(generation))."""
    try:
        gen, _, suffix = accelerator_type.partition("-")
        units = int(suffix)
    except (ValueError, AttributeError):
        raise ValueError(
            f"malformed TPU accelerator type {accelerator_type!r} "
            f"(expected e.g. 'v5p-8')") from None
    gen = gen.lower()
    if gen not in _CHIPS_PER_HOST:
        raise ValueError(f"unknown TPU generation {gen!r}")
    chips = units // _CORES_PER_CHIP[gen]
    per_host = _CHIPS_PER_HOST[gen]
    hosts = max(1, (chips + per_host - 1) // per_host)
    return gen, chips, hosts


def slice_node_resources(accelerator_type: str,
                         worker_id: int) -> Tuple[Dict[str, float],
                                                  Dict[str, str]]:
    """(resources, labels) one slice host contributes to the cluster.

    Worker 0 carries the ``TPU-<type>-head`` resource: gang-scheduled
    jobs reserve exactly one head per slice and fan per-host actors out
    with node affinity — the reference's TPU pod scheduling pattern
    (tpu.py TPU-{pod_type}-head resources)."""
    _gen, chips, hosts = parse_slice_shape(accelerator_type)
    per_host = chips // hosts if hosts else chips
    res: Dict[str, float] = {"TPU": float(per_host)}
    if worker_id == 0:
        res[f"TPU-{accelerator_type}-head"] = 1.0
    labels = {"accelerator-type": accelerator_type,
              "tpu-worker-id": str(worker_id)}
    return res, labels
