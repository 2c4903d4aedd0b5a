"""Resource vectors with fixed-point fractional accounting.

Equivalent of the reference's scheduling resource model (reference:
src/ray/common/scheduling/resource_set.h, fixed_point.h,
resource_instance_set.h), rebuilt around TPU-pod semantics: resources are
string->fixed-point maps; ``TPU`` is countable per-chip like CPU/GPU, and TPU
*slices* are modeled with head resources (e.g. ``TPU-v5e-8-head``) plus node
labels carrying slice name/topology so placement can keep an SPMD group on one
ICI domain (mirrors python/ray/_private/accelerators/tpu.py semantics).
"""

from __future__ import annotations

from typing import Dict, Iterable, Mapping, Optional

PRECISION = 10_000  # fixed-point denominator: 1.0 == 10000 units

CPU = "CPU"
GPU = "GPU"
TPU = "TPU"
MEMORY = "memory"
OBJECT_STORE_MEMORY = "object_store_memory"

# Label keys attached to nodes for topology-aware scheduling.
LABEL_SLICE_NAME = "ray_tpu.io/slice-name"
LABEL_SLICE_TOPOLOGY = "ray_tpu.io/slice-topology"
LABEL_ACCELERATOR_TYPE = "ray_tpu.io/accelerator-type"
LABEL_HOST_INDEX = "ray_tpu.io/slice-host-index"
LABEL_NODE_ID = "ray_tpu.io/node-id"


def to_fixed(v: float) -> int:
    return int(round(v * PRECISION))


def from_fixed(u: int) -> float:
    return u / PRECISION


class ResourceSet:
    """Immutable-ish demand vector (fixed-point internally)."""

    __slots__ = ("_units",)

    def __init__(self, units: Optional[Dict[str, int]] = None):
        self._units = {k: v for k, v in (units or {}).items() if v != 0}

    @classmethod
    def from_dict(cls, d: Mapping[str, float]) -> "ResourceSet":
        return cls({k: to_fixed(v) for k, v in d.items()})

    def to_dict(self) -> Dict[str, float]:
        return {k: from_fixed(v) for k, v in self._units.items()}

    def units(self) -> Dict[str, int]:
        return dict(self._units)

    def get(self, name: str) -> float:
        return from_fixed(self._units.get(name, 0))

    def is_empty(self) -> bool:
        return not self._units

    def keys(self) -> Iterable[str]:
        return self._units.keys()

    def __eq__(self, other):
        return isinstance(other, ResourceSet) and other._units == self._units

    def __repr__(self):
        return f"ResourceSet({self.to_dict()})"

    def __add__(self, other: "ResourceSet") -> "ResourceSet":
        out = dict(self._units)
        for k, v in other._units.items():
            out[k] = out.get(k, 0) + v
        return ResourceSet(out)


class NodeResources:
    """Mutable total/available pair for one node, with allocation."""

    def __init__(self, total: ResourceSet, labels: Optional[Dict[str, str]] = None):
        self.total = total
        self._avail: Dict[str, int] = total.units()
        self.labels = dict(labels or {})

    @property
    def available(self) -> ResourceSet:
        return ResourceSet(self._avail)

    def can_fit(self, demand: ResourceSet) -> bool:
        for k, v in demand.units().items():
            if self._avail.get(k, 0) < v:
                return False
        return True

    def has_total(self, demand: ResourceSet) -> bool:
        tot = self.total.units()
        return all(tot.get(k, 0) >= v for k, v in demand.units().items())

    def allocate(self, demand: ResourceSet) -> bool:
        if not self.can_fit(demand):
            return False
        for k, v in demand.units().items():
            self._avail[k] = self._avail.get(k, 0) - v
        return True

    def release(self, demand: ResourceSet) -> None:
        tot = self.total.units()
        for k, v in demand.units().items():
            self._avail[k] = min(self._avail.get(k, 0) + v, tot.get(k, 0))

    def utilization(self) -> float:
        """Max utilization over dimensions the node actually has (for packing)."""
        util = 0.0
        for k, total in self.total.units().items():
            if total <= 0:
                continue
            used = total - self._avail.get(k, 0)
            util = max(util, used / total)
        return util

    def add_dynamic(self, extra: ResourceSet) -> None:
        """Registers placement-group bundle resources (2-phase commit target)."""
        tot = self.total.units()
        for k, v in extra.units().items():
            tot[k] = tot.get(k, 0) + v
            self._avail[k] = self._avail.get(k, 0) + v
        self.total = ResourceSet(tot)

    def remove_dynamic(self, extra: ResourceSet) -> None:
        tot = self.total.units()
        for k, v in extra.units().items():
            tot[k] = max(tot.get(k, 0) - v, 0)
            self._avail[k] = max(self._avail.get(k, 0) - v, 0)
        self.total = ResourceSet(tot)


def detect_node_resources(num_cpus: Optional[float] = None,
                          num_tpus: Optional[float] = None,
                          memory: Optional[int] = None,
                          resources: Optional[Dict[str, float]] = None,
                          labels: Optional[Dict[str, str]] = None) -> NodeResources:
    """Autodetect this host's resources (CPU count, TPU chips via jax)."""
    import os

    d: Dict[str, float] = dict(resources or {})
    d[CPU] = num_cpus if num_cpus is not None else float(os.cpu_count() or 1)
    lbl = dict(labels or {})
    if num_tpus is None:
        num_tpus, tpu_labels = _detect_tpu()
        lbl.update(tpu_labels)
    if num_tpus:
        d[TPU] = num_tpus
    if memory is None:
        try:
            import psutil  # pragma: no cover - optional

            memory = int(psutil.virtual_memory().total * 0.7)
        except Exception:
            memory = 8 * 1024**3
    d[MEMORY] = float(memory)
    return NodeResources(ResourceSet.from_dict(d), lbl)


def _detect_tpu():
    """Counts locally attached TPU chips without initializing a TPU runtime.

    Uses the env override first (tests / explicit isolation), then sysfs accel
    devices. Deliberately does NOT call jax.devices(): only one process per
    host may own the TPU runtime, and the node daemon must never claim it.
    """
    import os

    from ray_tpu.core.accelerators import local_chip_nodes

    env = os.environ.get("RTPU_TPU_CHIPS")
    if env is not None:
        try:
            n = float(env)
        except ValueError:
            n = 0.0
        return n, ({LABEL_ACCELERATOR_TYPE: "TPU"} if n else {})
    chips = local_chip_nodes()
    if chips:
        return float(len(chips)), {LABEL_ACCELERATOR_TYPE: "TPU"}
    return 0.0, {}
