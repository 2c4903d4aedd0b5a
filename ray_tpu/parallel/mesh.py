"""Device-mesh construction and named sharding axes.

TPU-first replacement for the reference's process-group world (Ray Train wires
torch ``init_process_group`` per worker, reference `train/torch/config.py:94-163`;
collectives go through NCCL in `util/collective/collective.py:120`). Here the
unit of parallelism is a single SPMD program over a `jax.sharding.Mesh`; XLA
inserts the collectives over ICI.

Logical mesh axes (scaling-book convention):

- ``dp``   — pure data parallelism (gradient all-reduce over ICI/DCN)
- ``fsdp`` — data parallelism with parameter/optimizer sharding (ZeRO-3-style;
             XLA turns this into all-gather + reduce-scatter)
- ``tp``   — tensor (Megatron-style) parallelism inside each layer
- ``sp``   — sequence/context parallelism (ring attention over this axis)
- ``pp``   — pipeline stages (layer groups; `parallel/pipeline.py`)
- ``ep``   — expert parallelism for MoE layers (`models/mixtral.py`)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "fsdp", "sp", "pp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Sizes for each logical axis. 1 = axis unused (still present in the Mesh,
    so the same jitted program works for any configuration)."""

    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1
    tp: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.fsdp * self.sp * self.pp * self.ep * self.tp

    def axis_sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXES)

    @staticmethod
    def auto(n_devices: int, *, tp: Optional[int] = None, sp: int = 1,
             pp: int = 1, ep: int = 1, dp: int = 1) -> "MeshSpec":
        """Fill ``fsdp`` with whatever is left after the explicit axes.

        Default policy (one host / one slice): put tensor parallelism over the
        fastest ICI dimension (up to 8-way on v5p trays), FSDP over the rest.
        """
        if tp is None:
            tp = 8 if n_devices >= 8 else 1
        used = tp * sp * pp * ep * dp
        if n_devices % used:
            raise ValueError(f"{n_devices} devices not divisible by tp*sp*pp*ep*dp={used}")
        return MeshSpec(dp=dp, fsdp=n_devices // used, sp=sp, pp=pp, ep=ep, tp=tp)


def make_mesh(spec: MeshSpec, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with all six logical axes.

    Device order matters for ICI locality: ``tp`` is the innermost
    (fastest-varying) axis so tensor-parallel collectives ride nearest-neighbor
    ICI links; ``dp``/``fsdp`` are outermost so their (bigger, less frequent)
    reductions can cross DCN on multi-slice deployments.
    """
    if devices is None:
        devices = jax.devices()
    if spec.size != len(devices):
        raise ValueError(f"mesh spec {spec} needs {spec.size} devices, got {len(devices)}")
    arr = np.asarray(devices).reshape(spec.axis_sizes())
    return Mesh(arr, AXES)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    devs = [device] if device is not None else jax.devices()[:1]
    return make_mesh(MeshSpec(), devs)


def mesh_2d(n_devices: Optional[int] = None, *, tp: Optional[int] = None,
            devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """The canonical 2D **FSDP x tensor** training mesh.

    This is the production shape for dense-model pretraining (the
    scaling-book default): parameters ZeRO-3-shard over ``fsdp`` (outer
    axis — bigger, less frequent all-gather/reduce-scatter, DCN-safe)
    while each layer's matmuls split over ``tp`` (inner axis — chatty
    collectives ride nearest-neighbor ICI, see `make_mesh`). ``tp``
    defaults to the largest power of two <= min(8, n_devices) that
    divides ``n_devices``; everything left fills ``fsdp``. All other
    axes stay 1, so the mesh is logically 2D while remaining
    program-compatible with the full six-axis Mesh.

    The Llama train step needs no further wiring: `param_logical_axes`
    names every weight dim, `DEFAULT_RULES` maps embed->fsdp and
    heads/mlp/vocab->tp, and `spmd.sharded_init` materializes the
    NamedShardings (verified by `spmd.assert_params_sharded`).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = list(devices)[:n_devices]
        if len(devices) != n_devices:
            raise ValueError(
                f"mesh_2d: need {n_devices} devices, have {len(devices)}")
    n = len(devices)
    if tp is None:
        tp = largest_pow2_leq(min(8, n))
        while n % tp:
            tp //= 2
    if n % tp:
        raise ValueError(f"mesh_2d: {n} devices not divisible by tp={tp}")
    return make_mesh(MeshSpec(fsdp=n // tp, tp=tp), devices)


# ---------------------------------------------------------------------------
# Logical-axis → mesh-axis mapping (t5x-style logical annotations, minimal).
# ---------------------------------------------------------------------------

# Every tensor dimension in the model is named; this table maps the name to
# mesh axes. None = replicated along that dim.
DEFAULT_RULES: Dict[str, Optional[object]] = {
    "batch": ("dp", "fsdp"),   # batch dim sharded over all data axes
    "seq": "sp",               # sequence dim sharded for context parallelism
    # The residual stream between a block's matmul groups where the tp
    # ring engages (`parallel/collective_matmul.py`).
    "res_seq": ("sp", "tp"),
    "embed": "fsdp",           # parameters: d_model dim sharded for ZeRO-3
    "heads": "tp",             # attention heads over tensor parallel
    "kv_heads": "tp",
    "head_dim": None,
    "mlp": "tp",               # ffn hidden dim over tensor parallel
    "vocab": "tp",             # output vocab over tensor parallel
    "layers": None,            # stacked-layer leading dim (scanned over)
    "stages": "pp",            # pipeline stage dim
    "experts": "ep",           # MoE expert dim
    "kv_len": None,
    "patch_in": None,          # ViT flattened-patch input dim
    "classes": "tp",           # classifier head over tensor parallel
    "kh": None,                # conv kernel spatial dims (diffusion UNet)
    "kw": None,
    "c_in": None,              # conv input channels
    "channels": "tp",          # conv output channels over tensor parallel
}


def logical_spec(names: Sequence[Optional[str]],
                 rules: Optional[Dict[str, Optional[object]]] = None) -> P:
    """Translate per-dimension logical names into a PartitionSpec."""
    rules = rules or DEFAULT_RULES
    return P(*[rules.get(n) if n is not None else None for n in names])


def named_sharding(mesh: Mesh, names: Sequence[Optional[str]],
                   rules: Optional[Dict[str, Optional[object]]] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(names, rules))


def constrain(x, names: Sequence[Optional[str]],
              rules: Optional[Dict[str, Optional[object]]] = None):
    """`with_sharding_constraint` by logical dimension names (no-op outside jit
    over a mesh). Real spec errors (rank mismatch, unknown axis) surface —
    the no-mesh case is detected explicitly, not by matching error text."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.shape_tuple:
        return x
    return jax.lax.with_sharding_constraint(x, logical_spec(names, rules))


def mesh_context(mesh: Mesh):
    """``with mesh_context(m):`` enters ``mesh`` as the ambient mesh —
    the one place the repo calls ``jax.sharding.set_mesh`` (rtpu-lint
    banned-api keeps it so)."""
    return jax.sharding.set_mesh(mesh)  # rtpu-lint: disable=banned-api


def param_shardings(mesh: Mesh, logical_tree,
                    rules: Optional[Dict[str, Optional[object]]] = None):
    """Map a pytree of logical-name tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda names: named_sharding(mesh, names, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def largest_pow2_leq(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n > 0 else 1
