"""What a driver is handed: the cell, its files and the run's arguments."""

from __future__ import annotations

import os
import types

from benchmark.harness import device, manifest as manifest_mod


def _rehearsal(d: dict) -> dict:
    """A file's dict with its ``rehearse`` group laid over it."""
    return {**d, **d.get("rehearse", {})}


def build(root: str, workload: str, *, seed: int, seconds: float,
          t_start: float, trace: bool = False, rehearse: bool = False):
    """(manifest, ctx, device row) for one cell. Refuses at once
    (`device.NoAccelerator`) unless JAX finds the cell's chips; turns
    the persistent compile cache on before the first compile."""
    manifest = manifest_mod.load(root)
    cell = manifest.cell(workload)
    config, mix = manifest.config(cell), manifest.traffic(cell)
    if rehearse:
        config, mix = _rehearsal(config), _rehearsal(mix)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    dev = device.require(cell["chips"], rehearse)

    from ray_tpu.util import compile_cache

    ctx = types.SimpleNamespace(
        cell=cell, config=config, traffic=mix, seed=seed, seconds=seconds,
        trace=trace, chips=cell["chips"], rehearse=rehearse, t_start=t_start,
        cache=compile_cache.configure(), kind=manifest.kind(mix),
        builder=manifest.builder(config),
        scratch_dir=os.path.join(root, ".bench_tmp", cell["name"]))
    return manifest, ctx, dev
