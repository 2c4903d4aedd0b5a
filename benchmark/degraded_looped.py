"""Show that a looped cell's ``correct`` can fail: its replica brought up
wrong in one of six ways, held by ``drivers/serve_looped.py`` to the
float32 reference on the weights and the configuration as made.

    python3 benchmark/degraded_looped.py --workload ouro26b.math.flood --control shared_cache[,no_norm_between,...] [--seed 1,2]

``--control`` (several with commas: one process, one after another):
``shared_cache`` (every pass reads and writes the LAST pass's cache
entries: one cache where four are stated, the publisher's "last-step
reuse", a different model), ``no_norm_between`` (the final norm left
out BETWEEN passes: the next pass is handed the stack's output as it
stands; the gate and the head still read the normed state),
``no_branch_norms`` (the two norms of a block's branch outputs left
out: a pre-norm block), ``three_passes`` (``total_ut_steps`` 3 where 4
are stated), ``stream_bf16`` (the residual stream handed from block to
block rounded to bf16, the nearest precision under the float32 the
configuration states for it: no logit shows it, the check's fourth
limit does), ``int8`` (every matrix rounded to 127 steps of its
column's largest entry and widened again: the nearest precision under
the bf16 the configuration's products state). Or ``none`` (the sound
program: must pass). ``--seed`` takes several with commas too. Exits 0
if the check refused every control asked for (``none``: if it passed)
and prints what it said, which limits refused and every reading, 1
otherwise. Needs the chip, like `run.py`; ``--rehearse`` runs the tiny
sizes on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import degraded_hybrid, degraded_kda  # noqa: E402

_copy_of = degraded_hybrid._copy_of
_remade = degraded_kda._remade


def _shared_cache(model):
    return _copy_of(model, _entry=lambda u, layer_idx, cfg: (
        (cfg.n_loops - 1) * cfg.n_layers + layer_idx))


def _no_norm_between(model):
    return _copy_of(model, _between_passes=lambda x, h: x)


def _no_branch_norms(model):
    return _copy_of(model, _branch_norm=lambda y, gain, cfg: y)


def _stream_bf16(model):
    """Every block hands its output on rounded to bf16 and widened
    again (`lax.reduce_precision`: a pair of converts inside one
    program is excess precision the chip's compiler may drop)."""
    from jax import lax

    sound = model._after_attention

    def _after_attention(x, attn_out, layer, cfg):
        y, branches = sound(x, attn_out, layer, cfg)
        return lax.reduce_precision(y, exponent_bits=8,
                                    mantissa_bits=7), branches

    return _copy_of(model, _after_attention=_after_attention)


def _int8(params):
    """Every matrix rounded to 127 steps of its column's largest entry,
    IN ITS OWN BUFFER (donated: two sets of the cell's weights do not
    fit beside its cache); norms and the exit gate kept."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def rounded(a):
        wide = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wide), axis=-2, keepdims=True) / 127.0
        return (jnp.round(wide / jnp.maximum(scale, 1e-30))
                * scale).astype(a.dtype)

    def leaf(path, a):
        name = path[-1].key
        return (rounded(a) if a.ndim >= 2 and (
            name.startswith("w") or name in ("embed", "head")) else a)

    return jax.tree_util.tree_map_with_path(leaf, params)


_same = lambda params: params  # noqa: E731

# control -> (the configuration's changed fields; its model module's
# replaced names; what the ENGINE's parameters become); the reference
# keeps the driver's own of all three.
CONTROLS = {
    "none": ({}, None, _same),
    "shared_cache": ({}, _shared_cache, _same),
    "no_norm_between": ({}, _no_norm_between, _same),
    "no_branch_norms": ({}, _no_branch_norms, _same),
    "three_passes": ({"n_loops": 3}, None, _same),
    "stream_bf16": ({}, _stream_bf16, _same),
    "int8": ({}, None, _int8),
}
# Controls whose change of the weights consumes the tree it is given.
DONATES = {"int8"}


def degraded(builder, control: str):
    """``builder`` with the engine's side of it degraded; its reference
    reads the configuration file and the weights as the seed made
    them."""
    change_cfg, change_model, change_params = CONTROLS[control]
    made = {}

    def config(c, **kw):
        cfg = dataclasses.replace(builder.config(c, **kw), **change_cfg)
        if change_model is None:
            return cfg
        model = types.SimpleNamespace(**{**vars(cfg.model),
                                         **change_model(cfg.model)})
        return _remade(cfg, {"model": property(lambda self: model)})

    def init_params(cfg, seed):
        made["params"] = builder.init_params(cfg, seed)
        if control not in DONATES:
            return change_params(made["params"])
        # Two sets of the weights do not fit beside the cache: they are
        # rounded in their own buffers, and the reference reads the
        # sound ones from the host's memory.
        import jax
        import numpy as np

        on_device, made["params"] = made["params"], jax.tree.map(
            lambda a: np.array(a, copy=True), made["params"])
        return change_params(on_device)

    def as_made(fn):
        return lambda params, *rest: fn(made["params"], *rest)

    ref = builder.reference
    return types.SimpleNamespace(
        config=config, init_params=init_params,
        reference=types.SimpleNamespace(
            logits_at=as_made(ref.logits_at), gates_at=as_made(ref.gates_at),
            both_at=as_made(ref.both_at)))


def _checks_of(driver, ctx) -> dict:
    """The check's readings of a replica that passed, closed; nothing
    of it is kept."""
    _, engine, _, checks = driver.bring_up(ctx)
    engine.close()
    return checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True,
                    help="one of %s, or several with commas"
                    % ", ".join(sorted(CONTROLS)))
    ap.add_argument("--seed", default="0",
                    help="a seed, or several with commas")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    controls = args.control.split(",")
    unknown = sorted(set(controls) - set(CONTROLS))
    if unknown:
        ap.error(f"no control {unknown}; there are {sorted(CONTROLS)}")

    from benchmark.drivers import common
    from benchmark.harness import context

    wrong = 0
    for seed in (int(s) for s in args.seed.split(",")):
        manifest, ctx, dev = context.build(
            ROOT, args.workload, seed=seed, seconds=0.0, t_start=T_START,
            rehearse=args.rehearse)
        sound_builder = ctx.builder
        for control in controls:
            # The last control's replica is gone before the next one's
            # weights are made: two do not fit the chip.
            gc.collect()
            one = copy.copy(ctx)
            one.builder = degraded(sound_builder, control)
            sound = control == "none"
            said = {"control": control, "seed": seed, "device": dev}
            try:
                checks = _checks_of(manifest.driver(ctx.config["driver"]),
                                    one)
            except common.Incorrect as refused:
                print(json.dumps(dict(
                    said, refused=str(refused),
                    limits=getattr(refused, "limits", None),
                    readings=getattr(refused, "readings", None))),
                    flush=True)
                wrong += sound
                continue
            print(json.dumps(dict(said, passed=checks)), flush=True)
            wrong += not sound
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
