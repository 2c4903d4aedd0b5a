"""Show that a Mamba-2 hybrid cell's ``correct`` can fail: its replica
brought up wrong in one of eleven ways, held by
``drivers/serve_hybrid.py`` to the float32 reference on the weights and
the configuration as made.

    python3 benchmark/degraded_mamba2.py --workload granite4hmicro.reason.flood --control state_bf16 [--layers 10]

``--control``: ``state_bf16`` (every program hands the Mamba layers'
state on rounded to bf16: the nearest precision under the float32 the
configuration states), ``stale_state`` (an admission that does not
reset its slot: the state and conv tail of the slot's last request are
scanned on), ``pad_steps_state`` (a bucket's padding steps the state
and the conv tail like real tokens), ``tail_at_bucket_end`` (the
padding steps no state, but the conv tail is taken at the bucket's end
and not at the prompt's), ``no_skip`` (``D`` dropped), ``gate_after_norm``
(``RMSNorm(y) * silu(z)`` where the published layer norms the gated
values), ``no_residual_multiplier`` and ``no_logits_scaling`` (each 1),
``softmax_scale`` (``head_dim^-1/2`` where the published scale is
``attention_multiplier``), ``no_conv_bias``, ``int8`` (every matrix
rounded to 127 steps of its column's largest entry and widened again).
Or ``none`` (the sound program: must pass). Exits 0 if the check refused
the control (``none``: if it passed) and prints what it said and every
reading, 1 otherwise. ``--layers`` cuts the depth to the first layers of
``layer_types`` (``int8`` holds the sound weights beside the rounded
ones: at 40 layers the two do not fit the chip). Needs the chip, like
`run.py`; ``--rehearse`` runs the tiny sizes on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import degraded_hybrid, degraded_routed  # noqa: E402

_copy_of = degraded_hybrid._copy_of
SEAM = degraded_hybrid.SEAM


def _state_bf16(model):
    """Every seam function hands the state on rounded to bf16
    (`lax.reduce_precision`: a pair of converts inside one program is
    excess precision the chip's compiler may drop; the chip, PR 46, read
    a third of one rounding's error with the pair)."""
    from jax import lax

    def rounded(fn):
        def call(*args, **kwargs):
            logits, cache, *rest = fn(*args, **kwargs)
            ssm = lax.reduce_precision(cache["ssm"], exponent_bits=8,
                                       mantissa_bits=7)
            return (logits, dict(cache, ssm=ssm), *rest)
        return call

    return {name: rounded(getattr(model, name)) for name in SEAM}


def _stale_state(model):
    return _copy_of(model, _starts_fresh=lambda cache_index: cache_index < 0)


def _pad_steps_state(model):
    return _copy_of(model, _real=lambda t, last: (None, t))


def _tail_at_bucket_end(model):
    real = model._real
    return _copy_of(model, _real=lambda t, last: (real(t, last)[0], t))


def _gate_after_norm(model):
    import jax

    def _mamba_out(y, x, z, layer, cfg):
        y = (y + layer["d_skip"][:, None] * x).reshape(z.shape)
        y = model.rms_norm(y, layer["ln_gate"], cfg.norm_eps) * jax.nn.silu(z)
        return model._mm("...i,id->...d", y, layer["w_out"])

    return _copy_of(model, _mamba_out=_mamba_out)


def _zeroed(name):
    def change(params):
        import jax.numpy as jnp

        mamba = dict(params["mamba"])
        mamba[name] = jnp.zeros_like(mamba[name])
        return dict(params, mamba=mamba)
    return change


def _int8(params):
    """Every matrix rounded to 127 steps of its column's largest entry
    (the nearest precision under bf16's 8 bits); norms, the convolution
    and the Mamba layers' float32 vectors kept."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rounded(a):
        wide = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wide), axis=-2, keepdims=True) / 127.0
        return (jnp.round(wide / jnp.maximum(scale, 1e-30))
                * scale).astype(a.dtype)

    def leaf(path, a):
        name = path[-1].key
        return rounded(a) if name.startswith("w") or name == "embed" else a

    return jax.tree_util.tree_map_with_path(leaf, params)


_same = lambda x: x  # noqa: E731

# control -> (what the ENGINE's parameters become, its configuration's
# changed fields, its model module's replaced names); the reference
# keeps the driver's own of all three.
CONTROLS = {
    "none": (_same, {}, None),
    "state_bf16": (_same, {}, _state_bf16),
    "stale_state": (_same, {}, _stale_state),
    "pad_steps_state": (_same, {}, _pad_steps_state),
    "tail_at_bucket_end": (_same, {}, _tail_at_bucket_end),
    "no_skip": (_zeroed("d_skip"), {}, None),
    "no_conv_bias": (_zeroed("conv_b"), {}, None),
    "gate_after_norm": (_same, {}, _gate_after_norm),
    "no_residual_multiplier": (_same, {"residual_multiplier": 1.0}, None),
    "no_logits_scaling": (_same, {"logits_scaling": 1.0}, None),
    "softmax_scale": (_same, {"attention_multiplier": None}, None),
    "int8": (_int8, {}, None),
}


def degraded(builder, control: str):
    """``builder`` with the engine's side of it degraded; its reference
    reads the weights as the seed made them."""
    change_params, change_cfg, change_model = CONTROLS[control]
    made = {}

    def config(c, **kw):
        cfg = builder.config(c, **kw)
        changed = {k: cfg.head_dim ** -0.5 if v is None else v
                   for k, v in change_cfg.items()}
        cfg = dataclasses.replace(cfg, **changed)
        return (cfg if change_model is None else
                degraded_routed._with_model(cfg, change_model(cfg.model)))

    def init_params(cfg, seed):
        made["params"] = builder.init_params(cfg, seed)
        return change_params(made["params"])

    def as_made(fn):
        return lambda params, *rest: fn(made["params"], *rest)

    return types.SimpleNamespace(
        config=config, init_params=init_params,
        first_state=builder.first_state,
        reference=types.SimpleNamespace(
            logits_at=as_made(builder.reference.logits_at),
            first_state=as_made(builder.reference.first_state)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark.drivers import common
    from benchmark.harness import context

    manifest, ctx, dev = context.build(
        ROOT, args.workload, seed=args.seed, seconds=0.0, t_start=T_START,
        rehearse=args.rehearse)
    if args.layers:
        ctx.config["num_hidden_layers"] = args.layers
    ctx.builder = degraded(ctx.builder, args.control)
    sound = args.control == "none"
    try:
        _, engine, _, checks = manifest.driver(
            ctx.config["driver"]).bring_up(ctx)
    except common.Incorrect as refused:
        print(json.dumps({"control": args.control, "refused": str(refused),
                          "readings": getattr(refused, "readings", None),
                          "device": dev}))
        return 1 if sound else 0
    engine.close()
    print(json.dumps({"control": args.control, "passed": checks,
                      "device": dev}))
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
