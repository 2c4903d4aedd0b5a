"""Show that a HYBRID cell's ``correct`` can fail: its replica brought
up wrong in one of six ways, held by ``drivers/serve_hybrid.py`` to the
float32 reference on the weights and the configuration as made.

    python3 benchmark/degraded_hybrid.py --workload olmohybrid.rag.flood --control state_bf16 [--layers 4]

``--control``: ``state_bf16`` (every program hands the linear layers'
state on rounded to bf16: the nearest precision under the float32 the
configuration states), ``no_decay`` (alpha = 1: the state never
forgets), ``beta_unscaled`` (beta = sigmoid without the 2 of
``linear_allow_neg_eigval``), ``no_conv`` (the convolution replaced by
the identity), ``stale_state`` (an admission that does not reset its
slot: the state and conv tail of the slot's last request are scanned
on), ``pad_steps_state`` (a bucket's padding steps the state and the
conv tail like real tokens). Or ``none`` (the sound program: must
pass). Exits 0 if the check refused the control (``none``: if it
passed) and prints what it said and every reading, 1 otherwise.
``--layers`` cuts the depth (whole periods). Needs the chip, like
`run.py`; ``--rehearse`` runs the tiny sizes on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import degraded_routed  # noqa: E402

SEAM = ("forward_with_cache", "forward_last_with_cache",
        "decode_step_with_cache")


def _copy_of(model, **patched) -> dict:
    """The globals of the family's module loaded a second time under a
    name of its own, with some of them replaced: what its functions
    call of each other is then the replacement too."""
    spec = importlib.util.spec_from_file_location(
        model.__name__ + "_degraded", model.__file__)
    copy = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = copy
    spec.loader.exec_module(copy)
    vars(copy).update(patched)
    return vars(copy)


def _state_bf16(model):
    """Every seam function hands the state on rounded to bf16."""
    import jax.numpy as jnp

    def rounded(fn):
        def call(*args, **kwargs):
            logits, cache, *rest = fn(*args, **kwargs)
            state = cache["state"].astype(jnp.bfloat16).astype(jnp.float32)
            return (logits, dict(cache, state=state), *rest)
        return call

    return {name: rounded(getattr(model, name)) for name in SEAM}


def _stale_state(model):
    return _copy_of(model, _starts_fresh=lambda cache_index: cache_index < 0)


def _pad_steps_state(model):
    return _copy_of(model, _real=lambda t, last: (None, t))


def _no_decay(params):
    import jax.numpy as jnp

    linear = dict(params["linear"])
    linear["a_log"] = jnp.full_like(linear["a_log"], -1e30)
    return dict(params, linear=linear)


def _no_conv(params):
    import jax.numpy as jnp

    linear = dict(params["linear"])
    taps = jnp.zeros_like(linear["conv_w"])
    linear["conv_w"] = taps.at[..., -1].set(1)
    return dict(params, linear=linear)


_same = lambda x: x  # noqa: E731

# control -> (what the ENGINE's parameters become, its configuration's
# changed fields, its model module's replaced names); the reference
# keeps the driver's own of all three.
CONTROLS = {
    "none": (_same, {}, None),
    "state_bf16": (_same, {}, _state_bf16),
    "no_decay": (_no_decay, {}, None),
    "beta_unscaled": (_same, {"allow_neg_eigval": False}, None),
    "no_conv": (_no_conv, {}, None),
    "stale_state": (_same, {}, _stale_state),
    "pad_steps_state": (_same, {}, _pad_steps_state),
}


def degraded(builder, control: str):
    """``builder`` with the engine's side of it degraded; its reference
    reads the weights as the seed made them."""
    change_params, change_cfg, change_model = CONTROLS[control]
    made = {}

    def config(c, **kw):
        cfg = dataclasses.replace(builder.config(c, **kw), **change_cfg)
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
