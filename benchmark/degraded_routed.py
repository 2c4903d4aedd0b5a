"""Show that a ROUTED cell's ``correct`` can fail: its replica brought
up wrong in one of seven ways, held by ``drivers/serve_routed.py`` to the
float32 reference on the weights and the configuration as made.

    python3 benchmark/degraded_routed.py --workload glm47flash.code.flood --control int8 [--layers 4]

``--control``: ``int8`` (the engine's matrices rounded to 8 bits a
column and widened again), ``select_on_score`` (the router chooses on
the score without its correction bias), ``no_scale`` (without
``routed_scaling_factor``), ``no_norm`` (without dividing the chosen
scores by their sum): the whole engine wrong. ``decode_int8`` (the
DECODE STEP alone reads the rounded matrices; every prefill is sound)
and ``tick_row`` (the TICK'S PREFILL alone is wrong: it takes the row
before the prompt's last for the last, so that token reaches no expert
and the first token comes from the wrong row), ``chunk_token`` (the
engine's CHUNK alone is wrong: its steps are fed token 0 for every
slot's token, while the step the check replays is sound): one program
wrong, which only a check that reads that program can refuse. Or
``none`` (the sound program: must pass).
Exits 0 if the check refused the control (``none``: if it passed) and
prints what it said, 1 otherwise. ``--layers`` cuts the depth where
sound and rounded weights together do not fit the chip. Needs the chip,
like `run.py`; ``--rehearse`` runs the tiny sizes on the CPU.
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


def _rounded(params):
    """Every matrix rounded to 127 steps of its column's largest entry
    (the nearest precision under bf16's 8 bits), norms and bias kept."""
    import jax
    import jax.numpy as jnp

    def rounded(path, a):
        name = path[-1].key
        if name.startswith("ln_") or name == "router_bias":
            return a
        wide = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wide), axis=-2, keepdims=True) / 127.0
        return (jnp.round(wide / jnp.maximum(scale, 1e-30))
                * scale).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(rounded, params)


def _int8(params):
    import jax

    return jax.jit(_rounded)(params)


def _no_bias(params):
    import jax.numpy as jnp

    moe = dict(params["moe"])
    moe["router_bias"] = jnp.zeros_like(moe["router_bias"])
    return dict(params, moe=moe)


def _decode_int8(model):
    """The decode step alone on the rounded matrices (rounded inside the
    step: slow and twice the weights, which a control can afford)."""
    return {"decode_step_with_cache": lambda params, *rest:
            model.decode_step_with_cache(_rounded(params), *rest)}


def _tick_row(model):
    """The tick's prefill alone takes ``last - 1`` for ``last``."""
    return {"forward_last_with_cache":
            lambda params, tokens, cache, cache_index, last, cfg:
            model.forward_last_with_cache(params, tokens, cache,
                                          cache_index, last - 1, cfg)}


def _chunk_token(model):
    """The engine's chunk alone feeds its steps token 0. The chunk is
    the FIRST program traced from the step (the engine answers before
    the check replays); `main` holds the control to exactly that: one
    wrong trace, then sound ones."""
    import jax.numpy as jnp

    def step(params, tokens, *rest):
        _chunk_token.traces += 1
        if _chunk_token.traces == 1:
            tokens = jnp.zeros_like(tokens)
        return model.decode_step_with_cache(params, tokens, *rest)

    _chunk_token.traces = 0
    return {"decode_step_with_cache": step}


def _with_model(cfg, replaced: dict):
    """``cfg`` of a class whose ``model`` (the engine's seam) is the
    family's module with some of its functions replaced."""
    model = types.SimpleNamespace(**{**vars(cfg.model), **replaced})
    cls = type("Degraded" + type(cfg).__name__, (type(cfg),),
               {"model": property(lambda self: model)})
    return cls(**{f.name: getattr(cfg, f.name)
                  for f in dataclasses.fields(cfg)})


_same = lambda x: x  # noqa: E731

# control -> (what the ENGINE's parameters become, its configuration's
# changed fields, its model module's replaced functions); the reference
# keeps the driver's own of all three.
CONTROLS = {
    "none": (_same, {}, None),
    "int8": (_int8, {}, None),
    "select_on_score": (_no_bias, {}, None),
    "no_scale": (_same, {"routed_scaling_factor": 1.0}, None),
    "no_norm": (_same, {"norm_topk_prob": False}, None),
    "decode_int8": (_same, {}, _decode_int8),
    "tick_row": (_same, {}, _tick_row),
    "chunk_token": (_same, {}, _chunk_token),
}


def degraded(builder, control: str):
    """``builder`` with the engine's side of it degraded: the program's
    configuration (and through it the model module the engine is handed)
    and the weights the engine is given; its reference reads the
    weights as the seed made them."""
    change_params, change_cfg, change_model = CONTROLS[control]
    made = {}

    def config(c, **kw):
        cfg = dataclasses.replace(builder.config(c, **kw), **change_cfg)
        return (cfg if change_model is None
                else _with_model(cfg, change_model(cfg.model)))

    def init_params(cfg, seed):
        made["params"] = builder.init_params(cfg, seed)
        return change_params(made["params"])

    def as_made(fn):
        return lambda params, *rest: fn(made["params"], *rest)

    return types.SimpleNamespace(
        config=config,
        init_params=init_params,
        reference=types.SimpleNamespace(
            logits_at=as_made(builder.reference.logits_at),
            routed_logits_at=as_made(builder.reference.routed_logits_at)))


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
