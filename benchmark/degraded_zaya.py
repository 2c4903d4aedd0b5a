"""Show that the ZAYA1 cell's ``correct`` can fail: its replica brought
up wrong in one of ten ways, held by ``drivers/serve_routed_tail.py``
to the float32 reference on the weights and the configuration as made.

    python3 benchmark/degraded_zaya.py --workload zaya1.reason.flood --control no_value_shift [--layers 8]

``--control``, a step of CCA left out or turned round:
``no_value_shift`` (both value halves from this token), ``no_qk_mean``
(queries and keys without the mean of the two latents),
``taps_reversed`` (each convolution's two taps exchanged: the weight
meant for the last token multiplies this one), ``no_key_temperature``
(``tau = 0``), ``no_l2_norm`` (heads not normed); the router:
``select_on_p`` (top-1 on ``p`` without its bias ``b``), ``no_gate``
(the chosen expert's output not weighted by its ``p``), ``bf16_router``
(the router MLP's weights and activations in bf16); ``int8`` (every
matrix rounded to 8 bits a column and widened again: the nearest
precision under bf16's 8 bits); ``tail_not_reset`` (a prefill at row 0
reads the tail the slot's last owner left). Or ``none`` (the sound
program: must pass). Exits 0 if the check refused the control
(``none``: if it passed) and prints what it said, 1 otherwise.
``--layers`` cuts the depth where sound and rounded weights together do
not fit the chip. Needs the chip, like `run.py`; ``--rehearse`` runs
the tiny sizes on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Entries of ``params["layers"]`` that are no matrix: kept by ``int8``.
_VECTORS = ("ln_attn", "ln_mlp", "ln_router", "conv0_w", "conv0_b",
            "conv1_b", "tau", "router_bias")


def _int8(params):
    """Every matrix rounded to 127 steps of its column's largest entry
    (the nearest precision under bf16's 8 bits); norms, biases, the
    depthwise taps and ``tau`` kept."""
    import jax
    import jax.numpy as jnp

    def rounded(path, a):
        if path[-1].key in _VECTORS + ("ln_out",):
            return a
        wide = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wide), axis=-2, keepdims=True) / 127.0
        return (jnp.round(wide / jnp.maximum(scale, 1e-30))
                * scale).astype(a.dtype)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(rounded, p))(
        params)


def _layers(change):
    """A change of some entries of ``params["layers"]``."""
    return lambda params: dict(
        params, layers=dict(params["layers"], **change(params["layers"])))


_taps_reversed = _layers(lambda w: {
    "conv0_w": w["conv0_w"][..., ::-1], "conv1_w": w["conv1_w"][:, :, ::-1]})
_no_temperature = _layers(lambda w: {"tau": w["tau"] * 0})
_no_bias = _layers(lambda w: {"router_bias": w["router_bias"] * 0})


def _no_value_shift(zaya):
    return {"_value": lambda w, w_prev, cfg: w}


def _no_qk_mean(zaya):
    def zero(u, cfg):
        mq, mk = zaya._qk_mean(u, cfg)
        return mq * 0, mk * 0
    return {"_qk_mean": zero}


def _no_l2_norm(zaya):
    return {"_unit": lambda z, cfg: z}


def _no_gate(zaya):
    def route(g, layer, cfg):
        expert, gate, p = zaya.route(g, layer, cfg)
        return expert, gate * 0 + 1, p
    return {"route": route}


def _bf16_router(zaya):
    """The router MLP as the sound program has it, but every weight and
    every activation rounded to bf16 (the nearest precision under its
    float32)."""
    import jax.numpy as jnp

    def route(g, layer, cfg):
        low = lambda a: a.astype(jnp.bfloat16)
        weights = {k: low(v) for k, v in layer.items()
                   if k.startswith(("router_", "ln_router"))
                   and k != "router_bias"}
        expert, gate, p = zaya.route(low(g), dict(layer, **weights), cfg)
        return expert, gate.astype(jnp.float32), p.astype(jnp.float32)
    return {"route": route}


def _tail_not_reset(zaya):
    import jax.numpy as jnp

    return {"_starts_fresh": lambda cache_index: jnp.asarray(False)}


_same = lambda x: x  # noqa: E731

# control -> (what the ENGINE's parameters become, the functions of
# ``ray_tpu/models/zaya.py`` that are replaced while it comes up); the
# reference keeps the driver's own parameters and reads no program.
CONTROLS = {
    "none": (_same, None),
    "no_value_shift": (_same, _no_value_shift),
    "no_qk_mean": (_same, _no_qk_mean),
    "taps_reversed": (_taps_reversed, None),
    "no_key_temperature": (_no_temperature, None),
    "no_l2_norm": (_same, _no_l2_norm),
    "select_on_p": (_no_bias, None),
    "no_gate": (_same, _no_gate),
    "bf16_router": (_same, _bf16_router),
    "int8": (_int8, None),
    "tail_not_reset": (_same, _tail_not_reset),
}


@contextlib.contextmanager
def patched(control: str):
    """The family's module with the control's functions in place of its
    own, for as long as the replica comes up and is checked (every
    program the engine and the check trace in that time is the
    degraded one)."""
    replace = CONTROLS[control][1]
    if replace is None:
        yield
        return
    from ray_tpu.models import zaya

    sound = types.SimpleNamespace(**vars(zaya))
    new = replace(sound)
    try:
        for name, fn in new.items():
            setattr(zaya, name, fn)
        yield
    finally:
        for name in new:
            setattr(zaya, name, getattr(sound, name))


def degraded(builder, control: str):
    """``builder`` with the weights the engine is given degraded; its
    reference reads the weights as the seed made them."""
    change_params = CONTROLS[control][0]
    made = {}

    def init_params(cfg, seed):
        made["params"] = builder.init_params(cfg, seed)
        return change_params(made["params"])

    def as_made(fn):
        return lambda params, *rest: fn(made["params"], *rest)

    ref = builder.reference
    return types.SimpleNamespace(
        config=builder.config, init_params=init_params,
        reference=types.SimpleNamespace(
            logits_at=as_made(ref.logits_at),
            routed_logits_at=as_made(ref.routed_logits_at),
            kept_at=as_made(ref.kept_at),
            router_probs=as_made(ref.router_probs)))


def bring_up(manifest, ctx, control: str):
    """-> the check's readings, or the `Incorrect` it raised."""
    from benchmark.drivers import common

    ctx.builder = degraded(ctx.builder, control)
    with patched(control):
        try:
            _, engine, _, checks = manifest.driver(
                ctx.config["driver"]).bring_up(ctx)
        except common.Incorrect as refused:
            return refused
    engine.close()
    return checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark.harness import context

    manifest, ctx, dev = context.build(
        ROOT, args.workload, seed=args.seed, seconds=0.0, t_start=T_START,
        rehearse=args.rehearse)
    if args.layers:
        ctx.config["num_hidden_layers"] = args.layers
        ctx.config["layer_types"] = ctx.config["layer_types"][:args.layers]
    got = bring_up(manifest, ctx, args.control)
    sound = args.control == "none"
    if isinstance(got, dict):
        print(json.dumps({"control": args.control, "passed": got,
                          "device": dev}))
        return 0 if sound else 1
    print(json.dumps({"control": args.control, "refused": str(got),
                      "readings": getattr(got, "readings", None),
                      "device": dev}))
    return 1 if sound else 0


if __name__ == "__main__":
    sys.exit(main())
