"""Show that an mHC cell's ``correct`` can fail: its replica brought up
wrong in one of twelve ways, held by ``drivers/serve_routed_mhc.py`` to
the float32 reference on the weights and the configuration as made.

    python3 benchmark/degraded_mhc.py --workload xing4.rag.flood --control sinkhorn_one_pass[,post_without_2,...] [--layers 8]

``--control`` (several with commas: one process, one after another):
``sinkhorn_one_pass`` (``hc_sinkhorn_iters`` 1: a row pass and a column
pass), ``post_without_2`` (``H_post = sigmoid(.)``, the factor 2 of mHC
dropped), ``res_identity`` (``H_res`` the identity: each stream keeps
itself, the plain residual four times), ``maps_without_norm`` (the maps
computed from ``vec(X)`` as it stands, the norm of step 1 left out),
``maps_bf16`` (the product ``u Phi`` on operands rounded to bf16: the
nearest precision under the float32 the configuration states),
``sinkhorn_bf16`` (``exp`` and every Sinkhorn pass rounded to bf16),
``streams_bf16`` (the four streams handed from sub-layer to sub-layer
rounded to bf16, the nearest precision under the float32 the
configuration states for them: at 40 layers no logit and no routing
reading shows it, the check's seventh limit does),
``sum_stream0`` (the model's output read from stream 0, not the sum of
the four), ``no_yarn`` (the rotary frequencies plain ``theta^(-2i/64)``),
``no_mscale`` (the softmax scale ``192^-1/2`` without ``mscale^2``),
``held_shifted`` (the held experts' matrices answering for the range
shifted by one expert: 1 to 8 where the chip holds 0 to 7),
``int8`` (every matrix rounded to 127 steps of its
column's largest entry and widened again: the nearest precision under
the bf16 the configuration's products state). Or ``none`` (the sound
program: must pass). ``--seed`` takes several with commas too. Exits 0
if the check refused every control asked for (``none``: if it passed)
and prints what it said, which limits refused and every reading, 1
otherwise. ``--layers`` cuts the depth to the first layers. Needs the
chip, like `run.py`; ``--rehearse`` runs the tiny sizes on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import degraded_hybrid, degraded_kda  # noqa: E402

_copy_of = degraded_hybrid._copy_of
_remade = degraded_kda._remade


def _bf16(a):
    """Rounded to bf16 and widened again (`lax.reduce_precision`: a pair
    of converts inside one program is excess precision the chip's
    compiler may drop)."""
    from jax import lax

    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _with_ops(**replaced):
    """A copy of the model whose ``mhc`` (the ops module's name in it)
    has some of its functions replaced; each replacement is made from
    the sound module."""
    def patched(model):
        sound = model.mhc
        ops = types.SimpleNamespace(**{
            **vars(sound), **{name: make(sound)
                              for name, make in replaced.items()}})
        return _copy_of(model, mhc=ops)
    return patched


def _maps_changed(change):
    """`mhc_pre` whose maps [R, 128] pass through ``change(maps, n)``
    before they are handed on (the collapsed input ``x`` is computed
    again from the changed H_pre)."""
    def make(sound):
        def mhc_pre(streams, phi_t, alpha, bias, *, spec, interpret=None):
            _, maps = sound.mhc_pre(streams, phi_t, alpha, bias, spec=spec,
                                    interpret=interpret)
            maps = change(maps, spec.n)
            return sound.collapse(streams, maps, spec), maps
        return mhc_pre
    return make


def _post_halved(maps, n):
    return maps.at[:, n:2 * n].multiply(0.5)


def _res_identity(maps, n):
    import jax.numpy as jnp

    return maps.at[:, 2 * n:2 * n + n * n].set(jnp.eye(n).ravel())


def _pre_from(logits_of):
    """`mhc_pre` in ``jnp`` from other map logits or another Sinkhorn:
    ``logits_of(sound, streams, phi_t, alpha, bias, spec)`` -> maps
    [R, 128]."""
    def make(sound):
        def mhc_pre(streams, phi_t, alpha, bias, *, spec, interpret=None):
            maps = logits_of(sound, streams, phi_t, alpha, bias, spec)
            return sound.collapse(streams, maps, spec), maps
        return mhc_pre
    return make


def _without_norm(sound, streams, phi_t, alpha, bias, spec):
    # The norm's scale is the only thing ``norm_eps`` enters: with an
    # eps that swamps the mean square, ``u = vec(X) / sqrt(eps)``, and
    # the logits times sqrt(eps) are those of the unnormed streams.
    import jax.numpy as jnp

    big = 1e12
    raw = sound.map_logits(streams, phi_t, alpha * jnp.sqrt(big), bias,
                           dataclasses.replace(spec, norm_eps=big))
    return sound.maps_from_logits(raw, spec)


def _product_bf16(sound, streams, phi_t, alpha, bias, spec):
    import jax.numpy as jnp
    from jax import lax

    flat = streams
    u = flat * lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                         + spec.norm_eps)
    scale, offset = sound._expanded(alpha, bias, spec.n)[:, :spec.n_maps]
    raw = jnp.einsum("rk,mk->rm", _bf16(u), _bf16(phi_t),
                     precision=lax.Precision.HIGHEST)
    return sound.maps_from_logits(raw * scale + offset, spec)


def _sinkhorn_bf16(sound, streams, phi_t, alpha, bias, spec):
    import jax
    import jax.numpy as jnp

    n = spec.n
    p, q, r = sound.split_maps(
        sound.map_logits(streams, phi_t, alpha, bias, spec), n)
    m = _bf16(jnp.exp(jnp.clip(r, spec.clamp_min, spec.clamp_max)))
    for _ in range(spec.sinkhorn_iters):
        m = _bf16(m / (jnp.sum(m, axis=-1, keepdims=True) + spec.hc_eps))
        m = _bf16(m / (jnp.sum(m, axis=-2, keepdims=True) + spec.hc_eps))
    maps = jnp.concatenate([jax.nn.sigmoid(p), 2.0 * jax.nn.sigmoid(q),
                            m.reshape(m.shape[0], n * n)], axis=-1)
    return jnp.pad(maps, ((0, 0), (0, sound.LANES - spec.n_maps)))


def _streams_bf16(sound):
    def mhc_post(streams, y, maps, *, spec, interpret=None):
        return _bf16(sound.mhc_post(streams, y, maps, spec=spec,
                                    interpret=interpret))
    return mhc_post


def _stream0(model):
    return _copy_of(model, _streams_out=lambda streams, n: streams[
        ..., :streams.shape[-1] // n])


def _plain_rotation(self, x, positions):
    from ray_tpu.ops import apply_rope

    return apply_rope(x, positions, self.rope_theta)


def _one_pass(cfg):
    return {"mhc": dataclasses.replace(cfg.mhc, sinkhorn_iters=1)}


def _shifted(cfg):
    first, count = cfg.held_experts
    return {"held_experts": (first + 1, count)}


_keep = lambda cfg: {}  # noqa: E731
_same = lambda params: params  # noqa: E731

# control -> (the configuration's changed fields, from the sound one;
# its class's replaced attributes; its model module's replaced names;
# what the ENGINE's parameters become, where they change); the
# reference keeps the driver's own of all four.
CONTROLS = {
    "none": (_keep, {}, None),
    "sinkhorn_one_pass": (_one_pass, {}, None),
    "post_without_2": (_keep, {}, _with_ops(
        mhc_pre=_maps_changed(_post_halved))),
    "res_identity": (_keep, {}, _with_ops(
        mhc_pre=_maps_changed(_res_identity))),
    "maps_without_norm": (_keep, {}, _with_ops(
        mhc_pre=_pre_from(_without_norm))),
    "maps_bf16": (_keep, {}, _with_ops(mhc_pre=_pre_from(_product_bf16))),
    "sinkhorn_bf16": (_keep, {}, _with_ops(
        mhc_pre=_pre_from(_sinkhorn_bf16))),
    "streams_bf16": (_keep, {}, _with_ops(mhc_post=_streams_bf16)),
    "sum_stream0": (_keep, {}, _stream0),
    "no_yarn": (_keep, {"rotate": _plain_rotation}, None),
    "no_mscale": (_keep, {"attn_scale": property(
        lambda self: self.qk_head_dim ** -0.5)}, None),
    "held_shifted": (_shifted, {}, None),
    "int8": (_keep, {}, None, degraded_kda._int8),
}
# Controls whose change of the weights consumes the tree it is given.
DONATES = degraded_kda.DONATES


def degraded(builder, control: str):
    """``builder`` with the engine's side of it degraded; its reference
    reads the configuration file and the weights as the seed made
    them."""
    change_cfg, change_class, change_model, *rest = CONTROLS[control]
    change_params = rest[0] if rest else _same
    made = {}

    def config(c, **kw):
        cfg = builder.config(c, **kw)
        cfg = dataclasses.replace(cfg, **change_cfg(cfg))
        attrs = dict(change_class)
        if change_model is not None:
            model = types.SimpleNamespace(**{**vars(cfg.model),
                                             **change_model(cfg.model)})
            attrs["model"] = property(lambda self: model)
        return _remade(cfg, attrs) if attrs else cfg

    def init_params(cfg, seed):
        made["params"] = builder.init_params(cfg, seed)
        if control not in DONATES:
            return change_params(made["params"])
        # Two sets of the cell's 40 layers do not fit the chip: the
        # weights are rounded in their own buffers, and the reference
        # reads the sound ones from the host's memory.
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
            logits_at=as_made(ref.logits_at),
            routed_logits_at=as_made(ref.routed_logits_at),
            first_maps=as_made(ref.first_maps)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True,
                    help="one of %s, or several with commas"
                    % ", ".join(sorted(CONTROLS)))
    ap.add_argument("--seed", default="0",
                    help="a seed, or several with commas")
    ap.add_argument("--layers", type=int, default=None)
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
        if args.layers:
            ctx.config["num_hidden_layers"] = args.layers
        sound_builder = ctx.builder
        for control in controls:
            one = copy.copy(ctx)
            one.builder = degraded(sound_builder, control)
            sound = control == "none"
            said = {"control": control, "seed": seed, "device": dev}
            try:
                _, engine, _, checks = manifest.driver(
                    ctx.config["driver"]).bring_up(one)
            except common.Incorrect as refused:
                print(json.dumps(dict(
                    said, refused=str(refused),
                    limits=getattr(refused, "limits", None),
                    readings=getattr(refused, "readings", None))),
                    flush=True)
                wrong += sound
                continue
            engine.close()
            print(json.dumps(dict(said, passed=checks)), flush=True)
            wrong += not sound
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
