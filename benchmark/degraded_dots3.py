"""Show that the dots3-note cell's ``correct`` can fail: its replica
brought up wrong in one of thirteen ways, held by
``drivers/serve_routed_sparse.py`` to the float32 reference on the
weights and the configuration as made.

    python3 benchmark/degraded_dots3.py --workload dots3.longdoc.flood --control no_gate[,top_minus_1,...]

``--control`` (several, with commas, run one after another in this
process): the latent attention: ``no_rescale`` (the two latents without
their constants ``rho``), ``no_gate`` (the heads' outputs not gated);
the window: ``window_minus_1`` and ``window_plus_1`` (512 and 514 rows
where 513 are published), ``window_not_reset`` (a ring row counts as
the slot's whatever position it would hold: a new owner reads the last
one's rows); the selection: ``top_minus_1`` (2,047 rows where 2,048 are
published), ``approx_topk`` (`lax.approx_max_k` at a recall target of
0.8 on the TPU: at its default 0.95 it is EXACT for 2,048 of up to
40,940 rows, which a slot of 32,768 is, and the check passed it;
elsewhere, where the operation is exact, what it does on the TPU: the
best of each of ``4 k`` interleaved buckets, then the top k of those),
``no_relu`` (the
heads' products summed signed), ``no_head_weights`` (every head
weighted alike), ``bf16_scores`` (products, weights, terms and sums
rounded to bf16: the nearest precision under the float32 stated); the
router: ``gates_over_held`` (the gates normalised over the chosen
experts this chip HOLDS, where the published sum is over all chosen),
``bf16_router`` (the router's weights and gates rounded to bf16);
``int8`` (every matrix rounded to 8 bits a column and widened again).
Or ``none`` (the sound program: must pass). Exits 0 if the check
refused every control asked for (``none``: if it passed) and prints,
a line a control, what it said and every reading; 1 otherwise.
Every control runs at the cell's own depth (``int8`` rounds a leaf at a
time in the sound leaf's place, and the reference reads the sound
weights from the host). Needs the chip, like `run.py`; ``--rehearse``
runs the tiny sizes on the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Entries that are no matrix: kept by ``int8``.
_VECTORS = ("router_bias", "ik_gain", "ik_bias")


def _int8(params):
    """Every matrix rounded to 127 steps of its column's largest entry
    (the nearest precision under bf16's 8 bits); norms, the router's
    bias and the indexer's LayerNorm kept. A leaf at a time, each in
    the sound leaf's place: sound and rounded weights do not fit the
    chip together."""
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
        return a if name.startswith("ln_") or name in _VECTORS else rounded(a)

    return jax.tree_util.tree_map_with_path(leaf, params)


def _bf16(a):
    """float32 at bf16's precision (`reduce_precision`: a convert to
    bf16 and back is what the chip's compiler, allowed excess
    precision, takes out again)."""
    import jax.numpy as jnp
    from jax import lax

    return lax.reduce_precision(a.astype(jnp.float32), exponent_bits=8,
                                mantissa_bits=7)


def _approx_top_rows(sound):
    """`row_select.top_rows` by an approximate search."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def top_rows(scores, visible, k: int):
        s = scores.shape[-1]
        ranked = jnp.where(visible, scores, -jnp.inf)
        if jax.default_backend() == "tpu":
            # At its default recall of 0.95 the operation keeps
            # (k - 1) / 0.05 = 40,940 candidates: every row of a slot
            # of 32,768, an exact choice. 0.8 keeps 10,235.
            _, ids = lax.approx_max_k(ranked, k, recall_target=0.8)
        else:
            buckets = 4 * k
            if s % buckets:
                return sound.top_rows(scores, visible, k)
            # Row r lies in bucket r mod buckets.
            by_bucket = ranked.reshape(*ranked.shape[:-1], s // buckets,
                                       buckets)
            best = jnp.max(by_bucket, axis=-2)
            where = jnp.argmax(by_bucket, axis=-2) * buckets + jnp.arange(
                buckets)
            _, among = lax.top_k(best, k)
            ids = jnp.take_along_axis(where, among, axis=-1)
        chosen = jnp.sum(jax.nn.one_hot(ids, s, dtype=jnp.int32), axis=-2) > 0
        return chosen & visible

    return top_rows


def _selection(*, relu=True, head_weights=True, low=False, approx=False):
    """The indexer's scoring and the choice of rows, in ``jnp`` for the
    prefill (`row_select.index_scores`) and the decode step
    (`row_select.select_decode_rows`) alike, with one step left out or
    turned round."""

    def replace(row_select):
        import jax
        import jax.numpy as jnp
        from jax import lax

        f32 = jnp.float32
        top_rows = (_approx_top_rows(row_select) if approx
                    else row_select.top_rows)

        def scores_of(q, w, keys):
            """q [T,Hi,Di], w [T,Hi], keys [S,Di] -> [T,S] float32."""
            part = jnp.einsum("thd,sd->ths", q.astype(keys.dtype), keys,
                              preferred_element_type=f32)
            w = w.astype(f32)
            if not head_weights:
                w = jnp.full_like(w, (q.shape[1] * q.shape[2]) ** -0.5)
            if low:
                part, w = _bf16(part), _bf16(w)
            if relu:
                part = jax.nn.relu(part)
            terms = part * w[:, :, None]
            if low:
                return _bf16(jnp.sum(_bf16(terms), axis=1))
            return jnp.sum(terms, axis=1)

        def index_scores(q, w, keys, rows_seen=None, *, tile: int = 512):
            t, s = q.shape[0], keys.shape[0]
            tile = min(tile, s)

            def one(i, out):
                k_t = lax.dynamic_slice_in_dim(keys, i * tile, tile, axis=0)
                return lax.dynamic_update_slice_in_dim(  # rtpu-lint: disable=unclamped-dynamic-update-slice
                    out, scores_of(q, w, k_t), i * tile, axis=1)

            n = s // tile if rows_seen is None else jnp.minimum(
                lax.div(jnp.asarray(rows_seen, jnp.int32) + (tile - 1), tile),
                s // tile)
            return lax.fori_loop(0, n, one, jnp.zeros((t, s), f32))

        def select_decode_rows(q, w, cache, positions, *, layer, k,
                               block_s=None, interpret=None):
            keys = lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
            scores = jax.vmap(scores_of)(q[:, None], w[:, None], keys)[:, 0]
            positions = positions.astype(jnp.int32)
            visible = (jnp.arange(keys.shape[1])[None, :]
                       <= positions[:, None])
            keep = jnp.where((positions + 1 <= k)[:, None], visible,
                             top_rows(scores, visible, k))
            return keep.astype(f32)

        return {"index_scores": index_scores, "top_rows": top_rows,
                "select_decode_rows": select_decode_rows}

    return {"row_select": replace}


def _no_gate(model):
    import jax.numpy as jnp

    def gate_and_out(x, h, attn, layer):
        return x + jnp.einsum("...hv,hvd->...d", attn.astype(x.dtype),
                              layer["w_o"]).astype(x.dtype)
    return {"_gate_and_out": gate_and_out}


def _window_not_reset(model):
    return {"_is_a_row": lambda positions: positions == positions}


def _gates_over_held(model):
    import jax.numpy as jnp

    def route(x, router, bias, cfg, precision=None):
        experts, gates = model.route(x, router, bias, cfg, precision)
        first, count = cfg.held_experts
        held = (experts >= first) & (experts < first + count)
        gates = jnp.where(held, gates, 0.0)
        return experts, (gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
                         * cfg.routed_scaling_factor)
    return {"route": route}


def _bf16_router(model):
    def route(x, router, bias, cfg, precision=None):
        experts, gates = model.route(x, _bf16(router), bias, cfg, precision)
        return experts, _bf16(gates)
    return {"route": route}


_same = lambda x: x  # noqa: E731

# control -> (what the ENGINE's parameters become, what its program's
# configuration becomes, {module: functions replaced while it comes
# up}); the reference keeps the driver's own parameters and the file's
# configuration, and reads no program.
CONTROLS = {
    "none": (_same, None, {}),
    "no_rescale": (_same, lambda c: {"lora_rescale": False}, {}),
    "no_gate": (_same, None, {"dots3_note": _no_gate}),
    "window_minus_1": (_same, lambda c: {"window": c.window - 1}, {}),
    "window_plus_1": (_same, lambda c: {"window": c.window + 1}, {}),
    "window_not_reset": (_same, None, {"dots3_note": _window_not_reset}),
    "top_minus_1": (_same, lambda c: {"index_topk": c.index_topk - 1}, {}),
    "approx_topk": (_same, None, _selection(approx=True)),
    "no_relu": (_same, None, _selection(relu=False)),
    "no_head_weights": (_same, None, _selection(head_weights=False)),
    "bf16_scores": (_same, None, _selection(low=True)),
    "gates_over_held": (_same, None, {"dots3_note": _gates_over_held}),
    "bf16_router": (_same, None, {"dots3_note": _bf16_router}),
    "int8": (_int8, None, {}),
}


def _modules():
    from ray_tpu.models import dots3_note
    from ray_tpu.ops import row_select

    return {"dots3_note": dots3_note, "row_select": row_select}


@contextlib.contextmanager
def patched(control: str):
    """The family's modules with the control's functions in place of
    their own, for as long as the replica comes up and is checked
    (every program the engine and the check trace in that time is the
    degraded one)."""
    replaced = []
    try:
        for which, replace in CONTROLS[control][2].items():
            module = _modules()[which]
            sound = types.SimpleNamespace(**vars(module))
            for name, fn in replace(sound).items():
                replaced.append((module, name, getattr(module, name)))
                setattr(module, name, fn)
        yield
    finally:
        for module, name, fn in reversed(replaced):
            setattr(module, name, fn)


def degraded(builder, control: str):
    """``builder`` with the weights and the configuration the engine is
    given degraded; its reference reads the weights as the seed made
    them and the file's configuration."""
    change_params, change_cfg, _ = CONTROLS[control]
    made = {}

    def config(c, **overrides):
        cfg = builder.config(c, **overrides)
        return (cfg if change_cfg is None
                else dataclasses.replace(cfg, **change_cfg(cfg)))

    def init_params(cfg, seed):
        params = made["params"] = builder.init_params(cfg, seed)
        if change_params is not _same:
            # The reference reads the sound weights from the HOST, a
            # layer at a time; the engine's take their place on the chip.
            import jax
            made["params"] = jax.device_get(params)
        return change_params(params)

    def as_made(fn):
        return lambda params, *rest: fn(made["params"], *rest)

    ref = builder.reference
    return types.SimpleNamespace(
        config=config, init_params=init_params,
        reference=types.SimpleNamespace(
            logits_at=as_made(ref.logits_at),
            followed_logits_at=as_made(ref.followed_logits_at)))


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
    ap.add_argument("--control", required=True,
                    help="one of, or several with commas: "
                    + " ".join(sorted(CONTROLS)))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    controls = args.control.split(",")
    unknown = sorted(set(controls) - set(CONTROLS))
    if unknown:
        ap.error(f"no such control: {unknown}")

    from benchmark.harness import context

    as_expected = True
    for control in controls:
        manifest, ctx, dev = context.build(
            ROOT, args.workload, seed=args.seed, seconds=0.0,
            t_start=T_START, rehearse=args.rehearse)
        got = bring_up(manifest, ctx, control)
        passed = isinstance(got, dict)
        print(json.dumps(
            {"control": control, "passed": got, "device": dev} if passed else
            {"control": control, "refused": str(got),
             "readings": getattr(got, "readings", None), "device": dev}),
            flush=True)
        as_expected &= passed == (control == "none")
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
