"""Show that the MiniCPM-SALA cell's ``correct`` can fail: its replica
brought up wrong in one of eleven ways, held by
``drivers/serve_sparse_hybrid.py`` to the float32 reference on the
weights and the configuration as made.

    python3 benchmark/degraded_sala.py --workload minicpmsala.longdoc.flood --control dense [--layers 4]

``--control``: ``dense`` (every query reads every row: ``dense_len``
past the cache's extent, where the selection is published),
``no_forced`` (a selection without its forced blocks: the best 64 by
score alone), ``one_head`` (the selection taken from the FIRST query
head of a group alone where it is the group's sum: what a selection a
head would choose for that head; a list a query head is not something
the kernel takes), ``kc_incomplete`` (the compressed key of the window
that straddles two prefill chunks is written by the first chunk alone,
from half its rows, and never again), ``state_bf16`` (every program
hands the lightning state on rounded to bf16: the nearest precision
under the float32 the configuration states), ``stale_state`` (an
admission that does not reset its slot), ``wrong_decay`` (the slope
table counted from h = 0 where it begins at 1), ``sparse_rope``
(rotary embedding on the sparse layers' q and k, which the published
model switches off), ``pad_steps_state`` (a bucket's padding steps the
state like real tokens), ``ignores_live`` (the decode step takes every
slot for live: a slot between two chunks of its prefill, idle or frozen
steps its state and completes its windows), ``first_slot_blocks`` (the
block-list kernel is handed slot 0's list for every slot: what a check
that replays one slot cannot see). Or ``none`` (the sound program: must pass).
Exits 0 if the check refused the control (``none``: if it passed) and
prints what it said and every reading, 1 otherwise. ``--layers`` keeps
the first layers of ``mixer_types``. Needs the chip, like `run.py`;
``--rehearse`` runs the tiny sizes on the CPU.
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
    (`reduce_precision`: a convert to bf16 and back is what the chip's
    compiler, allowed excess precision, takes out again)."""
    from jax import lax

    def rounded(fn):
        def call(*args, **kwargs):
            logits, cache, *rest = fn(*args, **kwargs)
            state = lax.reduce_precision(cache["state"], exponent_bits=8,
                                         mantissa_bits=7)
            return (logits, dict(cache, state=state), *rest)
        return call

    return {name: rounded(getattr(model, name)) for name in SEAM}


def _dense(cfg):
    return dataclasses.replace(cfg, selection=dataclasses.replace(
        cfg.selection, dense_len=cfg.max_seq_len))


def _no_forced(cfg):
    return dataclasses.replace(cfg, selection=dataclasses.replace(
        cfg.selection, init_blocks=0, window=0))


def _one_head(model):
    """The group's score is its first head's softmax alone."""
    ops = model.sparse_attention
    scores = ops._block_scores

    def first_head(q, kc, t1, sel):
        return scores(q[:, :1], kc, t1, sel)

    patched = types.SimpleNamespace(**_copy_of(ops, _block_scores=first_head))
    return _copy_of(model, sparse_attention=patched)


def _kc_incomplete(model):
    """A chunk writes the windows that BEGIN in it and no other."""
    ops = model.sparse_attention

    def begun_here(ck, cache_index, t, sel):
        from jax import lax

        rows = lax.dynamic_slice_in_dim(ck, cache_index, t, axis=2)
        return ops.window_means(rows, sel), cache_index // sel.stride

    return _copy_of(model, _chunk_windows=begun_here)


def _wrong_decay(model):
    import jax.numpy as jnp

    ops = model.lightning

    def from_zero(n_heads):
        h = jnp.arange(n_heads, dtype=jnp.float32)
        return -jnp.exp2(-8.0 * h / n_heads)

    patched = types.SimpleNamespace(**_copy_of(ops, log_decays=from_zero))
    return _copy_of(model, lightning=patched)


def _stale_state(model):
    return _copy_of(model, _starts_fresh=lambda cache_index: cache_index < 0)


def _pad_steps_state(model):
    return _copy_of(model, _real=lambda t, last: (None, t))


def _ignores_live(model):
    step = model.decode_step_with_cache

    def every_slot(params, tokens, cache, lengths, cfg, live=None):
        return step(params, tokens, cache, lengths, cfg, None)

    return {"decode_step_with_cache": every_slot}


def _first_slot_blocks(model):
    import jax.numpy as jnp

    ops = model.sparse_attention
    select = ops.select_blocks

    def of_slot_zero(q, kc, t1, sel):
        ids, count = select(q, kc, t1, sel)
        return jnp.broadcast_to(ids[:1], ids.shape), count

    patched = types.SimpleNamespace(
        **_copy_of(ops, select_blocks=of_slot_zero))
    return _copy_of(model, sparse_attention=patched)


_same = lambda x: x  # noqa: E731

# control -> (the ENGINE's configuration changed, its model module's
# replaced names); the reference keeps the driver's own of both.
CONTROLS = {
    "none": (_same, None),
    "dense": (_dense, None),
    "no_forced": (_no_forced, None),
    "one_head": (_same, _one_head),
    "kc_incomplete": (_same, _kc_incomplete),
    "state_bf16": (_same, _state_bf16),
    "stale_state": (_same, _stale_state),
    "wrong_decay": (_same, _wrong_decay),
    "sparse_rope": (lambda cfg: dataclasses.replace(cfg, sparse_rope=True),
                    None),
    "pad_steps_state": (_same, _pad_steps_state),
    "ignores_live": (_same, _ignores_live),
    "first_slot_blocks": (_same, _first_slot_blocks),
}


def degraded(builder, control: str):
    """``builder`` with the engine's side of it degraded; its reference
    reads the configuration file as it stands."""
    change_cfg, change_model = CONTROLS[control]

    def config(c, **kw):
        cfg = change_cfg(builder.config(c, **kw))
        return (cfg if change_model is None else
                degraded_routed._with_model(cfg, change_model(cfg.model)))

    return types.SimpleNamespace(
        config=config, init_params=builder.init_params,
        first_state=builder.first_state, reference=builder.reference)


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
