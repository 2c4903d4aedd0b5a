"""Driver ``train_spmd``: the sharded train step as a user builds it —
``spmd.sharded_init`` + ``spmd.make_train_step`` on ``mesh_2d(chips,
tp=...)`` — stepping back to back on fresh batches from the seed.

Set-up: the state born in its shards, the reference comparison on two
sequences of the first batch, and one step on that batch (it pays or
loads the one program the window uses).
"""

from __future__ import annotations

import inspect
import math
import time

from benchmark.drivers import common
from benchmark.harness import tracing_run

# What this driver calls of a configuration's builder (`manifest.check`
# refuses a configuration whose builder lacks one): `spmd.sharded_init`
# makes the weights, so no `init_params`.
BUILDER_CALLS = ("config", "reference.loss")

# The system's forward + loss (bf16 weights and activations, chunked
# cross entropy in float32) against the float32 reference on the SAME
# two sequences, relative. The loss is near ln(vocab) = 10.8, a mean
# over 4094 positions of values whose bf16 rounding errors (2^-8
# relative on logits of order 1) largely average out; PR 22's sharded
# and one-device steps agreed to 4e-6. The chip shows 5e-5 (PR 24);
# the limit is four times that.
TOL_LOSS_SAME_SEQUENCES = 2e-4
# The first train step's loss is over the whole batch, the reference's
# over two of its sequences: with random weights every sequence's loss
# lies within a percent or two of ln(vocab) (the check that the step's
# loss IS that quantity; the tight one is above).
TOL_LOSS_WHOLE_BATCH = 3e-2
CHECK_SEQUENCES = 2


def _program_bytes(step, mesh, state, tokens) -> int:
    """What the step program holds on one device while it runs, by the
    compiler's own account: arguments + outputs - aliased + temporaries.
    The allocator's ``peak_bytes_in_use`` counts live buffers only, not
    a running program's temporaries (PERF.md section 6, PR 24), so the
    run reports the larger of the two. ``step`` has run once: the
    trace is cached and the compile is served from the compile cache
    (about 2 s of set-up, measured on the chip). `make_train_step`
    wraps its jitted function with `functools.wraps`, whose public
    inverse is `inspect.unwrap`."""
    import jax

    jitted = inspect.unwrap(step, stop=lambda f: hasattr(f, "lower"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        m = jitted.lower(state, tokens).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import mesh_2d, mesh_context

    config, mix, args = ctx.config, ctx.traffic, ctx.config["driver_args"]
    cfg = ctx.builder.config(config)
    vocab = cfg.vocab_size
    mesh = mesh_2d(ctx.chips, tp=min(args["tp"], ctx.chips),
                   devices=jax.devices())
    tx = spmd.default_optimizer(lr=args["lr"], warmup=args["warmup"])
    tokens_per_step = mix["batch"] * mix["seq"]
    tracer = tracing_run.Tracer(ctx) if ctx.trace else None

    with mesh_context(mesh):
        state = spmd.sharded_init(cfg, mesh, jax.random.PRNGKey(ctx.seed), tx)
        step = spmd.make_train_step(cfg, mesh, tx)
        placed = spmd.data_sharding(mesh)

        def batch(i):
            return jax.device_put(ctx.kind.batch(mix, ctx.seed, i, vocab),
                                  placed)

        first = ctx.kind.batch(mix, ctx.seed, 0, vocab)
        few = jnp.asarray(first[:CHECK_SEQUENCES])
        ref = float(ctx.builder.reference.loss(state.params, few, config))
        same = float(spmd.make_eval_step(cfg, mesh)(
            state.params, jax.device_put(first[:CHECK_SEQUENCES], placed)
        )["loss"])
        common.require(math.isfinite(ref) and abs(same - ref)
                       <= TOL_LOSS_SAME_SEQUENCES * abs(ref),
                       f"loss {same} against the reference's {ref} on the "
                       f"same {CHECK_SEQUENCES} sequences")
        state, out = step(state, batch(0))
        loss0 = float(out["loss"])
        common.require(abs(loss0 - ref) <= TOL_LOSS_WHOLE_BATCH * abs(ref),
                       f"first step's loss {loss0} against the "
                       f"reference's {ref}")
        program_bytes = _program_bytes(step, mesh, state, batch(0))
        compiles0 = ctx.cache.requests

        setup_s = time.perf_counter() - ctx.t_start
        steps, outs = [], []
        if tracer:
            tracer.begin()
            tracer.mark(tracing_run.BEGIN)
        t0 = time.perf_counter()

        def more():
            if tracer:
                return len(steps) < mix["trace_steps"]
            return time.perf_counter() - t0 < ctx.seconds

        while more():
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.span.make_batch"):
                tokens = batch(len(steps) + 1)
            with jax.profiler.TraceAnnotation("bench.span.step"):
                state, out = step(state, tokens)
                jax.block_until_ready(out)
            steps.append(time.perf_counter() - ts)
            outs.append(out)
        window = time.perf_counter() - t0
        if tracer:
            tracer.end()
        compiles = ctx.cache.requests - compiles0
        finite = [all(math.isfinite(float(o[k])) for k in ("loss", "grad_norm"))
                  for o in outs]
    trace = tracer.finish() if tracer else None
    return {
        "setup_s": setup_s, "window_s": window, "steps": steps,
        "tokens_per_step": tokens_per_step,
        "losses": [loss0] + [float(o["loss"]) for o in outs],
        "trace": trace, "program_bytes": program_bytes,
        "checks": {"loss_reference": ref, "loss_same_sequences": same,
                   "loss_first_step": loss0},
        "attempted": len(steps), "failed": finite.count(False),
        "compiles_in_window": compiles,
        "correct": all(finite) and compiles == 0,
        "why_incorrect": f"finite steps {finite}; {compiles} compilations "
                         f"inside the window",
    }
