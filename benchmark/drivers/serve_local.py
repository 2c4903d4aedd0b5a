"""Driver ``serve_local``: one `serve.llm` replica in the process that
owns the chip, entered as a user enters it —
``serve.run(build_llm_deployment(...), _local_testing_mode=True)`` and
``handle.options(method_name="stream", stream=True)`` — under the load
that the mix's traffic kind (``benchmark/traffic_kinds/``) makes and
drives.

Set-up: weights from the seed in one jitted call (the configuration's
builder makes them, ``benchmark/builders/``), the engine, one
request per prompt bucket (they pay or load every program the traffic
uses: one ``prefill`` per bucket and ``decode_chunk``), and on those
same requests the comparison with the plain reference.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.drivers import common
from benchmark.harness import tracing_run

# What this driver calls of a configuration's builder (`manifest.check`
# refuses a configuration whose builder lacks one).
BUILDER_CALLS = ("config", "init_params", "reference.logits_at")

# Relative L2 error of the engine's bf16 logits (one vocabulary row)
# against the float32 reference, at the prompt's last position and at
# the 16 decoded positions read back THROUGH THE CACHE (the engine's
# own prefill program run on the decoded tokens at ``cache_index`` =
# the prompt's length, over the cache the first prefill wrote). bf16
# keeps 8 bits: 2^-8 = 4e-3 a rounding, accumulated over 16 layers'
# residual stream. The chip shows 1.45e-2 and 1.47e-2 (PR 24); the
# engine's own weight-only int8 shows 4.6e-2 to 5.3e-2 even at 8
# layers and is refused (`benchmark/degraded.py`; PERF.md, PR 24).
TOL_LOGITS_REL_L2 = 3e-2
# How far below the reference's best logit the reference's logit of an
# engine-chosen token may lie, as a share of the reference's logit
# spread (max - min) at that position. The engine is greedy, so with
# exact arithmetic this is 0; bf16 may swap near-ties: the chip shows
# 0.0024 at worst over 68 tokens (PR 24), and the limit is four times
# that. This is the check of the decode program itself (decode
# attention over the cache, a step at a time).
TOL_TOKEN_MARGIN = 0.01
CHECK_TOKENS = 17   # the prefill's first token + 16 decoded


def _check_prompts(buckets, max_len, vocab, seed):
    """One seeded prompt per bucket, mid-way into the bucket."""
    rng = np.random.default_rng([seed, 1])
    prompts, prev = [], 0
    for b in buckets:
        n = max(1, min((prev + b) // 2, max_len - CHECK_TOKENS - 8))
        prompts.append([int(t) for t in rng.integers(1, vocab, n)])
        prev = b
    return prompts


def warm_and_check(handle, engine, params, config, cfg, seed: int,
                   reference) -> dict:
    """Warm every program and hold the engine to ``reference``, the
    plain reference of the configuration's builder."""
    import jax
    import jax.numpy as jnp

    eng = config["driver_args"]["engine"]
    buckets, max_len = eng["prompt_buckets"], eng["max_len"]
    prompts = _check_prompts(buckets, max_len, cfg.vocab_size, seed)
    stream = handle.options(method_name="stream", stream=True)
    answers = [None] * len(prompts)

    def ask(i):
        answers[i] = list(stream.remote(
            {"prompt_ids": prompts[i], "max_new_tokens": CHECK_TOKENS}))

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(1100)
    for i, got in enumerate(answers):
        common.require(got is not None and len(got) == CHECK_TOKENS,
                       f"check request {i}: {got}")
    # Teacher-forced: the reference reads prompt + the engine's tokens.
    width = max(len(p) for p in prompts) + CHECK_TOKENS
    tokens = np.zeros((len(prompts), width), np.int32)
    rows = []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        tokens[i, :len(p)] = p
        tokens[i, len(p):len(p) + CHECK_TOKENS] = a
        rows += [(i, len(p) - 1 + j) for j in range(CHECK_TOKENS)]
    # The reference reads the weights the driver made, not whatever the
    # engine keeps of them.
    ref = reference.logits_at(params, jnp.asarray(tokens), rows, config)
    ref = np.asarray(ref).reshape(len(prompts), CHECK_TOKENS, -1)
    common.require(np.all(np.isfinite(ref)), "reference logits not finite")
    margins = []
    for i, a in enumerate(answers):
        for j, tok in enumerate(a):
            row = ref[i, j]
            margins.append(float((row.max() - row[tok])
                                 / (row.max() - row.min())))
    common.require(max(margins) <= TOL_TOKEN_MARGIN,
                   f"an engine token lies {max(margins):.4f} of the logit "
                   f"spread under the reference's best (prefill + 16 "
                   f"decoded, through the cache)")
    # The engine's own prefill program on the same prompts, whole
    # vocabulary rows (functional: the returned cache is dropped) — and
    # once more on the decoded tokens, which then attend to the prompt
    # through the cache that the first call wrote.
    put = jax.device_put
    after = min(b for b in buckets if b >= CHECK_TOKENS - 1)
    errs, errs_cached = [], []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        bucket = min(b for b in buckets if b >= len(p))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(p)] = p
        got, cache = engine.loop.prefill(
            engine.params, engine.cache, put(padded), put(np.int32(0)),
            put(np.int32(0)))
        errs.append(common.rel_l2(got[0, len(p) - 1], ref[i, 0]))
        if len(p) + after > max_len:
            del cache, got
            continue
        padded = np.zeros((1, after), np.int32)
        padded[0, :CHECK_TOKENS - 1] = a[:-1]
        # ``[0]``, and ``del`` below: a cache bound to a name, even to
        # ``_``, lives on while the next is made — 2 GiB each at a peak
        # that is 3 caches as it is (the engine's, this, the output).
        got = engine.loop.prefill(
            engine.params, cache, put(padded), put(np.int32(0)),
            put(np.int32(len(p))))[0]
        errs_cached.append(common.rel_l2(got[0, :CHECK_TOKENS - 1],
                                         ref[i, 1:]))
        del cache, got
    common.require(errs_cached, "no check prompt leaves room for the "
                   "decoded tokens")
    common.require(max(errs + errs_cached) <= TOL_LOGITS_REL_L2,
                   f"logits off the reference: rel L2 {errs} at the "
                   f"prompts' ends, {errs_cached} through the cache")
    return {"prefill_rel_l2_max": max(errs),
            "cached_rel_l2_max": max(errs_cached),
            "token_margin_max": max(margins),
            "argmax_agree": sum(m == 0.0 for m in margins) / len(margins)}


def _under_a_trace(stream):
    """The engine emits its ``engine.*`` spans only for a request made
    under a trace: open one on the consumer's thread, where the request
    is made (the generator's body runs at the first ``next()``)."""
    from ray_tpu.util import tracing

    def traced(request):
        with tracing.trace("bench.request"):
            yield from stream(request)

    return traced


def bring_up(ctx):
    """(handle, engine, cfg, checks): the replica up, warm and held to
    the reference."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_deployment

    cfg = ctx.builder.config(ctx.config)
    params = ctx.builder.init_params(cfg, ctx.seed)
    handle = serve.run(
        build_llm_deployment(engine_kwargs=dict(
            cfg=cfg, params=params, seed=ctx.seed,
            **ctx.config["driver_args"]["engine"])),
        _local_testing_mode=True)
    engine = handle._instance.engine
    try:
        checks = warm_and_check(handle, engine, params, ctx.config, cfg,
                                ctx.seed, ctx.builder.reference)
    except BaseException:
        engine.close()
        raise
    return handle, engine, cfg, checks


def offer(ctx, handle, engine, cfg, mix, window: float, tracer=None) -> dict:
    """One lead-in, one window of ``window`` seconds and whatever drain
    the mix's kind makes, against a replica that is up. A tracer
    profiles ``trace_s`` seconds from ``trace_at_s`` (relative to the
    window's start; negative lies in the lead-in, so that the profiler,
    which stalls the host for most of a second when it stops, is gone
    before the first timed request is due)."""
    eng = ctx.config["driver_args"]["engine"]
    reqs = ctx.kind.requests(mix, ctx.seed, window, cfg.vocab_size,
                             max_total=eng["max_len"] - eng["decode_chunk"])
    stream = handle.options(method_name="stream", stream=True).remote
    stats = {}

    def snap(key):
        stats[key] = dict(engine.stats(), compiles=ctx.cache.requests)

    t0 = time.perf_counter() + mix["lead_in_s"]
    snaps = {"start": t0}       # "end" comes from the kind, at the window's
    if tracer:
        at, length = t0 + mix["trace_at_s"], min(window, mix["trace_s"])
        tracer.start_at(at, length)
        stream = _under_a_trace(stream)
        snaps.update(trace_start=at, trace_end=at + length)
    timers = [threading.Timer(max(0.0, when - time.perf_counter()), snap,
                              args=(key,)) for key, when in snaps.items()]
    for t in timers:
        t.start()
    records = ctx.kind.drive(stream, reqs, mix, seconds=window,
                             vocab=cfg.vocab_size, t0=t0,
                             on_window_end=lambda: snap("end"))
    for t in timers:
        t.join()
    return {"window_s": window, "requests": [dict(r) for r in records],
            "counters": stats}


def run(ctx) -> dict:
    mix = ctx.traffic
    handle, engine, cfg, checks = bring_up(ctx)
    # A traced run measures the whole window too (its host-clock and
    # counter metrics are over all of it); what it profiles is a
    # stretch of ``trace_s`` seconds that the mix places.
    tracer = tracing_run.Tracer(ctx) if ctx.trace else None
    try:
        setup_s = time.perf_counter() - ctx.t_start
        out = offer(ctx, handle, engine, cfg, mix, ctx.seconds, tracer)
    finally:
        engine.close()
    timed = [r for r in out["requests"] if r["timed"]]
    wrong = [r for r in timed if r["done"]
             and (r["n_got"] != r["n_want"] or r["bad_ids"])]
    compiles = (out["counters"]["end"]["compiles"]
                - out["counters"]["start"]["compiles"])
    return dict(
        out, setup_s=setup_s, checks=checks,
        trace=tracer.finish() if tracer else None,
        attempted=len(timed), failed=sum(1 for r in timed if r["error"]),
        compiles_in_window=compiles,
        correct=not wrong and compiles == 0,
        why_incorrect=(f"{len(wrong)} answers of the wrong length or with "
                       f"ids out of range; {compiles} compilations inside "
                       f"the window"))
