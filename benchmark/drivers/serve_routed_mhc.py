"""Driver ``serve_routed_mhc``: `serve_routed` for a routed model whose
every sub-layer sits inside a residual of several STREAMS mixed by
learned, Sinkhorn-normalised maps (mHC), at a depth at which the latent
cache is a quarter of the chip. The replica, the load
(`serve_local.offer`), the counters and the rules that decide
``correct`` in the window are `serve_local`'s; the comparison with the
plain reference at set-up is `serve_routed`'s four controls and three
more, all read AT THE SHAPES THE WINDOW TIMES. Why a file of its own
and not `serve_routed` as it stands:

- `serve_routed._replay` runs the functional programs on the engine's
  cache: the engine's, the one read and the one written. At 40 layers
  the engine's 32 slots are 3.36 GB beside 10.5 GB of weights: a second
  does not fit, let alone a third. So the replay DONATES: once the
  check's requests are answered and the engine stands idle, the tick's
  prefill and the decode step run on the engine's own cache
  (``loop.prefill_last_inplace``, ``loop.decode_step_whole_inplace``:
  one prompt into one slot of all 32, one step of all 32 slots, the
  programs the window times but for what they return), the engine is
  handed its cache back, and its manager forgets what the slots held.
  The check's requests are answered with every slot of the engine live.
- Logits cannot tell maps computed in bf16 from maps computed in
  float32 beside 80 sub-layers of bf16 products: a map rounded to 8
  bits moves a row of logits by less than the weights' own rounding
  does. The maps CAN be read where no rounding has come before them:
  the first sub-layer's input is the embedding's rows. So control (v)
  holds the first sub-layer's maps (``seen["mhc_maps"]`` of the tick's
  prefill and of the decode step: H_pre, H_post, vec(H_res)) to the
  reference's (``reference.first_maps``).
- Nor can logits tell WHICH of the streams the head read: twenty
  doubly stochastic mixes a token make the four streams alike, and a
  head that reads stream 0 alone moves a row by 5 to 9 % where the
  sound seeds' own rounding moves it by 4 to 7 (the chip's readings,
  PR 54). So control (vi) reads the other end where it is exact: the
  last sub-layer's streams at the row the head reads and what the
  final norm read of them (``seen["mhc_end"]``), which must be their
  sum.
- Nor can they tell streams handed from sub-layer to sub-layer in bf16
  from the float32 the configuration states: at 40 layers every logit
  and every routing reading lies inside the sound seeds' range. So
  control (vii) holds EVERY sub-layer's write-back at the row the head
  reads (``seen["mhc_mixes"]``): from the streams that entered, the
  sub-layer's output and the maps the program used, this file computes
  ``H_res X + H_post^T y`` in float64 and compares what the program
  handed on to the next sub-layer.

(i)   a system choice that the reference did not make lies within
      `TOL_ROUTE_EXCESS` of the reference's boundary, as a share of the
      row's spread of ``score + bias``;
(ii)  with the system's choices followed, every replayed row of logits
      (the prefill's one, the step's 16) within `TOL_LOGITS_REL_L2`;
(iii) at least `TOL_TOKENS_STRICT_SHARE` of the engine's streamed tokens
      within `TOL_TOKEN_MARGIN` of the reference's best;
(iv)  at most `TOL_ROUTE_DIFFER` of the (row, layer) choices differ;
(v)   every map of the first sub-layer, at every prompt row and every
      replayed step, within `TOL_MAPS_ABS` of the reference's;
(vi)  what the final norm read, at each prompt's end and every replayed
      step, within `TOL_STREAMS_END` of the SUM of the last sub-layer's
      streams (relative L2);
(vii) what each of the 80 sub-layers handed on, at each prompt's end
      and every replayed step, within `TOL_STREAMS_HANDED` of ``H_res X
      + H_post^T y`` (relative L2).

(v) reaches ONE sub-layer's maps and (vi), (vii) compare the program
with itself: the maps of the other 79 sub-layers are held by (i) to
(iv) alone. ``benchmark/degraded_mhc.py`` shows the ways of being wrong
that this refuses; PERF.md (section 6, PR 54) has the chip's readings
on either side of each limit, at the cell's sizes.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers import common, serve_hybrid, serve_local
from benchmark.harness import tracing_run

BUILDER_CALLS = ("config", "init_params", "reference.logits_at",
                 "reference.routed_logits_at", "reference.first_maps")

# Every limit's two readings are the chip's at the cell's own 40 layers
# (PERF.md section 6, PR 54: the sound program's seeds; the controls of
# `degraded_mhc.py`; ``int8`` is the nearest precision under the bf16
# products the configuration states).
# (i): how far under the reference's k-th ``score + bias`` a system
# choice may lie, as a share of the row's spread. `serve_routed`'s 0.035
# is for a router that reads 7 layers of bf16 products; the router of
# layer 40 reads a stream that carries 79 sub-layers of them, and the
# sound program reads 0.039 to 0.056 (`serve_routed`'s limit refused it,
# call 70). Weights rounded to int8 read 0.317.
TOL_ROUTE_EXCESS = 0.12
# (iv): share of the (row, layer) choices that may differ at all (each
# within the excess above): sound 0.130 to 0.149 (4 of 64 scores lie
# close at 38 layers' depth), int8 0.564. `serve_routed_hybrid` chose
# 0.30 for its 26 expert layers on the same ground.
TOL_ROUTE_DIFFER = 0.30
# (iii): the share of the engine's tokens within the margin: sound 0.94
# to 1.0, int8 0.71 (`serve_routed`'s 0.6 would pass it).
TOL_TOKENS_STRICT_SHARE = 0.82
TOL_TOKEN_MARGIN = serve_local.TOL_TOKEN_MARGIN
# (ii): relative L2 of a row of logits. 80 sub-layers of bf16 products
# under float32 streams read 0.042 to 0.046 at a prompt's end and 0.051
# to 0.062 a replayed step (the absorbed order rounds the query's
# latent and the latent output once more a layer); `serve_local`'s 3e-2
# is a 16-layer dense family's and `serve_routed_hybrid`'s 4e-2 a
# 27-layer one's (0.026 to 0.032 there). int8 reads 0.226 and 0.270.
TOL_LOGITS_REL_L2 = 0.13
# (v): the largest |system - reference| over the first sub-layer's 24
# maps (each of order one: H_pre in (0, 1), H_post in (0, 2), H_res's
# entries in (0, 1)). The sound program differs by what two float32
# evaluations of a 14,336-term product and twenty normalisations differ
# by (1e-6 to 2e-6); maps whose product ran on bf16 operands or whose
# Sinkhorn passes were rounded to bf16 by 2^-9 of a map and more.
TOL_MAPS_ABS = 1e-4
# (vi): relative L2 between what the final norm read and the sum of the
# four streams the last layer left, both the program's own: two float32
# sums of four terms (1e-7); a head that reads one stream, or a mean
# where a sum belongs to an unnormed reader, by half of it and more.
TOL_STREAMS_END = 1e-5
# (vii): relative L2 between what a sub-layer handed on and ``H_res X +
# H_post^T y`` computed here in float64 from what it read: five float32
# products and four sums a value (1e-7); streams rounded to bf16 on
# their way to the next sub-layer, or a write-back on bf16 operands, by
# 2^-9 of a value (1e-3 and more).
TOL_STREAMS_HANDED = 1e-5

CHECK_TOKENS = serve_local.CHECK_TOKENS


def _end_error(seen, n: int) -> float:
    """(vi) of the first ``n`` slots of a program's ``seen``."""
    streams = np.asarray(seen["mhc_end"]["streams"][:n], np.float64)
    read = np.asarray(seen["mhc_end"]["read"][:n], np.float64)
    total = streams.sum(axis=1)
    return float(np.max(np.linalg.norm(read - total, axis=-1)
                        / np.linalg.norm(total, axis=-1)))


def _handed_error(seen, n: int) -> float:
    """(vii) of the first ``n`` slots of a program's ``seen``: the
    largest relative L2, over the sub-layers in the model's order,
    between the streams handed on and ``H_res X + H_post^T y``."""
    mixes = seen["mhc_mixes"]

    def in_order(a):                                # [L, 2, B, ..]
        a = np.asarray(a[:, :, :n], np.float64)
        return a.reshape((-1,) + a.shape[2:])       # [2L, n, ..]

    y, maps, after = (in_order(mixes[k]) for k in ("y", "maps", "after"))
    x = np.asarray(mixes["first"][:n], np.float64)  # [n, streams x C]
    streams = x.shape[-1] // y.shape[-1]
    worst = 0.0
    for y_s, maps_s, after_s in zip(y, maps, after):
        apart = x.reshape(n, streams, -1)
        h_post = maps_s[:, streams:2 * streams]
        h_res = maps_s[:, 2 * streams:].reshape(n, streams, streams)
        want = (np.einsum("bij,bjc->bic", h_res, apart)
                + h_post[:, :, None] * y_s[:, None, :]).reshape(n, -1)
        worst = max(worst, float(np.max(
            np.linalg.norm(after_s - want, axis=-1)
            / np.linalg.norm(want, axis=-1))))
        x = after_s
    return worst


def _idle(engine, slots: int, timeout_s: float = 60.0) -> None:
    """Wait until the engine holds no request: nothing of it will touch
    the cache until the next one arrives."""
    deadline = time.perf_counter() + timeout_s
    while True:
        stats = engine.stats()
        if not (stats["active"] or stats["prefilling"] or stats["waiting"]
                ) and stats["free_slots"] == slots:
            return
        common.require(time.perf_counter() < deadline,
                       f"the engine did not come to rest: {stats}")
        time.sleep(0.01)


def _replay(engine, prompts, answers, eng):
    """The tick's prefill and the decode step on the engine's own
    tokens, in the ENGINE's cache, donated and handed back (this file's
    header): prompt i into slot i of all the engine's, then one step of
    all its slots a token, the idle ones parked on their last row as
    the engine's roster parks them. -> (logits [prompts, 17, V]: the
    prefill's one row, then a row a step; chosen [Lm, prompts, width,
    k]: the experts of every prompt row and every replayed position, -1
    where no token was fed; maps [prompts, width, M]: the first
    sub-layer's, NaN where no token was fed; the largest (vi) and the
    largest (vii) of any replayed call)."""
    import jax

    put, loop = jax.device_put, engine.loop
    buckets, max_len, slots = (eng["prompt_buckets"], eng["max_len"],
                               eng["max_batch"])
    n = len(prompts)
    common.require(n <= slots, "more check prompts than slots")
    width = max(len(p) for p in prompts) + CHECK_TOKENS
    chosen, maps, got, ends, handed = None, None, [], [], []
    _idle(engine, slots)
    cache = engine.cache
    try:
        for i, p in enumerate(prompts):
            common.require(len(p) + CHECK_TOKENS <= max_len,
                           "a check prompt leaves no room for the decoded "
                           "tokens")
            bucket = min(b for b in buckets if b >= len(p))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(p)] = p
            logits, cache, _, seen = loop.prefill_last_inplace(
                engine.params, cache, put(padded), put(np.int32(i)),
                put(np.int32(0)), put(np.int32(len(p) - 1)))
            got.append([np.asarray(logits[0], np.float32)])
            experts = np.asarray(seen["experts"])       # [Lm, 1, bucket, k]
            seen_maps = np.asarray(seen["mhc_maps"])    # [1, bucket, M]
            if chosen is None:
                chosen = np.full((experts.shape[0], n, width,
                                  experts.shape[-1]), -1, np.int32)
                maps = np.full((n, width, seen_maps.shape[-1]), np.nan,
                               np.float32)
            chosen[:, i, :len(p)] = experts[:, 0, :len(p)]
            maps[i, :len(p)] = seen_maps[0, :len(p)]
            ends.append(_end_error(seen, 1))
            handed.append(_handed_error(seen, 1))
        tokens = np.zeros((slots, 1), np.int32)
        lengths = np.full((slots,), max_len - 1, np.int32)
        for j in range(CHECK_TOKENS - 1):
            for i, (p, a) in enumerate(zip(prompts, answers)):
                tokens[i, 0], lengths[i] = a[j], len(p) + j
            logits, cache, _, seen = loop.decode_step_whole_inplace(
                engine.params, cache, put(tokens), put(lengths))
            rows = np.asarray(logits[:n], np.float32)
            experts = np.asarray(seen["experts"][:, :n])    # [Lm, n, 1, k]
            seen_maps = np.asarray(seen["mhc_maps"][:n])    # [n, 1, M]
            ends.append(_end_error(seen, n))
            handed.append(_handed_error(seen, n))
            for i, p in enumerate(prompts):
                got[i].append(rows[i])
                chosen[:, i, len(p) + j] = experts[:, i, 0]
                maps[i, len(p) + j] = seen_maps[i, 0]
    finally:
        # The engine's one buffer, rewritten where it lay: what its
        # manager remembers of the slots' rows is gone.
        engine.cache = cache
        engine.kv.forget_resident()
    return np.asarray(got), chosen, maps, max(ends), max(handed)


def warm_and_check(handle, engine, params, config, cfg, seed: int,
                   reference) -> dict:
    """Warm every program and hold the engine to ``reference`` under
    the seven controls of this file's header."""
    eng = config["driver_args"]["engine"]
    prompts = serve_local._check_prompts(eng["prompt_buckets"],
                                         eng["max_len"], cfg.vocab_size, seed)
    # The check's requests are answered with EVERY slot of the engine
    # live: a short request for each slot they leave.
    rng = np.random.default_rng([seed, 3])
    beside = [[int(t) for t in rng.integers(1, cfg.vocab_size, 8)]
              for _ in range(eng["max_batch"] - len(prompts))]
    answers = serve_hybrid._ask(handle, prompts + beside,
                                CHECK_TOKENS)[:len(prompts)]
    got, chosen, maps, end_err, handed_err = _replay(engine, prompts,
                                                     answers, eng)

    # Teacher-forced, following the system's experts: the reference
    # reads prompt + the engine's tokens with the weights the driver
    # made, not whatever the engine keeps of them.
    tokens = np.zeros(chosen.shape[1:3], np.int32)
    rows = []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        tokens[i, :len(p)] = p
        tokens[i, len(p):len(p) + CHECK_TOKENS] = a
        rows += [(i, len(p) - 1 + j) for j in range(CHECK_TOKENS)]
    ref, report = reference.routed_logits_at(params, tokens, rows, config,
                                             chosen)
    ref = np.asarray(ref).reshape(got.shape)
    common.require(np.all(np.isfinite(ref)), "reference logits not finite")
    known = (chosen[..., 0] >= 0)                       # [Lm, B, T]
    differs = report["differs"] & known
    excess = float(np.max(np.where(known, report["excess"], 0.0)))
    share_differ = float(differs.sum() / known.sum())
    errs = np.array([[common.rel_l2(g, r) for g, r in zip(gs, rs)]
                     for gs, rs in zip(got, ref)])      # [prompts, 17]
    margins = np.array([[(row.max() - row[tok]) / (row.max() - row.min())
                         for row, tok in zip(rs, a)]
                        for rs, a in zip(ref, answers)])
    # The replay fed the prompt and the engine's first 16 tokens.
    map_errs = []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        fed = len(p) + CHECK_TOKENS - 1
        want = np.asarray(reference.first_maps(params, tokens[i, :fed],
                                               config))
        map_errs.append(float(np.max(np.abs(maps[i, :fed] - want))))
    maps_ok = bool(np.all(np.isfinite(map_errs))
                   and max(map_errs) <= TOL_MAPS_ABS)
    strict = float((margins <= TOL_TOKEN_MARGIN).mean())
    readings = {
        "maps_abs_max": float(np.nanmax(map_errs)),
        "streams_end_rel_max": end_err,
        "streams_handed_rel_max": handed_err,
        "prefill_rel_l2_max": float(errs[:, 0].max()),
        "step_rel_l2_max": float(errs[:, 1:].max()),
        "step_rel_l2_mean": float(errs[:, 1:].mean()),
        "token_margin_max": float(margins.max()),
        "token_margin_strict_share": strict,
        "argmax_agree": float((margins == 0.0).mean()),
        # Of the engine's tokens, those that the replayed programs' own
        # logits put first.
        "replay_agree": float((got.argmax(axis=-1)
                               == np.asarray(answers)).mean()),
        "route_excess_max": excess,
        "route_choices_differ_share": share_differ,
        "route_rows_differ_share": float(
            differs.any(axis=0).sum() / known.any(axis=0).sum())}
    # Every limit is read, and a refusal names each one that refused.
    refusals = [(limit, what) for limit, ok, what in (
            ("TOL_ROUTE_EXCESS", excess <= TOL_ROUTE_EXCESS,
             f"a chosen expert lies {excess:.4f} of the row's score spread "
             f"under the reference's boundary ({int(differs.sum())} of "
             f"{int(known.sum())} (row, layer) choices differ)"),
            ("TOL_ROUTE_DIFFER", share_differ <= TOL_ROUTE_DIFFER,
             f"{share_differ:.3f} of the (row, layer) choices differ from "
             f"the reference's"),
            ("TOL_LOGITS_REL_L2",
             np.all(np.isfinite(got)) and errs.max() <= TOL_LOGITS_REL_L2,
             f"logits off the reference, the system's experts followed: "
             f"rel L2 {errs[:, 0].tolist()} at the prompts' ends (the "
             f"tick's prefill), at worst {errs[:, 1:].max(axis=1).tolist()} "
             f"a row of the decode step's"),
            ("TOL_MAPS_ABS", maps_ok,
             f"the first sub-layer's maps are off the reference's: "
             f"largest |difference| {map_errs} over a prompt's rows and 16 "
             f"steps"),
            ("TOL_STREAMS_END", end_err <= TOL_STREAMS_END,
             f"the final norm did not read the sum of the last layer's "
             f"streams: relative L2 {end_err:.3g} between what it read and "
             f"their sum"),
            ("TOL_STREAMS_HANDED", handed_err <= TOL_STREAMS_HANDED,
             f"a sub-layer did not hand on H_res X + H_post^T y of what it "
             f"read: relative L2 {handed_err:.3g} at worst over the "
             f"sub-layers"),
            ("TOL_TOKENS_STRICT_SHARE", strict >= TOL_TOKENS_STRICT_SHARE,
             f"only {strict:.3f} of the engine's tokens lie within "
             f"{TOL_TOKEN_MARGIN} of the logit spread under the "
             f"reference's best (the worst {margins.max():.4f}; the step's "
             f"experts followed)")) if not ok]
    if refusals:
        refused = common.Incorrect("; ".join(what for _, what in refusals))
        refused.readings = readings         # for `degraded_mhc.py`
        refused.limits = [limit for limit, _ in refusals]
        raise refused
    return readings


def bring_up(ctx):
    """`serve_local.bring_up` with this file's check: (handle, engine,
    cfg, checks)."""
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


offer = serve_local.offer


def run(ctx) -> dict:
    """`serve_local.run` behind this file's `bring_up`: the same window,
    counters and rules for ``correct``."""
    mix = ctx.traffic
    handle, engine, cfg, checks = bring_up(ctx)
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
