"""Driver ``serve_routed``: `serve_local` for a model that ROUTES its
tokens to experts. The replica, the load (`serve_local.offer`), the
counters and the rules that decide ``correct`` in the window are
`serve_local`'s; what differs is the comparison with the plain
reference at set-up, because a routed model in bf16 does not reproduce
a float32 reference row by row: where a token's k-th and next router
score nearly tie, the set of experts flips, and that row's logits move
by 5 to 15 %. A single relative L2 of 3e-2 a row would refuse sound
runs on most seeds.

So the comparison knows what a near-tie is, and it reads THE PROGRAMS
THE WINDOW TIMES: the tick's prefill (``loop.prefill_last``: the
padding mask, the last-row gather; the functional twin of the donated
program) and the decode step (``loop.decode_step_whole``: the absorbed
attention over the latent cache, the step's own routing and grouped
products; what ``decode_chunk`` scans), both of which return each
token's chosen experts beside their logits. After the engine has
answered one request a prompt bucket, the check prefills the same
prompts with the tick's program into a cache of its own and replays the
engine's 16 decoded tokens through the step, one step a token. The
reference (``reference.routed_logits_at``) reports, a row a layer, how
those choices sit against ITS OWN router's boundary, then follows them.
Four controls, each able to fail a run:

(i)   a system choice that the reference did not make must lie inside
      `TOL_ROUTE_EXCESS` of the reference's boundary (the k-th
      ``score + bias``), as a share of the row's spread of them: on
      every prompt row of the tick's prefill and every replayed step;
(ii)  with the system's choices followed, the tick's logits at each
      prompt's last row and the step's at each of the 16 decoded
      positions are held to `serve_local.TOL_LOGITS_REL_L2`, ROW BY ROW;
(iii) the tokens the engine streamed (its donated programs, the chunk's
      scan, the tick) against those logits. They are the only thing the
      timed programs give out, and they cannot all be held: the chunk
      and the standalone step are two compilations of one function, XLA
      rounds them differently (it keeps excess precision where it
      fuses), so on the chip about one token in six was chosen by a
      step that took the other expert of a near-tie, and such a row's
      token can lie a fifth of the logit spread under the reference's
      best. So the limit is on their SHARE: at least
      `TOL_TOKENS_STRICT_SHARE` of the engine's tokens hold
      `serve_local.TOL_TOKEN_MARGIN`, the dense families' limit. That
      refuses a chunk or a tick that does not deliver the step's tokens
      (it then holds the margin on none); the step's PRECISION is (ii)'s
      to judge, on the function the chunk scans;
(iv)  the share of (row, layer) choices that differ from the
      reference's is printed and bounded by `TOL_ROUTE_DIFFER`, so that
      "near a tie" cannot excuse a router that is simply different.

``tests/benchmark/test_routed_controls.py`` shows on the CPU that this
fails weights rounded to int8, a router that selects on the score
without its bias, one without the scaling factor, one without the
normalisation, a decode step alone in int8, a tick prefill alone that
reads the wrong row and a chunk alone that is fed the wrong token, and
passes the sound program
(``benchmark/degraded_routed.py``; PERF.md has the chip's readings).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.drivers import common, serve_local
from benchmark.harness import tracing_run

BUILDER_CALLS = ("config", "init_params", "reference.logits_at",
                 "reference.routed_logits_at")

# How far under the reference's k-th ``score + bias`` a system choice
# may lie, as a share of the row's spread of ``score + bias`` over the
# experts. bf16 keeps 8 bits, and the router reads a hidden state that
# carries the rounding of every layer before it. PERF.md (PR 29) has
# the readings: the largest excess the chip showed over its seeds, and
# a router that selects without its bias (most rows differ, by a large
# share of the spread).
TOL_ROUTE_EXCESS = 0.035
# Share of the (row, layer) choices that may differ from the
# reference's at all (each within the excess above).
TOL_ROUTE_DIFFER = 0.15
# Share of the engine's tokens that must hold the dense families' limit
# (the rest sit on rows where the chunk's compilation of the step took
# the other expert of a near-tie). PERF.md (PR 29) has the readings:
# the least the sound program showed, and a chunk fed the wrong token.
TOL_TOKENS_STRICT_SHARE = 0.6

CHECK_TOKENS = serve_local.CHECK_TOKENS


def _ask(handle, prompts):
    """One streamed request a prompt, together: they warm every program
    of the tick, and their tokens are what (iii) judges."""
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
    return answers


def _replay(engine, prompts, answers, eng):
    """The tick's prefill and the decode step on the engine's own
    tokens, in a cache of the check's own (the functional programs: the
    engine's cache lives on untouched). -> (logits [prompts, 17, V]:
    the prefill's one row, then a row a step; chosen [Lm, prompts,
    width, k]: the experts of every prompt row and every replayed
    position, -1 where no token was fed)."""
    import jax

    put, loop = jax.device_put, engine.loop
    buckets, max_len = eng["prompt_buckets"], eng["max_len"]
    common.require(len(prompts) <= eng["max_batch"],
                   "more check prompts than slots")
    width = max(len(p) for p in prompts) + CHECK_TOKENS
    cache, chosen, got = engine.cache, None, []
    for i, p in enumerate(prompts):
        common.require(len(p) + CHECK_TOKENS <= max_len,
                       "a check prompt leaves no room for the decoded "
                       "tokens")
        bucket = min(b for b in buckets if b >= len(p))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(p)] = p
        # Rebinding drops the last cache of the check's own: never more
        # than the engine's, the one read and the one written.
        logits, cache, _, seen = loop.prefill_last(
            engine.params, cache, put(padded), put(np.int32(i)),
            put(np.int32(0)), put(np.int32(len(p) - 1)))
        got.append([np.asarray(logits[0], np.float32)])
        experts = np.asarray(seen["experts"])           # [Lm, 1, bucket, k]
        if chosen is None:
            chosen = np.full((experts.shape[0], len(prompts), width,
                              experts.shape[-1]), -1, np.int32)
        chosen[:, i, :len(p)] = experts[:, 0, :len(p)]
    # Idle slots parked on their last row, as the engine's roster does.
    tokens = np.zeros((eng["max_batch"], 1), np.int32)
    lengths = np.full((eng["max_batch"],), max_len - 1, np.int32)
    for j in range(CHECK_TOKENS - 1):
        for i, (p, a) in enumerate(zip(prompts, answers)):
            tokens[i, 0], lengths[i] = a[j], len(p) + j
        logits, cache, _, seen = loop.decode_step_whole(
            engine.params, cache, put(tokens), put(lengths))
        rows = np.asarray(logits[:len(prompts)], np.float32)
        experts = np.asarray(seen["experts"])           # [Lm, B, 1, k]
        for i, p in enumerate(prompts):
            got[i].append(rows[i])
            chosen[:, i, len(p) + j] = experts[:, i, 0]
    return np.asarray(got), chosen


def warm_and_check(handle, engine, params, config, cfg, seed: int,
                   reference) -> dict:
    """Warm every program and hold the engine to ``reference`` under
    the four controls of this file's header."""
    eng = config["driver_args"]["engine"]
    prompts = serve_local._check_prompts(eng["prompt_buckets"],
                                         eng["max_len"], cfg.vocab_size, seed)
    answers = _ask(handle, prompts)
    got, chosen = _replay(engine, prompts, answers, eng)

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
    # Like with like, row by row: the tick's prefill, then the step.
    errs = np.array([[common.rel_l2(g, r) for g, r in zip(gs, rs)]
                     for gs, rs in zip(got, ref)])      # [prompts, 17]
    margins = np.array([[(row.max() - row[tok]) / (row.max() - row.min())
                         for row, tok in zip(rs, a)]
                        for rs, a in zip(ref, answers)])
    readings = {
        "prefill_rel_l2_max": float(errs[:, 0].max()),
        "step_rel_l2_max": float(errs[:, 1:].max()),
        "token_margin_max": float(margins.max()),
        "token_margin_strict_share": float(
            (margins <= serve_local.TOL_TOKEN_MARGIN).mean()),
        "argmax_agree": float((margins == 0.0).mean()),
        # Of the engine's tokens, those that the replayed programs' own
        # logits put first.
        "replay_agree": float((got.argmax(axis=-1)
                               == np.asarray(answers)).mean()),
        "route_excess_max": excess,
        "route_choices_differ_share": share_differ,
        "route_rows_differ_share": float(
            differs.any(axis=0).sum() / known.any(axis=0).sum())}
    for ok, what in (
            # (i) every differing choice near the reference's boundary,
            (excess <= TOL_ROUTE_EXCESS,
             f"a chosen expert lies {excess:.4f} of the row's score spread "
             f"under the reference's boundary ({int(differs.sum())} of "
             f"{int(known.sum())} (row, layer) choices differ)"),
            # (iv) and not too many of them;
            (share_differ <= TOL_ROUTE_DIFFER,
             f"{share_differ:.3f} of the (row, layer) choices differ from "
             f"the reference's"),
            # (ii) every row of logits;
            (errs.max() <= serve_local.TOL_LOGITS_REL_L2,
             f"logits off the reference, the system's experts followed: "
             f"rel L2 {errs[:, 0].tolist()} at the prompts' ends (the "
             f"tick's prefill), at worst {errs[:, 1:].max(axis=1).tolist()} "
             f"a row of the decode step's"),
            # (iii) most of the tokens the engine streamed.
            (readings["token_margin_strict_share"] >= TOL_TOKENS_STRICT_SHARE,
             f"only {readings['token_margin_strict_share']:.3f} of the "
             f"engine's tokens lie within {serve_local.TOL_TOKEN_MARGIN} of "
             f"the logit spread under the reference's best (the worst "
             f"{margins.max():.4f}; the step's experts followed)")):
        if not ok:
            refused = common.Incorrect(what)
            refused.readings = readings     # for `degraded_routed.py`
            raise refused
    return readings


def bring_up(ctx):
    """`serve_local.bring_up` with this file's check (that one calls its
    module's own): (handle, engine, cfg, checks)."""
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
