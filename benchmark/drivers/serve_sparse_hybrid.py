"""Driver ``serve_sparse_hybrid``: `serve_local` for a model whose
attention CHOOSES the rows it reads (a top-k over blocks, made anew for
every query) among layers that keep a per-slot STATE, served with its
prompts prefilled IN CHUNKS. The replica, the load
(`serve_local.offer`), the counters and the rules that decide
``correct`` in the window are `serve_local`'s; what differs is the
comparison with the plain reference at set-up. It takes from both
drivers before it:

- as `serve_hybrid`, it reads THE PROGRAMS THE WINDOW TIMES in ONE
  cache of its own, of as many slots as it has long prompts (three:
  0.49 GB; a second cache as large as the engine's does not fit beside
  10 GB of weights), which starts full of ones, after every slot of the
  engine has answered a short request: a stale state or a stale
  compressed key shows in the engine's tokens and in the replayed
  logits alike. THREE prompts of the cell's lengths
  (``check_prompt_lens``: about 9.5k, 15k and 20k tokens, all past
  ``dense_len``, none a multiple of the chunk) are asked of the engine
  TOGETHER, 48 tokens each: their prefills advance a chunk a tick side
  by side, the shortest is answered while the longest is still being
  prefilled, and for a tick or two all three decode at once, at unlike
  lengths. Each is then
  prefilled in chunks of ``prefill_chunk`` into ITS OWN SLOT of the
  check's cache through the tick's prefill (``loop.prefill_last``, the
  engine's own plan of chunks and buckets: the slot's rows cut out of
  the cache and written back, the reset at ``cache_index`` 0, the state
  and the compressed keys handed from chunk to chunk, the window that
  straddles two chunks, whole 2,048-row chunks of selecting queries,
  the last bucket's padding), and the engine's tokens are replayed
  through the step ``decode_chunk`` scans (``loop.decode_step_whole``:
  the selection, the block-list kernel, the state step, the windows
  decode completes), ALL THE SLOTS IN ONE BATCH, each slot starting
  `STAGGER` steps after the one before under the step's ``live`` mask:
  one, two, three, two, one slots live, the others' state and windows
  to be left as they are. A SHORT request follows, through the engine
  and then through a used slot of the check's cache, the others not
  live: a stale state has decayed to nothing by the end
  of a prompt of 9,000 tokens (the slowest head forgets at 0.996 a
  token), and shows after 300;
- as `serve_routed`, it knows what a near-tie is. Where a query's 64th
  and 65th block nearly tie, bf16 takes the other one and that row's
  logits move by more than rounding. Both programs hand back what each
  query read: the prefill THE MASK over blocks its attention ran under
  (``seen["block_mask"]``), the step the block list its kernel was
  handed (``seen["blocks"]``); the reference
  (``reference.selected_logits_at``) FOLLOWS them and reports how they
  sit against its own selection.

Five limits, each able to fail a run:

(i)   a selected block that the reference did not select must lie
      within `TOL_SELECT_EXCESS` of the reference's 64th score, as a
      share of the spread of the scores that compete (1.0 where a
      forced block is missing, a block past the context was taken, a
      query past ``dense_len`` attended under another number of blocks
      than ``topk``, or a row under it under a selection);
(ii)  the share of selected blocks that differ from the reference's at
      all is printed and bounded by `TOL_SELECT_DIFFER`: "near a tie"
      cannot excuse a selection that is simply another;
(iii) with the system's blocks followed, the tick's logits at each
      prompt's last row and the step's at each replayed position, and
      the short request's rows, are held to `TOL_LOGITS_REL_L2`, ROW BY
      ROW;
(iv)  the first lightning layer's state of each slot after its replay
      against the reference's token-by-token recurrence
      (`TOL_STATE_REL_L2`), and the share of its float32 values that
      bf16 cannot hold (`TOL_STATE_F32_SHARE`): this family's first
      lightning layer reads a normed hidden state, not the embedding's
      own rows, so the products before the state round as much as a
      bf16 state would, and the distance alone cannot tell the two
      apart (PERF.md PR 35);
(v)   EVERY token the engine streamed (its donated programs, 16 slots,
      several live: 3 x 48 and the short request's 4) must hold
      `TOL_TOKEN_MARGIN` against the reference's logits. The chunk's
      scan may take the other block of a near-tie than the replayed
      step, which moves a logit by less than the margin: no token is
      excused.

What it does not read: rows of the cache past the longest check prompt
(20k to 32k), and the programs at the engine's 16 slots other than
through the engine's tokens. ``benchmark/degraded_sala.py`` shows the
ways of being wrong that this refuses; PERF.md has the chip's readings.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers import common, serve_hybrid, serve_local
from benchmark.harness import tracing_run

BUILDER_CALLS = ("config", "init_params", "first_state",
                 "reference.logits_at", "reference.selected_logits_at",
                 "reference.first_state")

# How far under the reference's 64th block score a block the system
# selected may lie, as a share of the spread (max - min) of the scores
# of the blocks that compete (visible, not forced). The scores are sums
# of 16 heads' softmaxes over a thousand windows and, with seeded
# weights, nearly flat; the reading is the worst of some ten million
# selected blocks. The chip's sound runs read 0.027 to 0.062 (PERF.md,
# PR 35, has each seed's); the controls that only this limit and the
# next refuse (another selection, rightly attended over) read 0.76 and
# more. Over twice the one, a fifth of the other.
TOL_SELECT_EXCESS = 0.15
# Share of the selected blocks (of rows past dense_len) that may differ
# from the reference's at all, each within the excess above: 0.0048 to
# 0.0059 in the chip's sound runs (96 % of the ROWS have one, which is
# why rows cannot be excused whole); 0.055 to 0.33 under the controls
# that select otherwise.
TOL_SELECT_DIFFER = 0.025
# Relative L2 of one replayed row of bf16 logits against the float32
# reference, the system's blocks followed: 0.014 to 0.024 at worst in
# the chip's sound runs; 0.094 (every row read where a selection is
# published) to 1.27 under the controls that compute another function.
TOL_LOGITS_REL_L2 = 4.5e-2
# How far under the reference's best logit the reference's logit of an
# engine token may lie, as a share of the row's logit spread. Held by
# every token the engine streamed: 0.0018 to 0.0067 at worst of a run's
# 148 in the chip's sound runs; 0.03 to 0.5 under the controls whose
# tokens are another function's.
TOL_TOKEN_MARGIN = 0.02
# Relative L2 of the first lightning layer's state after a replay
# against the reference's recurrence: 0.0028 after 9k tokens to 0.0035
# after 20k in every sound run of the chip (the layer before it and
# its own projections round their operands to bf16: no seed moves it);
# 0.023 (the sparse layer before it computing another function) to 9.5
# under the controls.
TOL_STATE_REL_L2 = 1e-2
# Share of that state's float32 values whose low 16 bits are not all
# zero: 1 - 2^-16 of them in a float32 sum, none after a rounding to
# bf16 anywhere on the way.
TOL_STATE_F32_SHARE = 0.9

CHECK_TOKENS = 48       # of each long prompt: six chunks of the engine's
REUSE_TOKENS = 4        # of the short request that takes a used slot
STAGGER = 8             # steps between two check slots' first replayed step
REUSE_SLOT = 1          # the check's slot the short request is replayed in


def _check_prompts(config, vocab, seed):
    """Seeded prompts: one of a seeded length in each range of the
    configuration's ``check_prompt_lens`` (past ``dense_len``, no
    multiple of a chunk), and a short one, half to the whole of the
    smallest bucket, for a slot's next request."""
    small = config["driver_args"]["engine"]["prompt_buckets"][0]
    rng = np.random.default_rng([seed, 1])
    lens = [int(rng.integers(lo, hi))
            for lo, hi in config["driver_args"]["check_prompt_lens"]]
    lens.append(int(rng.integers(small // 2, small)))
    prompts = [[int(t) for t in rng.integers(1, vocab, n)] for n in lens]
    return prompts[:-1], prompts[-1]


def _dirty(handle, eng, vocab, seed):
    """One short request a slot, together, their lengths going round
    the prompt buckets: every slot of the engine then holds a state,
    rows and compressed keys that are not the next request's, and every
    bucket's prefill and the chunk are warm."""
    rng = np.random.default_rng([seed, 2])
    lens, prev = [], 0
    for b in eng["prompt_buckets"]:
        lens.append(max(1, (prev + b) // 2))
        prev = b
    serve_hybrid._ask(
        handle, [[int(t) for t in rng.integers(1, vocab, lens[i % len(lens)])]
                 for i in range(eng["max_batch"])], 2)


def listed(mask, topk: int):
    """mask [.., NBLK] bool, the blocks a prefill's queries attended
    under -> ids [.., topk] int32, lowest first; -1 where a query read
    another number of blocks than ``topk`` (a dense context reads every
    block)."""
    mask = np.asarray(mask)
    selects = mask.sum(axis=-1) == topk
    ids = np.full(mask.shape[:-1] + (topk,), -1, np.int32)
    ids[selects] = np.nonzero(mask[selects])[-1].reshape(-1, topk)
    return ids


def _replay(engine, cache, asked, eng, cfg, first_state):
    """``asked`` [(slot, prompt, answer)]: each prompt through the
    tick's prefill, in the engine's own chunks, into its slot of
    ``cache`` (the check's own; whatever it holds is stale); then the
    decode step on the engine's own tokens but the last, every slot of
    the cache in one batch: request i's first step is step `STAGGER` x
    i, and a slot before its first step, after its last or with no
    request is not live. -> ([(logits [len(answer), V]: the prefill's
    one row, then a row a step; chosen [sparse layers, T, KH, topk]:
    every query's blocks, -1 where its context is dense; the first
    lightning layer's state of the slot after its last step)], cache)."""
    import jax

    put, loop = jax.device_put, engine.loop
    topk = cfg.selection.topk
    got, chosen = [], []
    for slot, prompt, answer in asked:
        common.require(len(prompt) + len(answer) <= eng["max_len"],
                       "a check prompt leaves no room for the decoded tokens")
        ids, pos = None, 0
        for n, bucket in engine.scheduler.prefill_plan(len(prompt)):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = prompt[pos:pos + n]
            # Rebinding drops the last cache: never more than the one
            # read and the one written.
            logits, cache, _, seen = loop.prefill_last(
                engine.params, cache, put(padded), put(np.int32(slot)),
                put(np.int32(pos)), put(np.int32(n - 1)))
            blocks = listed(seen["block_mask"], topk)  # [Ls,1,bucket,KH,k]
            if ids is None:
                ids = np.full((blocks.shape[0], len(prompt) + len(answer))
                              + blocks.shape[3:], -1, np.int32)
            ids[:, pos:pos + n] = blocks[:, 0, :n]
            pos += n
        got.append([np.asarray(logits[0], np.float32)])
        chosen.append(ids)
    slots = next(iter(cache.values())).shape[1]
    steps = max(STAGGER * i + len(answer) - 1
                for i, (_, _, answer) in enumerate(asked))
    for step in range(steps):
        # A slot with no request parks its row on the cache's last.
        tokens = np.zeros((slots, 1), np.int32)
        lengths = np.full((slots,), eng["max_len"] - 1, np.int32)
        live = np.zeros((slots,), bool)
        for i, (slot, prompt, answer) in enumerate(asked):
            j = step - STAGGER * i
            live[slot] = 0 <= j < len(answer) - 1
            # Not live, a slot stands at its first or its last step: the
            # row it writes there is the one the live step writes.
            j = min(max(j, 0), len(answer) - 2)
            tokens[slot, 0], lengths[slot] = answer[j], len(prompt) + j
        logits, cache, _, seen = loop.decode_step_whole(
            engine.params, cache, put(tokens), put(lengths), put(live))
        rows, blocks = np.asarray(logits, np.float32), np.asarray(
            seen["blocks"])                             # [Ls,B,1,KH,topk]
        for i, (slot, _, _) in enumerate(asked):
            if live[slot]:
                got[i].append(rows[slot])
                chosen[i][:, lengths[slot]] = blocks[:, slot, 0]
    states = [np.asarray(first_state(cfg, cache, slot))
              for slot, _, _ in asked]
    return ([(np.asarray(g), c, s) for g, c, s in zip(got, chosen, states)],
            cache)


def _against_reference(reference, params, config, prompt, answer, got,
                       chosen, state):
    """One replayed request against the reference, the system's blocks
    followed: -> (rel L2 a replayed row, the engine's tokens' margins,
    the state's rel L2, the selection's report over the rows fed)."""
    tokens = np.asarray([list(prompt) + list(answer)], np.int32)
    rows = [(0, len(prompt) - 1 + j) for j in range(len(answer))]
    ref, report = reference.selected_logits_at(params, tokens, rows, config,
                                               chosen[:, None])
    ref = np.asarray(ref)
    common.require(np.all(np.isfinite(ref)), "reference logits not finite")
    fed = len(prompt) + len(answer) - 1         # rows the replay fed
    errs = np.array([common.rel_l2(g, r) for g, r in zip(got, ref)])
    margins = np.array([(row.max() - row[tok]) / (row.max() - row.min())
                        for row, tok in zip(ref, answer)])
    # The state after the rows fed is what the replay's cache holds.
    state_ref = np.asarray(reference.first_state(
        params, tokens[0, :fed], config, chosen[:, :fed]))
    return (errs, margins, common.rel_l2(state, state_ref),
            {k: v[:, 0, :fed] for k, v in report.items()})


def _f32_share(state):
    return float(np.mean((np.ascontiguousarray(state, np.float32)
                          .view(np.uint32) & 0xFFFF) != 0))


def warm_and_check(handle, engine, params, config, cfg, seed: int,
                   reference, first_state) -> dict:
    """Warm every program and hold the engine to ``reference`` under
    the limits of this file's header."""
    import jax

    eng = config["driver_args"]["engine"]
    prompts, again = _check_prompts(config, cfg.vocab_size, seed)
    clock = [time.perf_counter()]
    _dirty(handle, eng, cfg.vocab_size, seed)
    answers = serve_hybrid._ask(handle, prompts, CHECK_TOKENS)
    # A short request next: whichever slot takes it served another
    # request before, as does the check's own slot below.
    answer_again = serve_hybrid._ask(handle, [again], REUSE_TOKENS)[0]
    clock.append(time.perf_counter())
    cache = jax.jit(lambda: jax.tree.map(
        lambda a: a + 1,
        cfg.model.init_kv_cache(cfg, len(prompts), eng["max_len"])))()
    replayed, cache = _replay(
        engine, cache, [(i, p, a) for i, (p, a)
                        in enumerate(zip(prompts, answers))],
        eng, cfg, first_state)
    reuse = _replay(engine, cache, [(REUSE_SLOT, again, answer_again)], eng,
                    cfg, first_state)[0][0]
    # The reference's float32 blocks want the room the check's cache
    # took; the device's peak so far is the engine's and the replay's.
    del cache
    replay_peak = (jax.local_devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    clock.append(time.perf_counter())
    compared = [_against_reference(reference, params, config, p, a, *r)
                for p, a, r in zip(prompts, answers, replayed)]
    reuse_errs, reuse_margins, reuse_state_err, _ = _against_reference(
        reference, params, config, again, answer_again, *reuse)
    clock.append(time.perf_counter())

    sizes = config["sparse_config"]
    layers, _, heads, topk = replayed[0][1].shape
    past = sum(int((np.arange(c[3]["excess"].shape[1])
                    >= sizes["dense_len"]).sum()) for c in compared)
    known = past * layers * heads * topk    # blocks of rows that select
    excess = max(float(c[3]["excess"].max()) for c in compared)
    differs = sum(int(c[3]["differs"].sum()) for c in compared)
    rows_differ = sum(int((c[3]["differs"] > 0).any(axis=(0, 2)).sum())
                      for c in compared)
    share_differ = differs / max(known, 1)
    errs = np.array([c[0] for c in compared])           # [prompts, tokens]
    state_errs = [float(c[2]) for c in compared]
    f32_share = min(_f32_share(r[2]) for r in replayed)
    every_margin = np.concatenate([c[1] for c in compared] + [reuse_margins])
    got = np.array([r[0] for r in replayed])
    readings = {
        "prompt_tokens": [len(p) for p in prompts],
        "select_excess_max": excess,
        "select_blocks_differ_share": share_differ,
        "select_rows_differ_share": rows_differ / max(past, 1),
        "prefill_rel_l2": float(errs[:, 0].max()),
        "step_rel_l2_max": float(errs[:, 1:].max()),
        "engine_tokens": int(every_margin.size),
        "token_margin_max": float(every_margin.max()),
        "argmax_agree": float((every_margin == 0.0).mean()),
        # Of the engine's tokens, those that the replayed programs' own
        # logits put first.
        "replay_agree": float((got.argmax(axis=-1)
                               == np.asarray(answers)).mean()),
        "state_rel_l2": max(state_errs),
        "state_f32_share": f32_share,
        "reuse_prompt_tokens": len(again),
        "reuse_rel_l2_max": float(reuse_errs.max()),
        "reuse_state_rel_l2": float(reuse_state_err),
        "memory_peak_bytes_before_reference": replay_peak,
        # Of set-up: the engine's answers (its programs' compiles among
        # them), the replay, the reference.
        "seconds_asked_replayed_referred": [
            round(b - a, 1) for a, b in zip(clock, clock[1:])]}
    worst, worst_state = (max(errs.max(), reuse_errs.max()),
                          max(max(state_errs), reuse_state_err))
    for ok, what in (
            (excess <= TOL_SELECT_EXCESS,
             f"a selected block lies {excess:.4f} of the score spread under "
             f"the reference's boundary ({differs} of {known} selected "
             f"blocks differ)"),
            (share_differ <= TOL_SELECT_DIFFER,
             f"{share_differ:.4f} of the selected blocks differ from the "
             f"reference's"),
            (np.all(np.isfinite(got)) and worst <= TOL_LOGITS_REL_L2,
             f"logits off the reference, the system's blocks followed: rel "
             f"L2 {errs[:, 0].tolist()} at the prompts' ends (the tick's "
             f"prefill, in chunks), at worst {errs[:, 1:].max(axis=1).tolist()}"
             f" a row of the decode step's, {reuse_errs.max():.4f} for the "
             f"short request that took a slot next"),
            (worst_state <= TOL_STATE_REL_L2,
             f"the first lightning layer's state is off the reference's "
             f"recurrence: rel L2 {state_errs} after a prompt and "
             f"{CHECK_TOKENS - 1} steps, {reuse_state_err:.5f} after the "
             f"short request that took a slot next"),
            (f32_share >= TOL_STATE_F32_SHARE,
             f"only {f32_share:.3f} of the state's float32 values hold "
             f"more than bf16 does: it was rounded on the way"),
            (every_margin.max() <= TOL_TOKEN_MARGIN,
             f"an engine token lies {every_margin.max():.4f} of the logit "
             f"spread under the reference's best (of {every_margin.size} "
             f"tokens: {len(prompts)} prompts asked together, then a short "
             f"one, through slots that served another request first)")):
        if not ok:
            refused = common.Incorrect(what)
            refused.readings = readings     # for `degraded_sala.py`
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
                                ctx.seed, ctx.builder.reference,
                                ctx.builder.first_state)
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
