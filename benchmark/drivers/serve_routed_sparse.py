"""Driver ``serve_routed_sparse``: `serve_local` for a model that both
ROUTES its tokens to experts (of which this chip holds a share) and
whose full-attention layers CHOOSE the single rows each query reads,
beside layers that keep only a window of rows, served with its prompts
prefilled IN CHUNKS. The replica, the load (`serve_local.offer`), the
counters and the rules that decide ``correct`` in the window are
`serve_local`'s; what differs is the comparison with the plain
reference at set-up, which takes from the two drivers before it:

- as `serve_sparse_hybrid` (whose `_check_prompts`, `_dirty` and
  replay schedule it uses as they stand), it reads THE PROGRAMS THE
  WINDOW TIMES in ONE cache of its own, of as many slots as it has long
  prompts, which starts full of ones, after every slot of the engine
  has answered a short request. THREE prompts of the cell's lengths
  (``check_prompt_lens``: about 9.5k, 15k and 20k tokens, none a
  multiple of the chunk) are asked of the engine TOGETHER,
  `CHECK_TOKENS` tokens each; each is then prefilled in the engine's
  own chunks into ITS OWN SLOT of the check's cache through the tick's
  prefill (``loop.prefill_last``: the latent rows, the index keys and
  the ring handed from chunk to chunk, whole 2,048-row chunks of
  selecting queries, the last bucket's padding), and the engine's
  tokens are replayed through the step ``decode_chunk`` scans
  (``loop.decode_step_whole``), all the slots in one batch, staggered
  under the step's ``live`` mask. A SHORT request follows, through the
  engine and then through a used slot of the check's cache: its rows
  are fewer than the window and than ``index_topk``, so whatever the
  slot's last owner left in the ring or under the mask would show;
- as `serve_routed` and `serve_sparse_hybrid`, it knows what a
  near-tie is. Both programs hand back each token's chosen experts
  (``seen["experts"]``) and THE MASK over rows each full layer's
  attention ran under (``seen["rows"]``); the reference
  (``reference.followed_logits_at``) FOLLOWS them and reports how each
  sits against its own boundary.

Nine limits, each able to fail a run:

(i)   a chosen expert that the reference did not choose must lie
      within `TOL_ROUTE_EXCESS` of the reference's boundary, as a share
      of the row's spread of ``score + bias`` (`serve_routed`'s (i));
(ii)  the share of (row, layer) expert choices that differ at all is
      bounded by `TOL_ROUTE_DIFFER`;
(iii) where the selection differs from the reference's, the exchange
      must lie within `TOL_SELECT_EXCESS` of the reference's
      ``index_topk``-th score ON BOTH SIDES (the lowest row followed
      under it, the highest row not followed over it), as a share of
      the spread of the visible rows' scores (1.0 where a query
      attended to a row past itself, or to another number of rows than
      the rule gives);
(iv)  the share of selected rows that differ from the reference's at
      all is bounded by `TOL_SELECT_DIFFER`;
(v)   with the system's experts and rows followed, the tick's logits at
      each prompt's last row and the step's at each replayed position,
      and the short request's rows, are held to `TOL_LOGITS_REL_L2`,
      ROW BY ROW;
(vi)  of the tokens the engine streamed (its donated programs, 16
      slots, several live), at least `TOL_TOKENS_STRICT_SHARE` hold
      `TOL_TOKEN_MARGIN` against the reference's logits
      (`serve_routed`'s (iii), and its reason: the chunk's compilation
      of the step may take the other expert of a near-tie).

(vii) the decode step's selection, query by query, is the exact top
      ``index_topk`` of float32 scores computed from THE STEP'S OWN
      operands (the indexer's queries and head weights it hands back,
      the index keys in the check's cache): the share of selected rows
      that lie under that top's last score by more than a tie
      (`OWN_SELECT_TIE` of the scores' spread: two orders of float32
      additions exchange such rows) is bounded by
      `TOL_OWN_SELECT_DIFFER`. Against the
      reference every score carries the bf16 stream's rounding of all
      the layers before, which hides a score kept in bf16; against its
      own operands nothing is left but the scoring and the search;
(viii) the decode step's gates, expert layer by expert layer, against
      the float32 router recomputed from the normed stream the step
      itself read (``seen["router_in"]``) over the experts it chose:
      relative L2 within `TOL_GATES_REL_L2`, for the same reason;
(ix)  every query of a sliding layer, in the tick's prefill and in the
      step, attended to exactly the rows of the published window
      (``sliding_window_size`` of the FILE, its own row among them),
      counted from the mask its attention ran under: none may differ.

What it does not read: rows of the cache past the longest check prompt
(20k to 32k), and the programs at the engine's 16 slots other than
through the engine's tokens. ``benchmark/degraded_dots3.py`` shows the
ways of being wrong that this refuses; PERF.md (PR 42) has the chip's
readings beside each limit.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers import (
    common,
    serve_hybrid,
    serve_local,
    serve_routed,
    serve_sparse_hybrid,
)
from benchmark.harness import tracing_run

BUILDER_CALLS = ("config", "init_params", "reference.logits_at",
                 "reference.followed_logits_at")

# Every limit lies between two readings on the chip (my runs, PR 42;
# PERF.md section 6 has the table): the largest the SOUND program read
# over ten seeds, and what the control the limit is for read, the
# nearest precision below the stated one where that is what it guards.
#
# (i) How far under the reference's k-th ``score + bias`` a chosen
# expert may lie, as a share of the row's spread over the 256 experts
# (`serve_routed`'s (i); its 0.035 is GLM's, over 64). Sound 0.0092 to
# 0.0123; int8 weights 0.039.
TOL_ROUTE_EXCESS = 0.025
# (ii) Share of the (row, layer) choices that differ from the
# reference's at all. The 8th and 9th of 256 scores lie closer than the
# 4th and 5th of 64, so a bf16 stream exchanges them more often than
# GLM's 0.03 to 0.05: sound 0.109 to 0.113; int8 weights 0.354.
TOL_ROUTE_DIFFER = 0.2
# (iii) How far across the reference's 2,048th index score the two
# selections may differ, as a share of the spread (max - min) of the
# query's visible rows' scores. The scores are float32 sums of 64 heads'
# ReLU'd products of bf16 operands over a hidden state that carries
# every earlier layer's rounding; the reading is the worst of some
# ninety million selected rows. Sound 0.0114 to 0.0141; int8 weights
# 0.054, a score without its ReLU 0.43, without its head weights 0.81,
# 2,047 rows 1.0.
TOL_SELECT_EXCESS = 0.03
# (iv) Share of the selected rows (of queries past index_topk) that
# differ from the reference's at all. Sound 0.0056 to 0.0057; int8
# weights 0.0245, no ReLU 0.26.
TOL_SELECT_DIFFER = 0.015
# (v) Relative L2 of one replayed row of bf16 logits against the
# float32 reference, the system's experts and rows followed. Sound:
# the prefill's row 0.0156 to 0.0189, a step's 0.0192 to 0.0207, the
# short request's 0.0183 to 0.0198; int8 weights 0.056 to 0.063; the
# gate or the rescale left out 1.0 and 1.4.
TOL_LOGITS_REL_L2 = 0.035
# (vi) `serve_routed`'s share, of tokens that hold `serve_sparse_hybrid`'s
# margin (long prompts: the logit spread of a 19,008-row slice).
TOL_TOKEN_MARGIN = serve_sparse_hybrid.TOL_TOKEN_MARGIN
TOL_TOKENS_STRICT_SHARE = serve_routed.TOL_TOKENS_STRICT_SHARE
# (vii) Selected rows of the replayed decode steps that lie under the
# float32 top index_topk of the step's own operands by more than a tie.
# Products of bf16 operands are exact in float32 on both sides, so what
# is left is the order of 8,192 additions. Sound 0 (one row of 383,148
# before the check parked a finished slot past its rows); a score kept
# in bf16 0.0026, no ReLU 0.33, no head weights 0.82, 2,047 rows 0.99.
TOL_OWN_SELECT_DIFFER = 5e-4
OWN_SELECT_TIE = 1e-5   # of the visible rows' score spread: a tie
# (viii) float32 against float32 at the chip's highest precision. Sound
# 1.3e-7 to 2.4e-7; a router whose weights and gates are bf16 2.7e-3;
# gates normalised over the held experts alone 2.7.
TOL_GATES_REL_L2 = 1e-4

CHECK_TOKENS = 32       # of each long prompt: four chunks of the engine's
REUSE_TOKENS = serve_sparse_hybrid.REUSE_TOKENS
STAGGER = serve_sparse_hybrid.STAGGER
REUSE_SLOT = serve_sparse_hybrid.REUSE_SLOT


def _packed(rows):
    """seen["rows"] [.., S] bool on the device -> uint8 [.., S/8] on the
    host (a chunk's masks are 134 MB as they stand)."""
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(lambda m: jnp.packbits(m, axis=-1))(rows))


def _window_wrong(seen, at, positions, window):
    """Queries of the sliding layers (``seen`` at index ``at`` of its
    [sliding layers, slots, T] reports, at ``positions``) whose
    attention ran over other rows than the window's."""
    rows = np.asarray(seen["window_rows"])[(slice(None),) + at]
    first = np.asarray(seen["window_first"])[(slice(None),) + at]
    positions = np.asarray(positions)
    return int(((rows != np.minimum(positions + 1, window))
                | (first != np.maximum(positions - (window - 1), 0))).sum())


def _own_selection_differs(keys, q, w, positions, masks, topk):
    """One slot's replayed decode steps against the float32 top
    ``topk`` of their own operands: keys [Lf,S,Di] (the check's cache),
    q [J,Lf,Hi,Di], w [J,Lf,Hi], positions [J], masks [Lf,J,S/8] uint8
    (what each step attended under) -> (rows that differ, rows
    selected)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def differs(keys, q, w, pos, mask):
        highest = jax.lax.Precision.HIGHEST
        part = jnp.einsum("jhd,sd->jhs", q.astype(jnp.float32),
                          keys.astype(jnp.float32), precision=highest)
        scores = jnp.einsum("jhs,jh->js", jax.nn.relu(part),
                            w.astype(jnp.float32), precision=highest)
        s = keys.shape[0]
        visible = jnp.arange(s)[None, :] <= pos[:, None]
        # A row all of whose heads' products are negative scores an
        # exact zero, of either sign: one value, the lower row first.
        scores = jnp.where(scores == 0, 0.0, scores)
        ranked = jnp.where(visible, scores, -jnp.inf)
        best, _ = jax.lax.top_k(ranked, min(topk, s))
        spread = jnp.max(ranked, -1) - jnp.min(
            jnp.where(visible, scores, jnp.inf), -1)
        # Two orders of float32 additions exchange rows that tie to
        # 1e-7 of their size; a row counts once it lies further under
        # the k-th score than `OWN_SELECT_TIE` of the spread.
        floor = best[:, -1] - OWN_SELECT_TIE * spread
        got = jnp.unpackbits(mask, axis=-1).astype(bool)[:, :s]
        under = jnp.sum(got & (~visible | (scores < floor[:, None])), -1)
        dense = pos + 1 <= topk
        want = jnp.minimum(pos + 1, topk)
        # A query that attended to another NUMBER of rows counts with
        # all of them; one with no more than topk visible reads them all.
        return jnp.sum(jnp.where(
            jnp.sum(got, -1) != want, want,
            jnp.where(dense, jnp.sum(got & ~visible, -1), under)))

    wrong = 0
    for layer in range(keys.shape[0]):
        for a in range(0, len(positions), 8):       # [8,Hi,S] float32
            b = slice(a, a + 8)
            wrong += int(differs(keys[layer], q[b, layer], w[b, layer],
                                 jnp.asarray(positions[b]),
                                 jnp.asarray(masks[layer, b])))
    selected = int(np.minimum(np.asarray(positions) + 1, topk).sum()
                   * keys.shape[0])
    return wrong, selected


def _own_gates_rel_l2(router, config, router_in, experts, gates):
    """The step's gates against the float32 router on the stream the
    step read: router [Lm,d,E], router_in [N,Lm,d], experts and gates
    [N,Lm,k] -> the worst relative L2 of a (token, layer)'s gates."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.einsum(
        "nld,lde->nle", jnp.asarray(router_in, jnp.float32),
        router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
    want = jnp.take_along_axis(s, jnp.asarray(experts), axis=-1)
    if config["norm_topk_prob"]:
        want = want / (jnp.sum(want, -1, keepdims=True) + 1e-20)
    want = np.asarray(want * config["routed_scaling_factor"])
    gates = np.asarray(gates, np.float32)
    return float((np.linalg.norm(gates - want, axis=-1)
                  / np.linalg.norm(want, axis=-1)).max())


def _replay(engine, cache, asked, eng, config):
    """``asked`` [(slot, prompt, answer)]: each prompt through the
    tick's prefill, in the engine's own chunks, into its slot of
    ``cache`` (the check's own; whatever it holds is stale); then the
    decode step on the engine's own tokens but the last, every slot of
    the cache in one batch: request i's first step is step `STAGGER` x
    i, and a slot before its first step, after its last or with no
    request is not live. -> ([(logits [len(answer), V]: the prefill's
    one row, then a row a step; experts [expert layers, T, k], -1 where
    no token was fed; rows [full layers, T, S/8] uint8: the packed mask
    each query attended under)], cache, own): ``own`` is what the
    programs say of themselves, held to their own operands:
    ``window_wrong`` (limit ix), ``select_differs`` and
    ``select_rows`` (vii), ``gates_rel_l2`` (viii)."""
    import jax

    put, loop = jax.device_put, engine.loop
    got, experts, rows = [], [], []
    window, topk = config["sliding_window_size"], config["index_topk"]
    own = {"window_wrong": 0, "select_differs": 0, "select_rows": 0,
           "gates_rel_l2": 0.0}
    stepped = [{"pos": [], "q": [], "w": [], "router_in": [], "experts": [],
                "gates": []} for _ in asked]
    for slot, prompt, answer in asked:
        common.require(len(prompt) + len(answer) <= eng["max_len"],
                       "a check prompt leaves no room for the decoded tokens")
        width = len(prompt) + len(answer)
        chose, masks, pos = None, None, 0
        for n, bucket in engine.scheduler.prefill_plan(len(prompt)):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = prompt[pos:pos + n]
            # Rebinding drops the last cache: never more than the one
            # read and the one written.
            logits, cache, _, seen = loop.prefill_last(
                engine.params, cache, put(padded), put(np.int32(slot)),
                put(np.int32(pos)), put(np.int32(n - 1)))
            e = np.asarray(seen["experts"])             # [Lm,1,bucket,k]
            m = _packed(seen["rows"])                   # [Lf,1,bucket,S/8]
            if chose is None:
                chose = np.full((e.shape[0], width, e.shape[-1]), -1,
                                np.int32)
                masks = np.zeros((m.shape[0], width, m.shape[-1]), np.uint8)
            chose[:, pos:pos + n] = e[:, 0, :n]
            masks[:, pos:pos + n] = m[:, 0, :n]
            own["window_wrong"] += _window_wrong(
                seen, (0, slice(0, n)), pos + np.arange(n), window)
            pos += n
        got.append([np.asarray(logits[0], np.float32)])
        experts.append(chose)
        rows.append(masks)
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
            # A slot that is not live still writes a latent row and an
            # index key where it is parked, made from a stream whose
            # attention read nothing: before its first step that is the
            # row its first step writes again; after its last it is the
            # row past the request's, as the engine's chunk parks it
            # (never a row the request holds).
            j = min(max(j, 0), len(answer) - 1)
            tokens[slot, 0] = answer[min(j, len(answer) - 2)]
            lengths[slot] = len(prompt) + j
        logits, cache, _, seen = loop.decode_step_whole(
            engine.params, cache, put(tokens), put(lengths), put(live))
        out = np.asarray(logits, np.float32)
        e, m = np.asarray(seen["experts"]), _packed(seen["rows"])
        about = {k: np.asarray(seen[k]) for k in
                 ("index_q", "index_w", "router_in", "gates")}
        for i, (slot, _, _) in enumerate(asked):
            if live[slot]:
                got[i].append(out[slot])
                experts[i][:, lengths[slot]] = e[:, slot, 0]
                rows[i][:, lengths[slot]] = m[:, slot, 0]
                own["window_wrong"] += _window_wrong(
                    seen, (slot, 0), lengths[slot], window)
                mine = stepped[i]
                mine["pos"].append(int(lengths[slot]))
                mine["q"].append(about["index_q"][:, slot])
                mine["w"].append(about["index_w"][:, slot])
                mine["router_in"].append(about["router_in"][:, slot, 0])
                mine["gates"].append(about["gates"][:, slot, 0])
                mine["experts"].append(e[:, slot, 0])
    router = engine.params["moe"]["router"]
    for (slot, _, _), mine, masks in zip(asked, stepped, rows):
        if not mine["pos"]:
            continue
        wrong, selected = _own_selection_differs(
            cache["ik"][:, slot], np.stack(mine["q"]), np.stack(mine["w"]),
            np.asarray(mine["pos"], np.int32), masks[:, mine["pos"]], topk)
        own["select_differs"] += wrong
        own["select_rows"] += selected
        own["gates_rel_l2"] = max(own["gates_rel_l2"], _own_gates_rel_l2(
            router, config, np.stack(mine["router_in"]),
            np.stack(mine["experts"]), np.stack(mine["gates"])))
    return ([(np.asarray(g), e, m) for g, e, m in zip(got, experts, rows)],
            cache, own)


def _against_reference(reference, params, config, prompt, answer, got,
                       experts, masks):
    """One replayed request against the reference, the system's experts
    and rows followed: -> (rel L2 a replayed row, the engine's tokens'
    margins, the routing report and the selection's over the rows fed)."""
    tokens = np.asarray([list(prompt) + list(answer)], np.int32)
    rows = [(0, len(prompt) - 1 + j) for j in range(len(answer))]

    def followed(layer, a, b):
        return np.unpackbits(masks[layer, a:b], axis=-1).astype(bool)

    ref, routing, selection = reference.followed_logits_at(
        params, tokens, rows, config, experts[:, None], [followed])
    ref = np.asarray(ref)
    common.require(np.all(np.isfinite(ref)), "reference logits not finite")
    fed = len(prompt) + len(answer) - 1         # rows the replay fed
    errs = np.array([common.rel_l2(g, r) for g, r in zip(got, ref)])
    margins = np.array([(row.max() - row[tok]) / (row.max() - row.min())
                        for row, tok in zip(ref, answer)])
    return (errs, margins, {k: v[:, 0, :fed] for k, v in routing.items()},
            {k: v[:, 0, :fed] for k, v in selection.items()})


def warm_and_check(handle, engine, params, config, cfg, seed: int,
                   reference) -> dict:
    """Warm every program and hold the engine to ``reference`` under
    the limits of this file's header."""
    import jax

    eng = config["driver_args"]["engine"]
    prompts, again = serve_sparse_hybrid._check_prompts(
        config, cfg.vocab_size, seed)
    clock = [time.perf_counter()]
    serve_sparse_hybrid._dirty(handle, eng, cfg.vocab_size, seed)
    answers = serve_hybrid._ask(handle, prompts, CHECK_TOKENS)
    answer_again = serve_hybrid._ask(handle, [again], REUSE_TOKENS)[0]
    clock.append(time.perf_counter())
    cache = jax.jit(lambda: jax.tree.map(
        lambda a: a + 1,
        cfg.model.init_kv_cache(cfg, len(prompts), eng["max_len"])))()
    replayed, cache, own = _replay(
        engine, cache, [(i, p, a) for i, (p, a)
                        in enumerate(zip(prompts, answers))], eng, config)
    (reuse,), cache, own_again = _replay(
        engine, cache, [(REUSE_SLOT, again, answer_again)], eng, config)
    own = {k: (max if k == "gates_rel_l2" else int.__add__)(v, own_again[k])
           for k, v in own.items()}
    # The reference's float32 blocks want the room the check's cache
    # took; the device's peak so far is the engine's and the replay's.
    del cache
    replay_peak = (jax.local_devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    clock.append(time.perf_counter())
    compared = [_against_reference(reference, params, config, p, a, *r)
                for p, a, r in zip(prompts, answers, replayed)]
    reuse_errs, reuse_margins, _, _ = _against_reference(
        reference, params, config, again, answer_again, *reuse)
    clock.append(time.perf_counter())

    topk = config["index_topk"]
    routed = sum(c[2]["differs"].size for c in compared)
    route_differs = sum(int(c[2]["differs"].sum()) for c in compared)
    route_excess = max(float(c[2]["excess"].max()) for c in compared)
    # Rows in the chosen sets of the queries that select.
    selecting = sum(int((np.arange(c[3]["excess"].shape[1]) >= topk).sum())
                    * c[3]["excess"].shape[0] for c in compared)
    select_differs = sum(int(c[3]["differs"].sum()) for c in compared)
    select_excess = max(float(c[3]["excess"].max()) for c in compared)
    share_route = route_differs / max(routed, 1)
    share_select = select_differs / max(selecting * topk, 1)
    errs = np.array([c[0] for c in compared])           # [prompts, tokens]
    margins = np.concatenate([c[1] for c in compared] + [reuse_margins])
    got = np.array([r[0] for r in replayed])
    strict = float((margins <= TOL_TOKEN_MARGIN).mean())
    share_own = own["select_differs"] / max(own["select_rows"], 1)
    readings = {
        "prompt_tokens": [len(p) for p in prompts],
        "route_excess_max": route_excess,
        "route_choices_differ_share": share_route,
        "select_excess_max": select_excess,
        "select_rows_differ_share": share_select,
        "select_queries_differ_share": sum(
            int((c[3]["differs"] > 0).sum()) for c in compared)
            / max(selecting, 1),
        "own_select_rows_differ_share": share_own,
        "own_gates_rel_l2_max": own["gates_rel_l2"],
        "window_queries_wrong": own["window_wrong"],
        "prefill_rel_l2": float(errs[:, 0].max()),
        "step_rel_l2_max": float(errs[:, 1:].max()),
        "engine_tokens": int(margins.size),
        "token_margin_max": float(margins.max()),
        "token_margin_strict_share": strict,
        "argmax_agree": float((margins == 0.0).mean()),
        # Of the engine's tokens, those that the replayed programs' own
        # logits put first.
        "replay_agree": float((got.argmax(axis=-1)
                               == np.asarray(answers)).mean()),
        "reuse_prompt_tokens": len(again),
        "reuse_rel_l2_max": float(reuse_errs.max()),
        "memory_peak_bytes_before_reference": replay_peak,
        # Of set-up: the engine's answers (its programs' compiles among
        # them), the replay, the reference.
        "seconds_asked_replayed_referred": [
            round(b - a, 1) for a, b in zip(clock, clock[1:])]}
    worst = max(errs.max(), reuse_errs.max())
    # What the programs say of themselves first: those readings are
    # exact, and name what is wrong where the stream only shows that
    # something is.
    for ok, what in (
            (share_own <= TOL_OWN_SELECT_DIFFER,
             f"{share_own:.5f} of the rows the decode step selected "
             f"({own['select_differs']} of {own['select_rows']}) are not "
             f"the float32 top {topk} of its own queries, weights and keys"),
            (own["gates_rel_l2"] <= TOL_GATES_REL_L2,
             f"the decode step's gates lie {own['gates_rel_l2']:.2e} (rel "
             f"L2) off the float32 router on the stream the step read"),
            (own["window_wrong"] == 0,
             f"{own['window_wrong']} queries of the sliding layers attended "
             f"to other rows than the {config['sliding_window_size']} of "
             f"the published window"),
            (route_excess <= TOL_ROUTE_EXCESS,
             f"a chosen expert lies {route_excess:.4f} of the row's score "
             f"spread under the reference's boundary ({route_differs} of "
             f"{routed} (row, layer) choices differ)"),
            (share_route <= TOL_ROUTE_DIFFER,
             f"{share_route:.3f} of the (row, layer) expert choices differ "
             f"from the reference's"),
            (select_excess <= TOL_SELECT_EXCESS,
             f"a selected row lies {select_excess:.4f} of the score spread "
             f"under the reference's boundary ({select_differs} of "
             f"{selecting * topk} selected rows differ)"),
            (share_select <= TOL_SELECT_DIFFER,
             f"{share_select:.4f} of the selected rows differ from the "
             f"reference's"),
            (np.all(np.isfinite(got)) and worst <= TOL_LOGITS_REL_L2,
             f"logits off the reference, the system's experts and rows "
             f"followed: rel L2 {errs[:, 0].tolist()} at the prompts' ends "
             f"(the tick's prefill, in chunks), at worst "
             f"{errs[:, 1:].max(axis=1).tolist()} a row of the decode "
             f"step's, {reuse_errs.max():.4f} for the short request that "
             f"took a slot next"),
            (strict >= TOL_TOKENS_STRICT_SHARE,
             f"only {strict:.3f} of the engine's {margins.size} tokens lie "
             f"within {TOL_TOKEN_MARGIN} of the logit spread under the "
             f"reference's best (the worst {margins.max():.4f})")):
        if not ok:
            refused = common.Incorrect(what)
            refused.readings = readings     # for `degraded_dots3.py`
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
