"""Device-resident multi-token decode loop.

The pre-subsystem engine (serve/llm.py round 5) fetched every generated
token to the host, so the host round-trip — not the model — set the
decode rate. This module keeps the decode loop ON DEVICE: one jitted
``lax.scan`` advances every slot ``chunk`` steps per dispatch, carrying

- per-slot cache write positions (``lengths``),
- per-slot remaining token budgets,
- per-slot EOS ids (-1 = none), and
- an on-device done mask (EOS seen / budget exhausted / row cap hit),

so the host syncs AT MOST ONCE PER ``chunk`` TOKENS and never needs to
inspect a token mid-chunk to decide termination. Slots that finish
mid-chunk freeze: their length/budget stop advancing (subsequent writes
land on the already-dead next-free row and are discarded with the slot)
and ``n_valid`` reports how many of the chunk's tokens were live, so the
EOS overshoot the old engine paid (up to K-1 wasted host tokens per
request) is discarded exactly.

The model comes through ONE seam: ``cfg.model`` is the configuration's
own model module (``models/llama.py``, ``models/glm_moe_lite.py``), and
this file asks it for three functions: ``forward_with_cache(params,
tokens, row, cache_index, cfg)`` (a prefill bucket with the bucket's
logits: what checks and the verify program read),
``forward_last_with_cache(params, tokens, row, cache_index, last,
cfg)`` (the same layers, the head for row ``last`` alone: the tick's
prefill, of which the tick's program hands back the argmax) and
``decode_step_with_cache(params, tokens, cache, lengths, cfg,
live=None)`` (the step: all slots in one batch, each slot's new row a
layer written at its own length, the family's decode-attention kernel
on TPU and its jnp reference off it, so CPU tests cover the identical
loop; ``live`` is the chunk's mask of the slots whose tokens anyone
reads). A module may offer a fourth,
``forward_last_rows_with_cache(params, tokens, rows, cache_index, last,
cfg)``: ``forward_last_with_cache`` for rows that are prompts APART,
``cache_index`` and ``last`` one a row; where it does, the tick may
prefill two waiting prompts in one program (``prefill_pair``; the rule
is the engine's, ``core._partner``), and where it does not, nothing of
this file or the tick differs. Each may
return, after its two results, a dict of scalar counters that the
tick's programs hand on (summed over a chunk's steps, or, for the names
in the family's optional ``COUNTER_MAXES``, their largest: a routed
family's expert counters ride the fetches the tick already makes) and
then a dict of what a check against a reference reads (each token's
chosen experts), which only the check's programs return. All this file
assumes of a cache is a dict of arrays with the slot axis second; a
family that keeps per-slot STATE among them (``SLOT_STATE_KEYS``: a
linear-attention layer's, which every step overwrites) steps only the
slots of the live mask. The
engine's cache is ONE buffer: the chunk, verify, install and
tick-prefill programs take it donated and return it aliased, so the
caller must rebind its reference to the result (``self.cache = ...``)
and must rebuild the cache if a donated call raises — the old array is
deleted either way. The ``params`` every program takes are the tree
`serving_params` hands back: the family's own layout of its published
weights where its module has one, else the tree as it was drawn.

Speculative verification (``spec_window`` > 1) adds a SECOND chunk
program, ``verify_chunk``: each scan iteration forwards a ``[B, W]``
candidate window (last committed token + W-1 host-drafted tokens) in
one batched call, computes the greedy accept mask ON DEVICE (longest
prefix where draft == argmax), applies the same EOS/budget/row-cap
stops per WINDOW POSITION, and emits between 1 and W tokens per live
slot per iteration — still one host sync per chunk. The engine's KV
cache must be allocated with ``scratch_rows`` extra rows past
``max_len``: rejected-draft and parked writes land in that scratch
strip instead of clamping backwards onto valid rows (XLA clamps
out-of-range dynamic_update_slice starts, which would otherwise let a
W-row window overwrite resident prefix KV).
"""

from __future__ import annotations


def serving_params(cfg, params):
    """``params`` as ``cfg.model.init_params`` draws them (the published
    form, which a checkpoint arrives in and a reference reads) -> the
    tree a `DecodeLoop`'s programs read: what the module's optional
    ``serving_params(params, cfg)`` makes of it, once (`kimi_linear`:
    the stacks that read a KDA layer's normed stream as one, in the
    layout the product reads where it lies); the SAME tree where the
    module has no such function."""
    relayout = getattr(cfg.model, "serving_params", None)
    return params if relayout is None else relayout(params, cfg)


# What each optional mechanism needs of a family, said ONCE: a family's
# module lists the options it offers in ``ENGINE_OFFERS``
# (`models/llama.py` alone does) and writes no reason of its own.
_ROWS_ALONE = (
    "verify_chunk, export_page / install_page and kv_fleet.pack_page are "
    "written for a {k, v} cache of rows with no per-slot state beside "
    "them (llama's)")
ENGINE_OPTIONS = {
    "quantize": "models/quant.py quantizes llama's weight tree only",
    "spec_draft_len": _ROWS_ALONE, "role": _ROWS_ALONE,
    "kv_fleet": _ROWS_ALONE}


def check_offers(model, **asked: bool) -> None:
    """``asked``: engine option -> whether this engine was asked for it.
    An option its family does not offer is a ``ValueError`` that names
    both and says what the mechanism needs; so is a name in the
    family's ``ENGINE_OFFERS`` that is no option of the engine."""
    offers = getattr(model, "ENGINE_OFFERS", ())
    stale = [option for option in offers if option not in ENGINE_OPTIONS]
    if stale:
        raise ValueError(
            f"{model.__name__}.ENGINE_OFFERS names {stale[0]!r}, which is no "
            f"option of this engine (it knows {sorted(ENGINE_OPTIONS)})")
    for option, on in asked.items():
        if on and option not in offers:
            raise ValueError(
                f"{model.__name__} cannot serve with {option} yet: "
                f"{ENGINE_OPTIONS[option]}")


class DecodeLoop:
    """Compiled prefill + chunked-decode programs for one model/cache.

    Exactly one decode program is compiled per engine (the chunk scan;
    ``chunk=1`` is the degenerate per-token case), plus one prefill
    program per prompt bucket. With ``spec_window`` > 1 the speculative
    verify program is compiled alongside (the plain program remains —
    ticks with zero drafted tokens dispatch it unchanged).
    """

    def __init__(self, cfg, *, max_len: int, chunk: int = 8,
                 spec_window: int = 1, spec_chunk: int = 0,
                 prefill_budget: int = 0, kv_page: int = 0):
        import jax

        self.cfg = cfg
        self.max_len = max_len
        self.chunk = max(1, int(chunk))
        self.spec_window = max(1, int(spec_window))
        self.prefill_budget = max(0, int(prefill_budget))
        self.kv_page = max(0, int(kv_page))
        # Verify iterations per dispatch. The default keeps the token
        # POSITIONS scanned per dispatch comparable to the plain chunk
        # (chunk // window): each verify iteration forwards a whole
        # window, so running `chunk` of them would multiply per-dispatch
        # compute by W — and every mid-chunk divergence would strand the
        # remaining iterations draft-free. Fewer, wider dispatches also
        # put the host back in the loop sooner with FRESH drafts. Raise
        # it explicitly when the host sync dominates.
        self.spec_chunk = (max(1, int(spec_chunk)) if spec_chunk
                           else max(1, self.chunk // self.spec_window))
        self._jax = jax
        self._build()
        if self.spec_window > 1:
            self._build_verify()
        if self.kv_page:
            self._build_kv_transfer()
        self._witness()

    def _witness(self) -> None:
        """Under RTPU_DEBUG_JAX=1, wrap every jit entry point in the
        recompile witness with its DECLARED steady-state program
        budget: one chunk program (+ one verify program when built),
        one prefill program per prompt bucket (and, where the family
        has the paired one, at most one of those a bucket). Off,
        wrap_jit returns
        the functions untouched — zero overhead."""
        from ray_tpu.devtools import jax_debug

        if not jax_debug.enabled():
            return
        self.prefill = jax_debug.wrap_jit(
            self.prefill, "decode_loop.prefill",
            budget=self.prefill_budget or None)
        self.prefill_inplace = jax_debug.wrap_jit(
            self.prefill_inplace, "decode_loop.prefill_inplace",
            budget=self.prefill_budget or None)
        if self.prefill_pair is not None:
            # At most one paired program a bucket, as of single ones.
            self.prefill_pair = jax_debug.wrap_jit(
                self.prefill_pair, "decode_loop.prefill_pair",
                budget=self.prefill_budget or None)
        self.decode_chunk = jax_debug.wrap_jit(
            self.decode_chunk, "decode_loop.decode_chunk", budget=1)
        self.decode_step = jax_debug.wrap_jit(
            self.decode_step, "decode_loop.decode_step", budget=1)
        self.roster_merge = jax_debug.wrap_jit(
            self.roster_merge, "decode_loop.roster_merge", budget=1)
        self.roster_join = jax_debug.wrap_jit(
            self.roster_join, "decode_loop.roster_join", budget=1)
        if self.spec_window > 1:
            self.verify_chunk = jax_debug.wrap_jit(
                self.verify_chunk, "decode_loop.verify_chunk", budget=1)
        if self.kv_page:
            self.export_page = jax_debug.wrap_jit(
                self.export_page, "decode_loop.export_page", budget=1)
            self.install_page = jax_debug.wrap_jit(
                self.install_page, "decode_loop.install_page", budget=1)

    def program_counts(self) -> dict:
        """{program name: distinct compiled signatures} when the
        RTPU_DEBUG_JAX witness wrapped this loop; {} otherwise."""
        from ray_tpu.devtools.jax_debug import JitWitness

        out = {}
        for name in ("prefill", "prefill_inplace", "prefill_pair",
                     "decode_chunk", "decode_step", "roster_merge",
                     "roster_join", "verify_chunk", "export_page",
                     "install_page"):
            fn = getattr(self, name, None)
            if isinstance(fn, JitWitness):
                out[name] = fn.program_count
        return out

    @property
    def scratch_rows(self) -> int:
        """Extra KV rows past ``max_len`` the engine must allocate so
        verify windows never clamp onto valid rows (0 when the verify
        program is not built)."""
        return self.spec_window if self.spec_window > 1 else 0

    # ------------------------------------------------------------ compile

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        model = cfg.model      # the configuration's own model module
        max_len = self.max_len

        def in_slot(forward, cache, slot, *args):
            """``forward`` on ``slot``'s rows of the cache, written
            back: -> (logits, cache, *whatever else it returns)."""
            row = {k: jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=1)
                   for k, v in cache.items()}
            logits, new_row, *more = forward(row, *args)
            # slot is bounded by contract: the scheduler only admits
            # into slots < max_batch (the cache's axis-1 extent), so
            # the start can never hit XLA's silent clamp.
            cache = {k: jax.lax.dynamic_update_slice_in_dim(  # rtpu-lint: disable=unclamped-dynamic-update-slice
                cache[k], new_row[k], slot, axis=1) for k in cache}
            return (logits, cache, *more)

        def prefill(params, cache, tokens, slot, cache_index):
            """tokens [1, Pb] written into ``slot``'s rows at
            [cache_index, cache_index+Pb) — cache_index > 0 is the
            prefix-cache path (only the uncached suffix re-prefills)."""
            return in_slot(
                lambda row: model.forward_with_cache(
                    params, tokens, row, cache_index, cfg), cache, slot)

        def prefill_last(params, cache, tokens, slot, cache_index, last):
            """The same layers, the head for row ``last`` alone (the
            prompt's last real token): logits [1, V]. A bucket of them
            is nothing the tick reads, and too large to fetch."""
            return in_slot(
                lambda row: model.forward_last_with_cache(
                    params, tokens, row, cache_index, last, cfg),
                cache, slot)

        def tick_prefill(*args):
            """``prefill_last`` as the tick wants it: the row's argmax
            (int32 [1]: the first index of the largest, as np.argmax
            has it), the cache, and of what the family returns besides
            the counters alone."""
            logits, cache, *counters = prefill_last(*args)[:3]
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache,
                    *counters)

        # ``prefill`` and ``prefill_last`` are functional: the caller's
        # cache lives on and a new one comes back, which is what a
        # check that prefills the same rows twice wants (compiled only
        # if one calls them). ``prefill_inplace`` is the tick's: the
        # cache is donated, the slot's rows are rewritten where they
        # lie, and 4 bytes and the counters are all there is to fetch.
        self.prefill = jax.jit(prefill)
        self.prefill_last = jax.jit(prefill_last)
        # ``prefill_last`` with the cache DONATED, for a check on an
        # idle engine's own cache where a second one does not fit: all
        # that the functional program returns, the caller's cache
        # rewritten where it lies (rebind it, as after the tick's).
        self.prefill_last_inplace = jax.jit(prefill_last,
                                            donate_argnums=(1,))
        # The name that device traces and their readers know the tick's
        # prefill by (`jit_prefill`).
        tick_prefill.__name__ = prefill.__name__
        self.prefill_inplace = jax.jit(tick_prefill, donate_argnums=(1,))

        rows_forward = getattr(model, "forward_last_rows_with_cache", None)
        maxes = getattr(model, "COUNTER_MAXES", ())

        def tick_prefill_pair(params, cache, tokens, slots, cache_index,
                              last):
            """The tick's prefill for TWO waiting prompts: tokens
            [2, Pb] (the shorter prompt padded to its partner's
            bucket), ``slots`` [2] (distinct by contract: two
            admissions never hold one slot), ``cache_index`` [2] and
            ``last`` [2] each row's own -> ((token a, token b), each
            int32 [1] as ``tick_prefill`` hands one back, the cache,
            the family's counters). The weights are streamed once for
            both."""
            row = {k: jnp.concatenate(
                [jax.lax.dynamic_slice_in_dim(v, slots[i], 1, axis=1)
                 for i in range(2)], axis=1) for k, v in cache.items()}
            logits, new_row, *counters = rows_forward(
                params, tokens, row, cache_index, last, cfg)[:3]
            for i in range(2):
                # Bounded by contract, as ``in_slot``'s start is.
                cache = {k: jax.lax.dynamic_update_slice_in_dim(  # rtpu-lint: disable=unclamped-dynamic-update-slice
                    cache[k], new_row[k][:, i:i + 1], slots[i], axis=1)
                    for k in cache}
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return ((token[:1], token[1:]), cache, *counters)

        # Only where the configuration's model module offers the
        # forward for rows that are prompts apart (``models/llama.py``);
        # None for every other family, and the tick then prefills one
        # prompt a program as it always did. Under the name the tick's
        # prefill has: to a trace's reader it IS one.
        self.prefill_pair = None
        if rows_forward is not None:
            tick_prefill_pair.__name__ = prefill.__name__
            self.prefill_pair = jax.jit(tick_prefill_pair,
                                        donate_argnums=(1,))

        def step(params, cache, tokens, lengths, live=None):
            """One decode step for every slot: tokens [B,1], lengths [B],
            ``live`` [B] bool (None: all). Batched by construction
            (``decode_step_with_cache``): each slot's one new row a
            layer is written at its own length and the rest of the
            cache is carried untouched. A slot that is not live (idle,
            frozen, or between two chunks of its prefill) is stepped
            all the same (static shapes), so the family is told: its
            rows need not be read, and a per-slot state
            (``SLOT_STATE_KEYS``) must not be stepped."""
            # ``live`` by position: a check's stand-in for the step
            # takes ``(params, *rest)``.
            logits, cache, *counters = model.decode_step_with_cache(
                params, tokens, cache, lengths, cfg, live)[:3]
            return (jnp.argmax(logits, axis=-1), cache, *counters)

        def decode_chunk(params, cache, tokens, lengths, remaining,
                         eos_ids, done):
            """``chunk`` greedy steps in ONE program.

            tokens [B,1] int32 (each slot's last token), lengths [B],
            remaining [B] (token budget), eos_ids [B] (-1 = none),
            done [B] bool (True = slot inactive / already finished).

            Returns (chunk_tokens [B, K], n_valid [B], next_tokens
            [B, 1], new_lengths [B], new_remaining [B], done [B],
            cache) and, last, the step's counters summed over the
            chunk where the family has any. chunk_tokens[b, j] for j >= n_valid[b] are frozen
            repeats of the slot's final token — discard them. The
            trailing carry (next_tokens/lengths/remaining/done) is the
            EXACT input state of the next chunk for an unchanged
            roster: the engine's multi-step tick feeds it straight back
            as device arrays (same shapes/dtypes — one program either
            way), enqueueing chunk N+1 before fetching chunk N's
            tokens so the host sync overlaps the next chunk's compute.
            """

            def body(carry, _):
                cache, tok, ln, rem, dn = carry
                nxt, cache, *counters = step(params, cache, tok, ln, ~dn)
                emit = jnp.where(dn, tok[:, 0], nxt).astype(jnp.int32)
                ln = jnp.where(dn, ln, ln + 1)
                rem = jnp.where(dn, rem, rem - 1)
                # Same termination rules the scheduler applies host-side
                # (scheduler.is_finished): budget exhausted, per-slot
                # EOS, or the slot's cache rows are full.
                fin = ((emit == eos_ids) | (rem <= 0)
                       | (ln + 1 >= max_len))
                new_dn = dn | fin
                return ((cache, emit[:, None], ln, rem, new_dn),
                        (emit, dn, *counters))

            (cache, tok, lengths, remaining, done), \
                (toks, was_done, *counters) = jax.lax.scan(
                    body, (cache, tokens, lengths, remaining, done), None,
                    length=self.chunk)
            n_valid = self.chunk - jnp.sum(was_done.astype(jnp.int32),
                                           axis=0)
            # Over the chunk's steps: summed, but for the names of which
            # the family wants the largest (``COUNTER_MAXES``).
            counters = [{name: (jnp.max if name in maxes else jnp.sum)(
                per_step, axis=0) for name, per_step in c.items()}
                for c in counters]
            return (toks.T, n_valid, tok, lengths, remaining, done, cache,
                    *counters)

        # Donated, like every program the engine binds back to its one
        # cache (this module's header).
        self.decode_chunk = jax.jit(decode_chunk, donate_argnums=(1,))
        # Exposed for the equivalence tests: the same single step the
        # chunk scans over, jitted standalone (functional); and the
        # family's step whole, its logits and all it returns besides,
        # for a check against a reference (compiled only if called).
        self.decode_step = jax.jit(step)

        def step_whole(params, cache, tokens, lengths, live=None):
            return model.decode_step_with_cache(params, tokens, cache,
                                                lengths, cfg, live)

        self.decode_step_whole = jax.jit(step_whole)
        # The same with the cache donated (``prefill_last_inplace``).
        self.decode_step_whole_inplace = jax.jit(step_whole,
                                                 donate_argnums=(1,))

        def roster_merge(keep, carried, fresh):
            """A chunk's five inputs (tokens, lengths, remaining,
            eos_ids, done) ACROSS a roster change: a slot of ``keep``
            [B] takes what the chunk in flight carries for it (its
            request is the one that chunk was dispatched with, so the
            device already holds its state, a freeze inside that chunk
            included); every other slot takes what the host wrote
            (``fresh``: a request activated since, or the parked row
            and ``done`` of a slot nobody holds). The engine never
            needs the chunk in flight on the host to dispatch the
            next."""
            return tuple(
                jnp.where(keep.reshape(keep.shape + (1,) * (c.ndim - 1)),
                          c, f) for c, f in zip(carried, fresh))

        def roster_join(tokens, lengths, remaining, eos_ids, done, slot,
                        token):
            """A request joins the roster with a first token the host
            has not seen: ``token`` int32 [1], as the tick's prefill
            hands it back, goes into ``slot``'s row, and the slot is
            done from the start under the scan's own termination rules
            (the host wrote its length, budget AFTER this token, and
            EOS id)."""
            tok = token[0]
            fin = ((tok == eos_ids[slot]) | (remaining[slot] <= 0)
                   | (lengths[slot] + 1 >= max_len))
            return (tokens.at[slot, 0].set(tok), done.at[slot].set(fin))

        # Two programs of a few [B] selects; no cache, nothing donated.
        self.roster_merge = jax.jit(roster_merge)
        self.roster_join = jax.jit(roster_join)

    def _build_verify(self) -> None:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        model = cfg.model
        max_len = self.max_len
        W = self.spec_window      # window = 1 committed token + K drafts
        K = W - 1

        def verify_step(params, cache, tokens, lengths):
            """One W-token forward per slot: tokens [B, W], lengths [B]
            (per-slot write offset). Returns greedy targets [B, W] —
            targets[b, j] is the model's next token after the context
            plus tokens[b, :j+1]."""

            def one(cache_row, tok, idx):
                row = {k: v[:, None] for k, v in cache_row.items()}
                logits, new_row = model.forward_with_cache(
                    params, tok[None], row, idx, cfg)
                return logits[0], {k: v[:, 0]
                                   for k, v in new_row.items()}

            logits, new_cache = jax.vmap(
                one, in_axes=({"k": 1, "v": 1}, 0, 0),
                out_axes=(0, {"k": 1, "v": 1}))(cache, tokens, lengths)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache

        def verify_chunk(params, cache, tokens, drafts, ndraft, lengths,
                         remaining, eos_ids, done):
            """``spec_chunk`` speculative verify iterations in ONE program.

            tokens [B,1] (each slot's last committed token), drafts
            [B, spec_chunk, K] (host prompt-lookup proposals; iteration
            i consumes row i), ndraft [B] (valid drafted tokens per
            slot, consumed front-to-back), lengths/remaining/eos_ids/
            done as in ``decode_chunk``.

            Returns (emits [B, spec_chunk, W], counts [B, spec_chunk],
            new_lengths [B], done [B], cache): iteration i of slot b
            emitted ``emits[b, i, :counts[b, i]]`` — the accepted draft
            prefix plus the model's bonus/correction token, cut at the
            first EOS/budget/row-cap stop. Greedy-equivalence: emitted
            tokens are exactly what ``decode_chunk`` would emit, in
            order, for any draft content.
            """
            jj = jnp.arange(W)

            def body(carry, window_drafts):  # window_drafts [B, K]
                cache, tok, ln, rem, nd, dn = carry
                w = jnp.concatenate([tok, window_drafts], axis=1)
                # Done slots park their W-row window write entirely in
                # the scratch strip [max_len, max_len + W).
                idx = jnp.where(dn, max_len, ln)
                t, cache = verify_step(params, cache, w, idx)  # [B, W]
                nd_eff = jnp.clip(nd, 0, K)
                match = ((jnp.arange(K)[None, :] < nd_eff[:, None])
                         & (window_drafts == t[:, :K]))
                # acc = longest accepted draft prefix, in [0, K].
                acc = jnp.cumprod(match.astype(jnp.int32),
                                  axis=1).sum(axis=1)
                # Per-position stop conditions on the CANDIDATE emission
                # t_j — identical to decode_chunk's post-update checks:
                # after emitting position j, length is ln+j+1 and the
                # budget is rem-j-1.
                ln_j = ln[:, None] + jj[None, :] + 1
                rem_j = rem[:, None] - jj[None, :] - 1
                stop = ((t == eos_ids[:, None]) | (rem_j <= 0)
                        | (ln_j + 1 >= max_len))
                # Position j emits iff every earlier position emitted
                # without stopping and j is within the accepted prefix
                # (+1 for the bonus token).
                elig = jj[None, :] <= acc[:, None]
                prev_ok = jnp.concatenate(
                    [jnp.ones((t.shape[0], 1), bool), ~stop[:, :-1]],
                    axis=1)
                alive = ((~dn)[:, None]
                         & (jnp.cumprod((elig & prev_ok).astype(jnp.int32),
                                        axis=1) > 0))
                m = alive.sum(axis=1).astype(jnp.int32)       # [B]
                stopped = jnp.any(alive & stop, axis=1)
                new_dn = dn | stopped
                last = jnp.take_along_axis(
                    t, jnp.maximum(m - 1, 0)[:, None], axis=1)
                new_tok = jnp.where((m > 0)[:, None], last, tok)
                ln = ln + m
                rem = rem - m
                # Drafts survive into the next window only after a FULL
                # window emission (all K drafts accepted, no stop): a
                # partial accept means the drafted continuation diverged
                # from the generation, so the rest of the buffer is dead.
                nd = jnp.where(~new_dn & (m == W), nd - K, 0)
                return (cache, new_tok, ln, rem, nd, new_dn), (t, m)

            (cache, _t, lengths, remaining, _nd, done), (toks, counts) = \
                jax.lax.scan(body, (cache, tokens, lengths, remaining,
                                    ndraft, done),
                             jnp.swapaxes(drafts, 0, 1),
                             length=self.spec_chunk)
            return (jnp.transpose(toks, (1, 0, 2)), counts.T, lengths,
                    done, cache)

        self.verify_chunk = jax.jit(verify_chunk, donate_argnums=(1,))

    def _build_kv_transfer(self) -> None:
        """KV-page export/install for disaggregated prefill/decode: the
        prefill engine slices one ``kv_page``-row page of a slot's KV
        out of the cache (ONE program, any page index — the host loops
        pages and fetches them in a single sync), the decode engine
        installs received pages into its own cache at the same rows.
        Page size == the KV manager's block size, so a "page" here is
        exactly the block of the hash chain."""
        import jax
        import jax.numpy as jnp

        P = self.kv_page

        def export_page(cache, slot, start):
            """-> (k_page [L, KH, P, D], v_page) for rows
            [start, start+P) of ``slot`` of a {k, v} cache of
            [L, B, KH, S, D] (a family with another cache refuses the
            roles and the tier that need pages, at the engine)."""
            L, _B, KH, S, D = cache["k"].shape
            start = jnp.clip(start, 0, S - P)
            out = []
            for key in ("k", "v"):
                page = jax.lax.dynamic_slice(
                    cache[key], (0, slot, 0, start, 0),
                    (L, 1, KH, P, D))
                out.append(page[:, 0])
            return tuple(out)

        def install_page(cache, k_page, v_page, slot, start):
            """Write one exported page into this cache's ``slot`` at
            rows [start, start+P)."""
            S = cache["k"].shape[3]
            start = jnp.clip(start, 0, S - P)
            new = {}
            for key, page in (("k", k_page), ("v", v_page)):
                # slot is bounded by contract (scheduler admits into
                # slots < max_batch) and start is jnp.clip-ed above.
                new[key] = jax.lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
                    cache[key], page[:, None].astype(cache[key].dtype),
                    (0, slot, 0, start, 0))
            return new

        self.export_page = jax.jit(export_page)
        self.install_page = jax.jit(install_page, donate_argnums=(0,))

