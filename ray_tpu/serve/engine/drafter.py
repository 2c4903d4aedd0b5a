"""Prompt-lookup draft proposer + per-request adaptive draft control.

Speculative decoding (Leviathan et al. 2023) needs a cheap source of
candidate continuations; a separate draft model is a deployment burden
(two sets of weights, two compiles) and is useless on the tiny-cpu test
config. Prompt-lookup decoding (vLLM's ``[ngram]`` speculator / PLD)
is model-free: the longest n-gram that ends the current context
(``prompt_ids + generated``) is searched for an EARLIER occurrence in
the same context, and the tokens that followed that occurrence are
proposed as the draft. It bites exactly where serving traffic repeats
itself — code edits, RAG quotes, structured output, and the repetition
loops greedy decode itself falls into. The scan is bounded
(``lookback`` most recent tokens) and chronic misses back off through
the same controller as rejections, so non-repetitive contexts stop
paying even the lookup after a few ticks.

The drafter and the controller are host-side and jax-free
(unit-testable without a model); the device-side verification of these
drafts lives in ``decode_loop.DecodeLoop.verify_chunk``, and
``Speculation`` below is the engine's tick around it.

Adaptive draft length: drafting is speculative WORK — every drafted
token widens the verify window the device must compute. ``SpecControl``
tracks the per-request accept rate and resizes the request's draft
allowance multiplicatively (double on >= ``grow_rate`` acceptance,
halve below ``shrink_rate``, floor 0). A request whose drafts keep
getting rejected stops drafting entirely — once NO active request drafts, the
engine dispatches the plain (non-speculative) decode program, so an
adversarial workload pays nothing over speculation-off — and a
periodic probe re-tries a minimal draft in case the generation has
become repetitive since.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

from ray_tpu.util import flight_recorder as _flight


class PromptLookupDrafter:
    """Longest-suffix n-gram matcher over the request's own context.

    ``lookback`` bounds the scanned region (most recent tokens): the
    right-to-left scan is O(ngram sizes x lookback) of Python slice
    compares per tick, on the engine thread — unbounded context length
    must not grow it. Repetition that matters for drafting is local
    (the current loop), so a bounded window loses almost nothing.
    """

    def __init__(self, ngram_max: int = 3, ngram_min: int = 1,
                 lookback: int = 512):
        if ngram_min < 1 or ngram_max < ngram_min:
            raise ValueError("need 1 <= ngram_min <= ngram_max")
        if lookback < 2:
            raise ValueError("lookback must be >= 2")
        self.ngram_max = ngram_max
        self.ngram_min = ngram_min
        self.lookback = lookback

    def draft(self, context: Sequence[int], need: int) -> List[int]:
        """Up to ``need`` proposed continuation tokens for ``context``.

        Tries suffix n-grams longest-first; for the first n-gram with an
        earlier occurrence, returns the tokens that followed its MOST
        RECENT earlier occurrence (recent matches track the current
        repetition loop better than distant ones). Empty list = no
        match — the caller should skip speculation this tick.
        """
        ctx = list(context)[-self.lookback:]
        L = len(ctx)
        if need <= 0 or L < self.ngram_min + 1:
            return []
        for n in range(min(self.ngram_max, L - 1), self.ngram_min - 1, -1):
            pat = ctx[L - n:]
            # Most recent earlier occurrence: scan right-to-left.
            for start in range(L - n - 1, -1, -1):
                if ctx[start:start + n] == pat:
                    # Read the continuation from the match point; when
                    # it runs off the end of the context, keep reading
                    # from the draft itself (self-extension). A match
                    # near the tail — THE common case for a generation
                    # in a repetition loop, where the best match ends
                    # one period back — would otherwise yield only a
                    # period's worth of tokens; self-extension unrolls
                    # the loop to the full ``need``.
                    out: List[int] = []
                    j = start + n
                    for _ in range(need):
                        out.append(ctx[j] if j < L else out[j - L])
                        j += 1
                    return out
        return []


@dataclasses.dataclass
class SpecControl:
    """Per-request adaptive draft allowance (lives on EngineRequest).

    ``allowance`` is the TOTAL tokens this request may draft per decode
    tick (the device consumes them window by window); ``max_allowance``
    is the draft-buffer capacity (``spec_chunk * draft_len``).

    The controller is deliberately ASYMMETRIC: it doubles on a good
    tick but needs ``bad_limit`` CONSECUTIVE bad ticks to switch off.
    Repetitive generations are bursty — runs of perfect acceptance
    punctuated by one-window breaks — and a controller that halves to
    zero on every break spends most ticks in the (slower) plain path
    waiting out a probe cooldown; that fallback-thrash was measured at
    ~70% plain ticks on a workload with 0.8 in-run accept. Sustained
    rejection (a prompt whose lookups never verify) still drives the
    allowance to a hard 0 within ``bad_limit`` ticks, after which only
    a 1-token probe every ``probe_interval`` ticks remains. (The
    plain-program fallback is roster-wide: it kicks in on ticks where
    NO active request drafted — a backed-off request co-batched with a
    drafting neighbor still rides that tick's verify dispatch.)
    """
    allowance: int
    max_allowance: int
    grow_rate: float = 0.5
    shrink_rate: float = 0.25
    bad_limit: int = 4
    probe_interval: int = 8
    drafted: int = 0          # lifetime drafted tokens
    accepted: int = 0         # lifetime accepted draft tokens
    _bad_streak: int = 0
    _cooldown: int = 0

    def budget(self) -> int:
        """Draft allowance for this tick (0 = skip speculation). A
        request backed off to 0 probes a 1-token draft every
        ``probe_interval`` ticks so it can rejoin if the generation
        turns repetitive."""
        if self.allowance > 0:
            return self.allowance
        self._cooldown -= 1
        if self._cooldown <= 0:
            self._cooldown = self.probe_interval
            return 1
        return 0

    def miss(self) -> None:
        """A tick where lookup found nothing to draft. Misses count
        toward the same bad streak as rejections: a chronically
        non-repetitive context otherwise pays the lookup scan on the
        engine thread EVERY tick forever (back-off only triggered on
        dispatched-then-rejected drafts). Once the streak zeroes the
        allowance, the lookup itself runs only on the periodic probe."""
        self._bad_streak += 1
        if self._bad_streak >= self.bad_limit and self.allowance:
            self.allowance = 0
            self._cooldown = self.probe_interval

    def observe(self, drafted: int, accepted: int) -> None:
        """Fold one tick's verify outcome into the allowance."""
        if drafted <= 0:
            return
        self.drafted += drafted
        self.accepted += accepted
        rate = accepted / drafted
        if rate >= self.grow_rate:
            self._bad_streak = 0
            self.allowance = min(self.max_allowance,
                                 max(1, self.allowance) * 2)
        elif rate < self.shrink_rate:
            self._bad_streak += 1
            self.allowance = max(1, self.allowance // 2)
            if self._bad_streak >= self.bad_limit:
                self.allowance = 0
                self._cooldown = self.probe_interval
        else:
            self._bad_streak = 0


class Speculation:
    """The speculative tick of an engine built with ``spec_draft_len``
    > 0 (``engine.speculation``; None otherwise: no verify program, no
    cache padding): ``drafts`` proposes, ``tick`` verifies them on the
    device and commits exactly the accepted prefix (engine/README.md
    "Speculative decoding"). A verify chunk is a tick phase and an
    entry of the device's queue like any other chunk: beside the
    engine's device surface this uses its ``_tick`` and ``_devq``."""

    def __init__(self, engine, ngram_max: int, adaptive: bool):
        self.engine = engine
        self.draft_len = engine.spec_draft_len
        self.adaptive = bool(adaptive)
        self.drafter = PromptLookupDrafter(ngram_max=ngram_max)

    def _capacity(self) -> int:
        # A fully accepted window advances W = K+1 positions (K drafts
        # + the model's bonus token), so a continuation long enough to
        # keep all spec_chunk windows fed spans C*W - 1 positions (the
        # final window needs no bonus prediction).
        return self.engine.loop.spec_chunk * (self.draft_len + 1) - 1

    def control(self) -> SpecControl:
        """A new request's controller: the draft buffer's capacity at
        full acceptance is its ceiling (``drafts`` packs rows at that
        stride)."""
        return SpecControl(
            allowance=self.draft_len,
            max_allowance=(self._capacity() if self.adaptive
                           else self.draft_len))

    def drafts(self) -> Dict[int, List[int]]:
        """Prompt-lookup proposals for this tick, keyed by slot.
        Empty dict = nothing to verify (dispatch the plain program)."""
        eng, cap = self.engine, self._capacity()
        out: Dict[int, List[int]] = {}
        for req in eng.scheduler.active:
            # Drafting past the request's own stopping point is pure
            # waste: at most remaining-1 drafts can be emitted (the last
            # budgeted token is always the model's own), and the row cap
            # freezes the slot at max_len-1 rows.
            need = min(req.spec.budget(), cap, req.remaining() - 1,
                       eng.max_len - req.length - 2)
            if need <= 0:
                continue
            cont = self.drafter.draft(req.prompt_ids + req.generated,
                                      need)
            if cont:
                out[req.slot] = cont
            else:
                req.spec.miss()
        return out

    def tick(self, drafts: Dict[int, List[int]]) -> None:
        """One speculative verify chunk: K-token draft windows verified
        on device, accepted prefixes committed, rejected rows rolled
        back — still ONE host fetch."""
        eng = self.engine
        active = eng.scheduler.active
        C, K = eng.loop.spec_chunk, self.draft_len
        W = K + 1
        try:
            with eng._tick.phase("decode_dispatch", slots=len(active),
                                 spec=True):
                t0 = eng._tick.now
                tokens, lengths, remaining, eos_ids, done = \
                    eng._roster_arrays(active)
                draft_buf = np.zeros((eng.max_batch, C, K), np.int32)
                ndraft = np.zeros((eng.max_batch,), np.int32)
                for slot, cont in drafts.items():
                    # Window rows are packed at stride W = K+1, not K:
                    # the only path to row i is i FULLY accepted
                    # windows, and each full window advances K+1
                    # positions (K drafts + the model's bonus token).
                    # The continuation's prediction for a bonus position
                    # is skipped — the bonus comes from the model's own
                    # argmax, so drafting it would desynchronize every
                    # later row by one position per window (systematic
                    # row-1+ rejection on any repetition with period
                    # > 1).
                    packed = 0
                    for i in range(C):
                        row = cont[i * (K + 1):i * (K + 1) + K]
                        if not row:
                            break
                        draft_buf[slot, i, :len(row)] = row
                        packed += len(row)
                    ndraft[slot] = packed
                for req in active:
                    eng.kv.begin_speculation(
                        req.slot, min(C * W, eng.max_len - req.length))
                emits_d, counts_d, _len_d, _done_d, eng.cache = \
                    eng.loop.verify_chunk(
                        eng.params, eng.cache, eng._put(tokens),
                        eng._put(draft_buf), eng._put(ndraft),
                        eng._put(lengths), eng._put(remaining),
                        eng._put(eos_ids), eng._put(done))
                program = eng._devq.put("chunk", t0, counts_d.is_ready,
                                        slots=len(active), spec=True)
            with eng._tick.phase("decode_fetch",
                                 slots=len(active)) as attrs:
                # device_get returns host ndarrays: [B,C,W] + [B,C].
                emits, counts = eng._fetch((emits_d, counts_d))
                attrs["bytes"] = emits.nbytes + counts.nbytes
        except BaseException as e:  # noqa: BLE001 — fail all waiters
            eng._fail_roster(e)
            return
        now = eng._tick.now
        eng._devq.seen(program, now)
        live_steps = len(active) * C * W  # token-positions scanned
        delivered = 0
        accepted_total = 0
        with eng._tick.phase("decode_deliver", slots=len(active),
                             spec=True) as attrs:
            for req in list(active):
                s = req.slot
                n = int(counts[s].sum())
                # Commit the verified rows, roll back the reservation
                # for the rejected remainder BEFORE delivery:
                # _maybe_finish may release the slot, and a released
                # slot must carry no in-flight reservation into the
                # free pool.
                eng.kv.commit_speculation(s, n)
                delivered += n
                req_accepted = int(np.maximum(counts[s] - 1, 0).sum())
                accepted_total += req_accepted
                if req.trace_ctx is not None and n:
                    eng._span("engine.decode_chunk", t0, now, req,
                              {"tokens": n, "slot": s, "spec": True,
                               "spec_accepted": req_accepted,
                               "drafted": int(ndraft[s])})
                finished = False
                for i in range(C):
                    for j in range(int(counts[s, i])):
                        tok = int(emits[s, i, j])
                        req.length += 1
                        req.generated.append(tok)
                        if req.stream_queue is not None:
                            req.stream_queue.put(("token", tok))
                        if eng._maybe_finish(req, tok):
                            finished = True
                            break
                    if finished:
                        break
                if (self.adaptive and not finished
                        and s in drafts):
                    consumed, acc = self._outcome(
                        counts[s], int(ndraft[s]), K, W)
                    if consumed:
                        req.spec.observe(consumed, acc)
            attrs["tokens"] = delivered
            eng.metrics.record_chunk(delivered, live_steps, now - t0)
            eng.metrics.record_spec(int(ndraft.sum()), accepted_total)
            _flight.record("engine_tick", tok=delivered, act=len(active),
                           spec=True)

    @staticmethod
    def _outcome(counts_row, drafted: int, K: int, W: int):
        """(verified, accepted) draft tokens for one non-finished slot's
        chunk — the adaptive controller's signal. Only drafts the device
        actually checked count as verified: a request that finished
        mid-chunk never reaches here (its unchecked tail is neither
        accepted nor rejected), and windows after a divergence run
        draft-free, consuming nothing."""
        consumed = accepted = 0
        nd_rem = drafted
        for m in (int(x) for x in counts_row):
            if m == 0:
                break
            k_i = min(nd_rem, K)
            if m == W:  # full window: all K drafts accepted
                consumed += k_i
                accepted += k_i
                nd_rem -= k_i
            else:
                consumed += k_i
                accepted += m - 1
                nd_rem = 0
        return consumed, accepted
