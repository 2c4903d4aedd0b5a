"""Arithmetic on request records: percentiles, TTFT, TPOT, rates."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the closest ranks (numpy's default), of a non-empty sequence."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ttft_ms(rec: dict) -> float:
    """First streamed token received minus the time the request was
    DUE (not the time it was sent: a late generator or a stalled server
    both make the user wait)."""
    return (rec["first"] - rec["due"]) * 1e3


def tpot_ms(rec: dict):
    """(last token - first token) / (tokens - 1), per request; None for
    a one-token answer. Per request and not per gap: the engine
    delivers tokens in bursts of ``decode_chunk``."""
    if rec["n_got"] < 2:
        return None
    return (rec["last"] - rec["first"]) / (rec["n_got"] - 1) * 1e3


def late_ms(rec: dict) -> float:
    """How late the generator sent the request."""
    return (rec["sent"] - rec["due"]) * 1e3


def timed(run: dict) -> list:
    """The records that count: due inside the window."""
    return [r for r in run["requests"] if r["timed"]]


def ttft_percentile(run: dict, q: float) -> float:
    return percentile([ttft_ms(r) for r in timed(run)], q)


def tpot_percentile(run: dict, q: float) -> float:
    per_request = [tpot_ms(r) for r in timed(run)]
    return percentile([t for t in per_request if t is not None], q)


def tokens_in_window(run: dict) -> int:
    """Output tokens received inside [0, window_s), from every request
    (lead-in requests too: the window counts what the server delivered
    in it)."""
    w = run["window_s"]
    return sum(1 for r in run["requests"] for t in r["token_times"]
               if 0.0 <= t < w)


def observations(run: dict) -> dict:
    """Counts printed on an earlier line of the run, judged by nothing.
    No metric is repeated here: each comes from its reader alone. The
    95th and 99th percentiles are no metrics (too few requests lie
    beyond them in one window) and are only printed."""
    if "steps" in run:
        s = sorted(run["steps"])
        return {"steps": len(s), "step_s_min": s[0],
                "step_s_p50": percentile(s, 50), "step_s_max": s[-1],
                "losses": run["losses"][:4] + run["losses"][-1:]}
    rs = timed(run)
    if not rs:
        return {"timed": 0}
    end = run["counters"]["end"]
    return {"timed": len(rs), "sent_in_all": len(run["requests"]),
            "ttft_ms_beyond": {q: ttft_percentile(run, q) for q in (95, 99)},
            "tpot_ms_beyond": {q: tpot_percentile(run, q) for q in (95, 99)},
            "tokens_in_window": tokens_in_window(run),
            "waiting_at_end": end["waiting"], "active_at_end": end["active"],
            "window_s": run["window_s"]}


def counter_delta(run: dict, key: str) -> float:
    c = run["counters"]
    return c["end"][key] - c["start"][key]
