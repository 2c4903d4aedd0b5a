"""Differences of ``engine.stats()`` counters that a program may lack.

`stats.counter_delta` is for counters every program has. A reader of a
counter that a PR added meets programs without it (the parent commit of
that PR, in the driver's comparison): it reads nothing there and returns
``None``, the rule of ``benchmark/README.md``.
"""

from __future__ import annotations


def delta(run: dict, key: str):
    """The counter's growth over the window, or None where a snapshot
    or the counter is missing."""
    c = run.get("counters") or {}
    if key not in c.get("start", ()) or key not in c.get("end", ()):
        return None
    return c["end"][key] - c["start"][key]


def mean_ms(run: dict, seconds_key: str, count_key: str):
    """Milliseconds per event over the window: Δ seconds / Δ count."""
    seconds, count = delta(run, seconds_key), delta(run, count_key)
    if seconds is None or not count:
        return None
    return seconds / count * 1e3
