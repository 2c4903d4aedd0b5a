"""Passes over the stack that a decoded token ran, over the window:
Δ``loop_passes`` / Δ``chunk_steps_retired`` of ``engine.stats()``. The
decode step counts the passes it ran (``loop_passes``: 4 at the
published ``total_ut_steps``) and the counter rides the chunk's fetch;
``chunk_steps_retired`` counts the steps of the chunks fetched, at the
same place. 4.00 while every token runs every pass; a program that ran
fewer (an early exit, a pass left out) reads under it. A program
without the counter reads nothing."""

from benchmark.harness import counters

COUNTED = ("loop_passes", "loop_layer_steps", "decode_attn_rows_streamed",
           "decode_steps")


def per_step(run, delta=counters.delta):
    """{counter: its growth a decode step} of `COUNTED` over the window
    (``delta``: over another stretch), or None where the program lacks
    one or no step was fetched."""
    steps = delta(run, "chunk_steps_retired")
    grown = {name: delta(run, name) for name in COUNTED}
    if not steps or any(v is None for v in grown.values()):
        return None
    return {name: v / steps for name, v in grown.items()}


def read(run):
    counted = per_step(run)
    return None if counted is None else counted["loop_passes"]
