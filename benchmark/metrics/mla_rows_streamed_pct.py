"""What the latent decode-attention kernel fetches for what it is asked
to read, over the window: 100 x Δ``mla_decode_rows_streamed`` /
Δ``mla_decode_rows`` of ``engine.stats()``. Both are one layer's counts
a step, over ALL slots: ``mla_decode_rows`` is Σ (lengths + 1), the
rows a call is asked to read (an idle slot is parked on a row and
counts), ``mla_decode_rows_streamed`` the rows of the blocks that begin
under those lengths, at the block the kernel itself derives from the
cache's shape. 100 is a kernel that moves no row it was not asked for;
what is over is the rounding of each slot's rows up to whole blocks
(half a block a slot on average). A program without the counter (one
whose kernel reads by another rule) reads nothing."""

from benchmark.harness import counters


def read(run):
    streamed = counters.delta(run, "mla_decode_rows_streamed")
    rows = counters.delta(run, "mla_decode_rows")
    if streamed is None or not rows:
        return None
    return streamed / rows * 100.0
