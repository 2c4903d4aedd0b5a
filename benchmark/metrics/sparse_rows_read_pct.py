"""Of the rows live slots hold in the sparse layers, the share that
their decode queries' selections read: Δ``sparse_rows_selected`` /
Δ``sparse_rows_held`` over the window (both summed over live slots,
sparse layers and steps). 100 while every context is under
``dense_len``; 64 blocks of 64 rows over the context's length past it.
A program without the counters reads nothing."""

from benchmark.harness import counters


def read(run):
    selected = counters.delta(run, "sparse_rows_selected")
    held = counters.delta(run, "sparse_rows_held")
    if selected is None or not held:
        return None
    return selected / held * 100.0
