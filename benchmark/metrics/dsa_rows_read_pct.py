"""Of the latent rows that live slots' decode queries could read in the
full layers, the share their attention READ: Δ``dsa_rows_attended`` /
Δ``dsa_rows_visible`` over the window (both summed over live slots,
full layers and steps). It says HOW the chosen rows are read: 100 where
the kernel reads a slot's rows whole under the selection's mask; near
``index_topk`` over the context's length (some 16 at 12k rows) where a
gather or a row-list kernel reads the chosen rows alone. A program
without the counters reads nothing."""

from benchmark.harness import counters


def read(run):
    attended = counters.delta(run, "dsa_rows_attended")
    visible = counters.delta(run, "dsa_rows_visible")
    if attended is None or not visible:
        return None
    return attended / visible * 100.0
