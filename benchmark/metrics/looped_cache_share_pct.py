"""The cache rows' share of the bytes a looped decode step must read,
over the window: 100 x rows / (block applications x a block's bytes +
the head + rows), rows = ``decode_attn_rows_streamed`` a step x
``loop_layer_steps`` entries x 8,192 B (`opcount_looped.step_cost`).
What is left is the stack's weights read once a PASS: the share says
how far a step is from the weights' floor, and it grows with the slots'
lengths. A program without the counters reads nothing."""

from benchmark.harness import opcount_looped
from benchmark.metrics import looped_passes_per_token as _passes


def read(run):
    counted = _passes.per_step(run)
    if counted is None:
        return None
    cost = opcount_looped.step_cost(
        run["config"], counted["loop_layer_steps"],
        counted["decode_attn_rows_streamed"])
    return cost["rows_bytes"] / cost["bytes"] * 100.0
