"""A looped decode step's share of its roofline: the least time the
chip could take to read what ONE step must (`opcount_looped.step_cost`:
a block's matrices once a block APPLICATION, so ``passes x`` the
stack; the head; the rows the attention kernel fetched of every
(pass, layer) entry) over the ``decode_chunk`` program's device seconds
a step in the traced stretch. The block applications and the rows are
COUNTED by the program over that stretch (``loop_layer_steps``,
``decode_attn_rows_streamed`` a ``chunk_steps_retired``). The weights
cannot stay in fast memory between passes and a token's pass u + 1
needs its pass u, so ``passes x`` is a true floor and the reading
cannot pass 100 (memory-bound: 2 operations a byte a live slot)."""

from benchmark.harness import opcount, opcount_looped
from benchmark.metrics import looped_passes_per_token as _passes
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta


def read(run):
    runs = (run.get("trace") or {}).get("program_s", {}).get("decode_chunk")
    counted = _passes.per_step(run, traced_delta)
    if not runs or counted is None or run["peaks"] is None:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    cost = opcount_looped.step_cost(
        run["config"], counted["loop_layer_steps"],
        counted["decode_attn_rows_streamed"], counted["decode_steps"])
    return (opcount.roofline_seconds(cost, run["peaks"])
            / (sum(runs) / (len(runs) * chunk)) * 100)
