"""Device milliseconds of one decode step OUTSIDE its named parts:
`decode_step_ms` less the selection (`dsa_select_ms_per_step`), the
full layers' attention (`dsa_decode_attn_ms_per_step`), the sliding
layers' (`swa_decode_attn_ms_per_step`) and the grouped expert products
(`moe_ms_per_step`: ``ragged-dot-none``, the chip compiler's grouped
matmul). It is where the projections of both attention geometries, the
indexer's, the gates, the router over all 256 experts, the sort around
the grouped product, the shared expert, the dense layer and the head
are: XLA fusions, which carry no name of their own in a trace (PERF.md
section 7). Only where the step has the selection kernel: elsewhere it
reads nothing."""

from benchmark.metrics import decode_step_ms as _step
from benchmark.metrics import dsa_decode_attn_ms_per_step as _dsa
from benchmark.metrics import dsa_select_ms_per_step as _select
from benchmark.metrics import moe_ms_per_step as _moe
from benchmark.metrics import swa_decode_attn_ms_per_step as _swa


def read(run):
    step, select = _step.read(run), _select.read(run)
    if step is None or select is None:
        return None
    return (step - select - (_dsa.read(run) or 0.0)
            - (_swa.read(run) or 0.0) - (_moe.read(run) or 0.0))
