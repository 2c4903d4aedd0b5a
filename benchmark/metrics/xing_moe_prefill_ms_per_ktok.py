"""Device milliseconds a PREFILL's grouped expert products take per
1000 REAL prompt tokens in the four-stream routed family: self time on
device 0, in the traced stretch, of the products whose rows are NOT the
decode step's (`xing_moe_ms_per_step.product_seconds`: a bucket of 512
to 2,048 tokens x 4 experts a token is 2,048 to 8,192 rows, the step
128) over the real tokens of the chunks dispatched in the stretch
(``prefill_chunk_tokens``, `prefill_chunk_ms_per_ktok`'s divisor).
`moe_prefill_ms_per_ktok` takes every ``rtpu_grouped_*`` call for a
prefill's, which holds where the step stays on ``ragged_dot``; this
family's step calls the kernels too."""

from benchmark.metrics import xing_moe_ms_per_step as _ms
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta


def read(run):
    seconds, layers = _ms.product_seconds(run, step=False)
    tokens = traced_delta(run, "prefill_chunk_tokens")
    if not seconds or not layers or not tokens:
        return None
    return seconds / tokens * 1e6
