"""Device milliseconds a decode step spends CHOOSING the rows its full
layers attend to: self time on device 0 of the ``rtpu_dsa_select``
custom calls (``ops/row_select.py`` names its ``pl.pallas_call``: the
indexer's scores over a slot's index keys and the exact top
``index_topk`` of them, one call a full layer for all slots; nothing is
gathered, the mask it writes is what the attention kernel reads under)
over the ``decode_chunk`` program's executions in the trace x
``decode_chunk`` steps each. The indexer's projections (its queries,
the key of the new token, the heads' weights) are XLA fusions with no
name of their own and lie in `dots3_step_rest_ms`."""

import re

from benchmark.metrics import sparse_decode_attn_ms_per_step as _ms

KERNEL = re.compile(r"rtpu_dsa_select\.?\d* custom-call .*tpu_custom_call$")


def read(run):
    return _ms.ms_per_step(run, KERNEL)
