"""Device milliseconds the sliding layers' attention takes in one
decode step: self time on device 0 of the ``rtpu_swa_decode_attention``
custom calls (the latent kernel of ``ops/mla_decode.py`` at the sliding
layers' row width, 1,152 lanes, over each slot's ring of kept rows
under the window's mask: one call a sliding layer for all slots) over
the ``decode_chunk`` program's executions in the trace x
``decode_chunk`` steps each. It does not grow with the context."""

import re

from benchmark.metrics import sparse_decode_attn_ms_per_step as _ms

KERNEL = re.compile(
    r"rtpu_swa_decode_attention\.?\d* custom-call .*tpu_custom_call$")


def read(run):
    return _ms.ms_per_step(run, KERNEL)
