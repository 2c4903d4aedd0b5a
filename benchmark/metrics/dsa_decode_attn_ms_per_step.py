"""Device milliseconds the full layers' attention takes in one decode
step: self time on device 0 of the ``rtpu_dsa_decode_attention`` custom
calls (the latent kernel of ``ops/mla_decode.py`` under the name
``models/dots3_note.py`` gives it for the layers whose queries choose
their rows: one call a full layer for all slots, absorbed MLA over the
slot's rows under the selection's mask) over the ``decode_chunk``
program's executions in the trace x ``decode_chunk`` steps each."""

import re

from benchmark.metrics import sparse_decode_attn_ms_per_step as _ms

KERNEL = re.compile(
    r"rtpu_dsa_decode_attention\.?\d* custom-call .*tpu_custom_call$")


def read(run):
    return _ms.ms_per_step(run, KERNEL)
