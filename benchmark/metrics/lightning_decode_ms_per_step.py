"""Device milliseconds the lightning state step takes in one decode
step: self time on device 0 of the ``rtpu_lightning_decode`` custom
calls (``ops/lightning.py`` names its ``pl.pallas_call``; the step
calls it once a lightning layer for all slots) over the
``decode_chunk`` program's executions in the trace x ``decode_chunk``
steps each. The kernel alone: the norms, the rotation and the
projections around it are fusions with names of their own."""

import re

from benchmark.metrics import sparse_decode_attn_ms_per_step as _ms

KERNEL = re.compile(
    r"rtpu_lightning_decode\.?\d* custom-call .*tpu_custom_call$")


def read(run):
    return _ms.ms_per_step(run, KERNEL)
