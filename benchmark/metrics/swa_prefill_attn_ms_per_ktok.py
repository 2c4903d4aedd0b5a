"""Device milliseconds the WINDOW layers' prefill attention takes per
1000 REAL prompt tokens: self time on device 0 of the
``rtpu_swa_prefill_attention`` custom calls in the traced stretch
(``ops/swa_prefill.py`` names its ``pl.pallas_call``; a prefill chunk
calls it once a sliding layer: a block of queries over its own rows and
the window's reach before them, the scores kept in fast memory) over
the real tokens of the chunks dispatched in the stretch
(``prefill_chunk_tokens`` of ``engine.stats()``, the divisor of
`prefill_chunk_ms_per_ktok`): the twin of `dsa_prefill_attn_ms_per_ktok`
for the other kind of layer. A program without the kernel (the `jnp`
fusions before it carry no name) reads nothing, so a number here also
says that the kernel engaged."""

import re

from benchmark.metrics import sparse_decode_attn_ms_per_step as _ms
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta

KERNEL = re.compile(
    r"rtpu_swa_prefill_attention\.?\d* custom-call .*tpu_custom_call$")


def read(run):
    seconds, calls = _ms.kernel_seconds(run, KERNEL)
    tokens = traced_delta(run, "prefill_chunk_tokens")
    if not calls or not seconds or not tokens:
        return None
    return seconds / tokens * 1e6
