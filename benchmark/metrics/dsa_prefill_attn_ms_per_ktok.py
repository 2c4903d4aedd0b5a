"""Device milliseconds the full layers' prefill attention takes per 1000
REAL prompt tokens: self time on device 0 of the
``rtpu_dsa_prefill_attention`` custom calls in the traced stretch
(``ops/dsa_prefill.py`` names its ``pl.pallas_call``; a prefill chunk
calls it once a full layer: the chunk's queries over the slot's latent
rows under the selection's mask, the scores kept in fast memory) over
the real tokens of the chunks dispatched in the stretch
(``prefill_chunk_tokens`` of ``engine.stats()``, the divisor of
`prefill_chunk_ms_per_ktok`). A program without the kernel (the `jnp`
fusions before it carry no name) reads nothing."""

import re

from benchmark.metrics import sparse_decode_attn_ms_per_step as _ms
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta

KERNEL = re.compile(
    r"rtpu_dsa_prefill_attention\.?\d* custom-call .*tpu_custom_call$")


def read(run):
    seconds, calls = _ms.kernel_seconds(run, KERNEL)
    tokens = traced_delta(run, "prefill_chunk_tokens")
    if not calls or not seconds or not tokens:
        return None
    return seconds / tokens * 1e6
