"""Device milliseconds a PREFILL's mHC mixes take per 1000 REAL prompt
tokens: self time on device 0, in the traced stretch, of the
``rtpu_mhc_*`` calls whose rows are not the decode step's
(`mhc_ms_per_step.kernel_seconds`) over the real tokens of the chunks
dispatched in the stretch (``prefill_chunk_tokens`` of
``engine.stats()``, `prefill_chunk_ms_per_ktok`'s divisor). A bucket's
padding is mixed like its real tokens, so a short prompt in a long
bucket reads high."""

from benchmark.metrics import mhc_ms_per_step as _ms
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta


def read(run):
    seconds, _ = _ms.kernel_seconds(run, step=False)
    tokens = traced_delta(run, "prefill_chunk_tokens")
    if not seconds or not tokens:
        return None
    return seconds / tokens * 1e6
