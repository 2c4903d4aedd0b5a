"""The window layers' prefill attention's share of its roofline: the
least time the chip could take for the products that the traced
stretch's real prompt tokens REQUIRE over
``rtpu_swa_prefill_attention``'s device time there. REQUIRED
(`opcount_dots3_swa_prefill.swa_prefill_attention_cost`): every real
token of the chunks dispatched in the stretch (``prefill_chunk_tokens``,
`swa_prefill_attn_ms_per_ktok`'s divisor) times the window's 513 rows
times the sliding layers' 64 heads' score and value products, a sliding
layer, at the chip's peak: from the counter and the configuration,
never from what the kernel did. Every token is charged the whole
window, which a prompt's first 512 do not have: at most 512 x 513 / 2
pairs too many a prompt of 8,704 tokens or more, under 3 %, and high by
that much at the most.

A kernel multiplies a block's whole span under its mask for a window of
513 (1,024 rows a query where it takes 512 queries at a time, 768 at
256), and bucket padding is time and no token: this share cannot pass
513 / 768 = 67 % of the kernel's own share of the peak, and no reading
near 105 % is possible."""

from benchmark.harness import opcount, opcount_dots3_swa_prefill
from benchmark.metrics import sparse_decode_attn_ms_per_step as _kernel
from benchmark.metrics import swa_prefill_attn_ms_per_ktok as _ms
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta


def read(run):
    if not run.get("trace") or run.get("peaks") is None:
        return None
    seconds, calls = _kernel.kernel_seconds(run, _ms.KERNEL)
    tokens = traced_delta(run, "prefill_chunk_tokens")
    if not calls or not seconds or not tokens:
        return None
    cost = opcount_dots3_swa_prefill.swa_prefill_attention_cost(
        run["config"], tokens)
    return opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
