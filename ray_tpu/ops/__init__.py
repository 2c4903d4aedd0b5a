"""Public kernel API for the model path.

Every op pairs a portable jnp reference with a TPU-tuned fast path and a
dispatcher that picks between them; callers import from THIS package,
not the submodules. Dispatch conditions:

=========================  ===============================  =========================================
op                         TPU fast path                    dispatch condition
=========================  ===============================  =========================================
full_causal_attention      Pallas flash kernels (fwd, dkv,  ``use_fused_kernel``: standard arange
                           dq: ``ops/flash_attention.py``,  positions, seq >= 256 and % 128 == 0,
                           blocks from head size and seq),  head_dim <= 128 or % 128 == 0; else
                           shard_mapped over batch and      blockwise scan (seq >= 1024) / dense
                           heads when ``mesh`` has > 1
                           device (XLA cannot partition
                           a Mosaic kernel)
causal_attention           (portable dense reference)       always available; position-based masks
blockwise_attention        (portable online-softmax scan)   seq a multiple of ``block_k``
decode_attention           Pallas single-query kernel:      on TPU, or ``interpret=True`` off-TPU;
                           streams the blocks of rows that  jnp reference elsewhere
                           begin under each slot's length
mla_decode_attention       Pallas single-query kernel over  on TPU, or ``interpret=True`` off-TPU;
                           a LATENT cache: one shared key   jnp reference elsewhere.
                           whose first columns are the      ``mla_step_rows`` gives a step's counters
                           value; streams the blocks that   ``mla_decode_rows`` and
                           begin under each slot's length,  ``mla_decode_rows_streamed`` (the rows of
                           each row read once               the blocks the kernel fetches)
gated_delta.gdn_decode     Pallas state step of a gated     on TPU, or ``interpret=True`` off-TPU;
                           delta-rule layer: every slot's   jnp twin elsewhere. Imported by its one
                           state of one layer stepped       caller (``models/olmo_hybrid.py``) as
                           where it lies (aliased)          ``ray_tpu.ops.gated_delta``, not from
gated_delta.chunk_scan     (jnp chunked WY form under       here: a family the process does not
                           ``lax.scan``; no kernel yet)     serve costs it no import
mamba2.mamba2_decode       Pallas state step of a Mamba-2   on TPU, or ``interpret=True`` off-TPU;
                           layer (S^T of 2 heads a tile,    jnp twin elsewhere. Imported by its one
                           B and C shared by the heads),    caller (``models/granite_hybrid.py``) as
                           aliased like gdn_decode          ``ray_tpu.ops.mamba2``; the convolution
mamba2.chunk_scan          (jnp SSD in chunks of 256 under  beside it is ``gated_delta.causal_conv``
                           ``lax.scan``; no kernel yet)
kda.kda_decode             Pallas state step of a KDA       on TPU, or ``interpret=True`` off-TPU;
                           layer (a delta rule whose decay  jnp twin elsewhere. Imported by its one
                           is a VECTOR over the key         caller (``models/kimi_linear.py``) as
                           channels: q, k and the decay     ``ray_tpu.ops.kda``; the convolutions and
                           spread to columns on the MXU),   the L2 norm beside it are
                           aliased like gdn_decode          ``gated_delta``'s
kda.chunk_scan             (jnp chunked form on decay       always; no exponent in it is positive
                           DIFFERENCES, sub-chunks of 16    (the factored form overflows float32
                           under ``lax.scan``; no kernel)   inside one chunk)
lightning.lightning_decode Pallas state step of a           on TPU, or ``interpret=True`` off-TPU;
                           lightning (constant-decay)       jnp twin elsewhere. ``ops.lightning`` and
                           layer, aliased like gdn_decode   ``ops.sparse_attention`` are imported by
lightning.chunk_scan       (jnp chunked form under          their one caller
                           ``lax.scan``; no kernel yet)     (``models/minicpm_sala.py``)
sparse_attention           Pallas single-query kernel over  on TPU, or ``interpret=True`` off-TPU;
 .sparse_decode_attention  a LIST of 64-row blocks a (slot, jnp gather twin elsewhere. The list is
                           KV head): a block table made     `select_blocks`' (scores over compressed
                           anew every step                  keys, pooled to blocks, top-k: XLA)
 .sparse_prefill_attention (jnp: each query's selection as  always; the loop's trip count follows
                           a mask, tiles of rows under a    the chunk's last position
                           ``fori_loop``; no kernel yet)
row_select                 Pallas scoring-and-selection of  on TPU, or ``interpret=True`` off-TPU;
 .select_decode_rows       single rows for a decode step:   jnp twin elsewhere. Imported by its one
                           a slot's index keys streamed to  caller (``models/dots3_note.py``). The
                           its length, the exact top-k of   mask it writes is ``mla_decode_attention``'s
                           the scores found with no sort    ``keep``
 .top_rows, .index_scores  (jnp: a chunk's scores tile by   always; the prefill's selection as a mask
                           tile, the exact top-k as a mask)  over rows
dsa_prefill                Pallas kernel of a CHUNK of      on TPU where the widths are whole lanes, or
 .dsa_prefill_attention    queries over a slot's latent     ``interpret=True`` off-TPU; jnp twin
                           rows under a mask over rows:     elsewhere (the same tiles of rows, their
                           rows expanded to keys and        scores through HBM). Imported by its one
                           values a tile once a group of    caller (``models/dots3_note.py``); the mask
                           heads, a block of scores kept    is `row_select`'s selection, so no row the
                           in fast memory, tiles past the   queries chose is dropped and none added
                           rows written not read
swa_prefill                Pallas kernel of a CHUNK of      on TPU where the widths and the window's
 .swa_prefill_attention    queries over a WINDOW: a block   reach are whole 128-tiles and the reach
                           of queries over its own rows     divides the chunk, or ``interpret=True``
                           and the reach before them (keys  off-TPU; jnp twin elsewhere (a block's
                           and values expanded once,        scores and its gathered span through HBM).
                           outside, head-major, given       Imported by its one caller
                           twice a block apart), the mask   (``models/dots3_note.py``: the sliding
                           made from positions and the      layers' prefill); also writes how many
                           scores kept in fast memory       rows each query read and the lowest
                                                            position among them, from that mask
mhc.mhc_pre, .mhc_post     Pallas kernels of a four-stream    on TPU where the width is whole lanes, or
                           (mHC) residual over a block of     ``interpret=True`` off-TPU; jnp twins
                           ROWS: ``rtpu_mhc_pre`` (the norm,  elsewhere. Imported by its one caller
                           the product with Phi at float32    (``models/xing_mhc.py``) as
                           precision in three bf16 passes,    ``ray_tpu.ops.mhc``; a decode step's
                           sigmoids, 20 Sinkhorn passes with  slots and a prefill bucket's tokens are
                           rows on the lanes, the streams'    the same two kernels
                           weighted sum) and ``rtpu_mhc_post``
                           (H_res X + H_post^T y written over
                           X, aliased)
grouped_experts            Pallas grouped kernels between a   a PREFILL's rows on the TPU (or
 .grouped_swiglu           stable sort by expert and its      ``interpret=True``): ``T * k`` at least
 .gated_sum                inverse (dropless, any k; pairs    ``KERNEL_ROWS_A_GROUP`` (8) a held group,
                           k-major, both gathers in range):   in whole tiles of 128 rows. Else (a decode
                           ``rtpu_grouped_swiglu`` (gate and  step; off the chip) ``lax.ragged_dot`` x 3:
                           up in one pass) and                the chip compiler's grouped matmul, elsewhere
                           ``rtpu_grouped_matmul``: each      a masked dense product. ``gated_sum`` is the
                           touched expert's matrices copied   same code everywhere. Imported by its
                           once a call, row tiles past the    callers (``models/glm_moe_lite.py``,
                           groups' total never visited;       ``models/zaya.py``,
                           then the gates' float32 sum over   ``models/dots3_note.py``,
                           the ``[k, T, d]`` rows, those of   ``models/kimi_linear.py`` and through
                           no group selected out in the one   it ``models/xing_mhc.py``,
                           fusion that widens them            ``models/granite_hybrid.py``, which hold
                                                            a SHARE of the experts: ``held``)
ring_attention             shard_map ppermute ring          mesh ``sp`` axis > 1 (with attention.py
                                                            and fused.py the only importers of
                                                            shard_map — rtpu-lint banned-API rule)
rms_norm                   (fp32 jnp reference)             always; the fused ops' exactness anchor
apply_rope                 (fp32 jnp reference)             always; ``freqs`` where the frequencies
                                                            are scaled (``rotary.YarnScaling``)
fused_rms_norm             Pallas one-pass norm kernel      ``LlamaConfig.fused_ops``: kernel on TPU
fused_rms_norm_residual    + residual-add fold              or under ``interpret``; reference impl
fused_swiglu               silu(gate)*up, no temp           elsewhere (same custom VJP both ways,
                                                            so the train path may fuse too)
fused_qk_rope              ``rtpu_fused_qk_rope``: q AND k  ``models/llama.py``'s whole-sequence,
                           rotated in one call on dense     no-cache block (the train step, forward
                           [rows, heads x head size]        and backward, behind the tp ring too): on
                           lanes, cos and sin once a row    the TPU where a device's share of q and k
                           block, its VJP the same kernel   is whole lane tiles of heads
                           at negated positions; under a    (``qk_rope_on_mesh_fits``: backend, mesh
                           mesh of several devices inside   and shapes, no option), ``apply_rope``
                           a ``shard_map`` manual over      elsewhere; the cache paths only under
                           every axis, like the flash       ``LlamaConfig.fused_ops``
                           kernels
=========================  ===============================  =========================================

Every dispatcher asks ``jax.default_backend() == "tpu"``, and on the TPU
nothing catches a kernel failure to continue on a reference. The
interpreter passes kernels the chip's compiler refuses:
``tests/test_chip_compile.py`` compiles each one for a described v5e and
``chip_smoke.py`` runs each one on the chip against its reference.
"""

from ray_tpu.ops.attention import (
    FLASH_SAVED,
    blockwise_attention,
    causal_attention,
    full_causal_attention,
    online_softmax_update,
    repeat_kv,
    use_fused_kernel,
)
from ray_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
    decode_step_rows,
)
from ray_tpu.ops.fused import (
    fused_qk_rope,
    fused_rms_norm,
    fused_rms_norm_residual,
    fused_swiglu,
    qk_rope_on_mesh_fits,
    swiglu_reference,
)
from ray_tpu.ops.mla_decode import (
    mla_decode_attention,
    mla_decode_attention_reference,
    mla_step_rows,
)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.rotary import apply_rope, rope_frequencies

__all__ = [
    "FLASH_SAVED",
    "apply_rope",
    "blockwise_attention",
    "causal_attention",
    "decode_attention",
    "decode_attention_reference",
    "decode_step_rows",
    "full_causal_attention",
    "fused_qk_rope",
    "fused_rms_norm",
    "fused_rms_norm_residual",
    "fused_swiglu",
    "qk_rope_on_mesh_fits",
    "mla_decode_attention",
    "mla_decode_attention_reference",
    "mla_step_rows",
    "online_softmax_update",
    "repeat_kv",
    "ring_attention",
    "rms_norm",
    "rope_frequencies",
    "swiglu_reference",
    "use_fused_kernel",
]
