"""The causal flash kernels (`ops/flash_attention.py`) and the blocks
`ops/attention.py` chooses for them, on the CPU.

The kernels run here under the Pallas interpreter against
`causal_attention` and its gradient (the `tests/test_fused_ops.py`
idiom): that holds the arithmetic and every skip / mask decision of the
inner tiles, at block shapes that put tiles above, on and below the
diagonal. What the interpreter cannot see (Mosaic's lowering, the
tiling) is `tests/test_chip_compile.py`'s, and the results at the real
size are `chip_smoke.py`'s, on the chip.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (FLASH_SAVED, causal_attention,
                                   flash_block_sizes)
from ray_tpu.ops.flash_attention import (LANES, _flash_mha_fwd,
                                         flash_attention)

HEAD_SIZES, SEQS = (64, 128, 256), (256, 512, 1024, 2048, 4096)


def _by_role(bs: BlockSizes) -> dict:
    """{kernel: (major q, q tile, major k, k tile)}."""
    return {"fwd": (bs.block_q, bs.block_q, bs.block_k_major, bs.block_k),
            "dkv": (bs.block_q_major_dkv, bs.block_q_dkv,
                    bs.block_k_major_dkv, bs.block_k_dkv),
            "dq": (bs.block_q_dq, bs.block_q_dq, bs.block_k_major_dq,
                   bs.block_k_dq)}


@pytest.mark.parametrize("seq", SEQS)
@pytest.mark.parametrize("hd", HEAD_SIZES)
def test_chosen_blocks_tile_the_sequence(hd, seq):
    bs = flash_block_sizes(hd, seq)
    fields = dataclasses.asdict(bs)
    assert len(fields) == 11 and fields.pop("block_b") == 1
    for name, block in fields.items():
        assert seq % block == 0 and block % LANES == 0, (name, block)
    for kernel, (qm, q, km, k) in _by_role(bs).items():
        assert q <= qm and qm % q == 0, kernel
        assert k <= km and km % k == 0, kernel
        # The unrolled tiles of one grid step: a kernel's code grows
        # with them, twice (with and without the mask).
        assert (qm // q) * (km // k) <= 16, kernel
    # A shape no sweep covered keeps one block for every field, the
    # rule every shape had before PR 38; PERF.md section 6 (PR 38) holds
    # the sweep's table for the others.
    if (hd, seq) not in attention.SWEPT:
        one = next(c for c in (1024, 512, 256, 128) if seq % c == 0)
        assert set(fields.values()) == {one}


def _blocks(fwd, dkv, dq) -> BlockSizes:
    return BlockSizes(
        block_q=fwd[0], block_k_major=fwd[1], block_k=fwd[2], block_b=1,
        block_q_major_dkv=dkv[0], block_q_dkv=dkv[1],
        block_k_major_dkv=dkv[2], block_k_dkv=dkv[3],
        block_q_dq=dq[0], block_k_major_dq=dq[1], block_k_dq=dq[2])


# (batch, heads, seq, head size, blocks): tiles wider than tall and
# taller than wide, a major block that is the whole sequence and one
# that is a tile, so that every kernel meets tiles it skips, tiles it
# masks and tiles it computes bare.
CASES = {
    "hd64_wide_tiles": (1, 2, 512, 64, _blocks(
        (256, 512, 128), (512, 256, 256, 128), (128, 512, 256))),
    "hd128_tall_tiles": (2, 1, 512, 128, _blocks(
        (128, 256, 256), (256, 128, 512, 256), (512, 128, 128))),
    "hd64_one_tile_a_step": (1, 1, 384, 64, _blocks(
        (128, 128, 128), (128, 128, 128, 128), (128, 128, 128))),
    "hd256_whole_sequence": (1, 1, 256, 256, _blocks(
        (256, 256, 256), (256, 256, 256, 256), (256, 256, 256))),
    "chosen_hd64": (1, 2, 512, 64, None),
}


def _operands(case, dtype=jnp.float32):
    b, h, s, d, blocks = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q, k, v, w = (jax.random.normal(key, (b, h, s, d), dtype)
                  for key in keys)
    return q, k, v, w, d ** -0.5, blocks or flash_block_sizes(d, s)


def _reference(q, k, v, scale):
    t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return t(causal_attention(t(q), t(k), t(v), scale=scale))


@pytest.mark.parametrize("case", CASES)
def test_interpreted_forward_matches_causal_attention(case):
    q, k, v, _, scale, blocks = _operands(case)
    got = flash_attention(q, k, v, scale, blocks, True)
    assert jnp.allclose(got, _reference(q, k, v, scale), atol=2e-5)


@pytest.mark.parametrize("case", CASES)
def test_interpreted_backward_matches_causal_attention(case):
    q, k, v, w, scale, blocks = _operands(case)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(q, k, v, scale, blocks, True))
    want = grads(lambda q, k, v: _reference(q, k, v, scale))
    for name, g, r in zip("qkv", got, want):
        assert jnp.allclose(g, r, atol=5e-5), (name, float(
            jnp.max(jnp.abs(g - r))))


def test_interpreted_bf16_operands_round_once():
    """bf16 operands as the cells run them: products accumulate in
    float32 and the softmax is float32, so the kernel sits within a
    bf16 rounding of the float32 reference on the same (rounded)
    operands."""
    q, k, v, _, scale, blocks = _operands("hd64_wide_tiles", jnp.bfloat16)
    got = flash_attention(q, k, v, scale, blocks, True)
    assert got.dtype == jnp.bfloat16
    want = _reference(*(x.astype(jnp.float32) for x in (q, k, v)), scale)
    assert jnp.allclose(got.astype(jnp.float32), want, atol=2e-2)


@pytest.mark.parametrize("block", [96, 384])
def test_a_block_off_the_tiling_is_refused_by_name(block):
    q, k, v, _, scale, _ = _operands("hd64_wide_tiles")
    bad = _blocks((block, block, block), (128,) * 4, (128,) * 3)
    with pytest.raises(ValueError, match="block_q"):
        flash_attention(q, k, v, scale, bad, True)


# ----------------------------------- what the backward reads, and keeps

@pytest.mark.parametrize("case", ["hd64_wide_tiles", "hd128_tall_tiles"])
def test_the_row_statistic_is_one_float_a_row(case):
    """The forward leaves ``log sum exp`` of a row's visible scores as a
    [B, H, 1, S] row (the kernel turns its 128 equal lanes into it):
    what a train step keeps a layer is 4 bytes a query."""
    q, k, v, _, scale, blocks = _operands(case)
    o, (_, _, _, kept, lse) = _flash_mha_fwd(q, k, v, scale, blocks, True)
    b, h, s, _ = q.shape
    assert kept is o and (lse.shape, lse.dtype) == ((b, h, 1, s), jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    visible = jnp.tril(jnp.ones((s, s), bool))
    want = jax.nn.logsumexp(jnp.where(visible, scores, -jnp.inf), axis=-1)
    assert jnp.allclose(lse[:, :, 0], want, atol=2e-5)


def pallas_kernels(jaxpr) -> list:
    """The kernel function's name of every `pallas_call` in a jaxpr and
    the jaxprs its equations hold (`tests/test_model_llama.py` counts a
    layer's with it too)."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["jaxpr"].debug_info.func_name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += pallas_kernels(sub)
    return names


@pytest.mark.parametrize("policy,forwards", [
    (jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED), 1),
    (jax.checkpoint_policies.nothing_saveable, 2)], ids=["named", "nothing"])
def test_keeping_the_named_residuals_runs_no_forward_kernel_again(
        policy, forwards):
    """The custom VJP's residuals ARE the values it names: a checkpoint
    that keeps the two names feeds dkv and dq from what it kept, and
    the gradients are the ones of no checkpoint at all, to the bit."""
    q, k, v, w, scale, blocks = _operands("chosen_hd64")

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, scale, blocks, True) * w)

    kept = jax.grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1, 2))
    assert sorted(pallas_kernels(jax.make_jaxpr(kept)(q, k, v).jaxpr)) == sorted(
        ["_fwd_kernel"] * forwards + ["_dkv_kernel", "_dq_kernel"])
    for g, r in zip(kept(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)):
        assert jnp.array_equal(g, r)
