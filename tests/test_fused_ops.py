"""Fused model-path kernels (ops/fused.py) vs the jnp references, under
the Pallas interpreter on CPU — the decode_attention test idiom: the
same kernel glue that runs on TPU is executed by the interpreter here,
so a fusion bug surfaces as a failed equivalence, not as wrong tokens
on hardware. Gradients are checked against autodiff of the references
(the fused ops carry custom VJPs so the TRAIN path can use them)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (apply_rope, fused_qk_rope, fused_rms_norm,
                         fused_rms_norm_residual, fused_swiglu, rms_norm,
                         swiglu_reference)


def _randn(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


# ------------------------------------------------------------- forward


@pytest.mark.parametrize("shape", [(2, 8, 64), (1, 5, 48), (3, 1, 128)])
def test_fused_rms_norm_matches_reference(shape):
    x = _randn(0, shape)
    s = _randn(1, shape[-1:]) * 0.2
    ref = rms_norm(x, s, 1e-5)
    got = fused_rms_norm(x, s, 1e-5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_fused_rms_norm_residual_matches_unfused_pair():
    x = _randn(2, (2, 8, 64))
    res = _randn(3, (2, 8, 64))
    s = _randn(4, (64,)) * 0.2
    y, summed = fused_rms_norm_residual(x, res, s, 1e-5, interpret=True)
    np.testing.assert_allclose(np.asarray(summed), np.asarray(x + res),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(rms_norm(x + res, s, 1e-5)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("h,kh,hd", [(4, 2, 16), (8, 8, 32), (4, 1, 64)])
def test_fused_qk_rope_matches_two_apply_rope_calls(h, kh, hd):
    q = _randn(5, (2, 8, h, hd))
    k = _randn(6, (2, 8, kh, hd))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    qr, kr = fused_qk_rope(q, k, pos, 500000.0, interpret=True)
    np.testing.assert_allclose(np.asarray(qr),
                               np.asarray(apply_rope(q, pos, 500000.0)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kr),
                               np.asarray(apply_rope(k, pos, 500000.0)),
                               rtol=1e-5, atol=1e-6)


def test_fused_qk_rope_cache_offset_positions():
    """Decode-shaped call: T=1 tokens at a nonzero cache offset."""
    q = _randn(7, (3, 1, 4, 16))
    k = _randn(8, (3, 1, 2, 16))
    pos = jnp.full((3, 1), 37, jnp.int32)
    qr, kr = fused_qk_rope(q, k, pos, 10000.0, interpret=True)
    np.testing.assert_allclose(np.asarray(qr),
                               np.asarray(apply_rope(q, pos, 10000.0)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kr),
                               np.asarray(apply_rope(k, pos, 10000.0)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(2, 8, 32), (1, 3, 128), (4, 4, 96)])
def test_fused_swiglu_matches_reference(shape):
    gate, up = _randn(9, shape), _randn(10, shape)
    got = fused_swiglu(gate, up, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(swiglu_reference(gate, up)),
                               rtol=1e-6, atol=1e-6)


def test_fused_ops_bfloat16_dtype_preserved():
    x = _randn(11, (2, 8, 64), jnp.bfloat16)
    s = _randn(12, (64,)) * 0.2
    out = fused_rms_norm(x, s, 1e-5, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = rms_norm(x, s, 1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------ backward


def test_fused_rms_norm_grad_matches_autodiff():
    x = _randn(13, (2, 6, 48))
    s = _randn(14, (48,)) * 0.2

    def ref_loss(x, s):
        return jnp.sum(rms_norm(x, s, 1e-5) ** 2)

    def fused_loss(x, s):
        return jnp.sum(fused_rms_norm(x, s, 1e-5, interpret=True) ** 2)

    for a, b in zip(jax.grad(ref_loss, argnums=(0, 1))(x, s),
                    jax.grad(fused_loss, argnums=(0, 1))(x, s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_fused_rms_norm_residual_grad_matches_autodiff():
    x = _randn(15, (2, 4, 32))
    res = _randn(16, (2, 4, 32))
    s = _randn(17, (32,)) * 0.2

    def ref_loss(x, res, s):
        u = x + res
        # Both outputs feed the loss so both cotangents are exercised.
        return jnp.sum(rms_norm(u, s, 1e-5) ** 2) + jnp.sum(u ** 3)

    def fused_loss(x, res, s):
        y, u = fused_rms_norm_residual(x, res, s, 1e-5, interpret=True)
        return jnp.sum(y ** 2) + jnp.sum(u ** 3)

    for a, b in zip(jax.grad(ref_loss, argnums=(0, 1, 2))(x, res, s),
                    jax.grad(fused_loss, argnums=(0, 1, 2))(x, res, s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_fused_qk_rope_grad_matches_autodiff():
    q = _randn(18, (2, 6, 4, 16))
    k = _randn(19, (2, 6, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))

    def ref_loss(q, k):
        return (jnp.sum(apply_rope(q, pos, 1000.0) ** 2)
                + jnp.sum(apply_rope(k, pos, 1000.0) ** 3))

    def fused_loss(q, k):
        qr, kr = fused_qk_rope(q, k, pos, 1000.0, interpret=True)
        return jnp.sum(qr ** 2) + jnp.sum(kr ** 3)

    for a, b in zip(jax.grad(ref_loss, argnums=(0, 1))(q, k),
                    jax.grad(fused_loss, argnums=(0, 1))(q, k)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_fused_swiglu_grad_matches_autodiff():
    gate, up = _randn(20, (2, 5, 40)), _randn(21, (2, 5, 40))

    def ref_loss(g, u):
        return jnp.sum(swiglu_reference(g, u) ** 2)

    def fused_loss(g, u):
        return jnp.sum(fused_swiglu(g, u, interpret=True) ** 2)

    for a, b in zip(jax.grad(ref_loss, argnums=(0, 1))(gate, up),
                    jax.grad(fused_loss, argnums=(0, 1))(gate, up)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# --------------------------------- rope at a device's share of the cell

# `smollm2.sft.fsdp2tp2` on one device: 16 heads of 64 of q and of k
# (1,024 dense lanes each), rows cut down from 16 x 2,048.
_SHARE = (2, 256, 16, 64)
_POSITIONS = {
    "plain": lambda b, s: jnp.broadcast_to(jnp.arange(s), (b, s)),
    # A packed batch: every row starts elsewhere.
    "offset": lambda b, s: (jnp.arange(s)[None, :]
                            + 1000 * jnp.arange(1, b + 1)[:, None]),
}


def _share_operands():
    return (_randn(30, _SHARE, jnp.bfloat16), _randn(31, _SHARE, jnp.bfloat16))


def _ulp_close(got, ref):
    """To bf16's last place: float32 inside, one rounding at the end,
    where the two may fall on either side of a tie."""
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2 ** -7, atol=2 ** -9)


@pytest.mark.parametrize("flat", [False, True], ids=["heads", "flat"])
@pytest.mark.parametrize("positions", sorted(_POSITIONS))
def test_fused_qk_rope_at_a_devices_share_of_the_train_cell(positions, flat):
    """bf16 q and k at the train cell's real widths, as `[B, S, H, D]`
    and as the `[B, S, H·D]` that `models/llama.py` hands over: the
    kernel's outputs against `apply_rope`."""
    from ray_tpu.ops.fused import qk_rope_kernel_fits

    b, s, h, d = _SHARE
    assert qk_rope_kernel_fits(b * s, h * d, h * d, d)
    q, k = _share_operands()
    pos = _POSITIONS[positions](b, s)
    if flat:
        qr, kr = fused_qk_rope(q.reshape(b, s, -1), k.reshape(b, s, -1), pos,
                               130000.0, head_dim=d, interpret=True)
        assert qr.shape == kr.shape == (b, s, h * d)
        qr, kr = qr.reshape(_SHARE), kr.reshape(_SHARE)
    else:
        qr, kr = fused_qk_rope(q, k, pos, 130000.0, interpret=True)
    assert qr.dtype == kr.dtype == jnp.bfloat16
    _ulp_close(qr, apply_rope(q, pos, 130000.0))
    _ulp_close(kr, apply_rope(k, pos, 130000.0))


@pytest.mark.parametrize("positions", sorted(_POSITIONS))
def test_fused_qk_rope_vjp_at_a_devices_share_of_the_train_cell(positions):
    """The VJP (the same kernel at negated positions) against autodiff
    of `apply_rope`, bf16 cotangents."""
    b, s, h, d = _SHARE
    q, k = _share_operands()
    gq, gk = _randn(32, _SHARE, jnp.bfloat16), _randn(33, _SHARE, jnp.bfloat16)
    pos = _POSITIONS[positions](b, s)
    _, vjp = jax.vjp(lambda q, k: fused_qk_rope(q, k, pos, 130000.0,
                                                interpret=True), q, k)
    _, ref = jax.vjp(lambda q, k: (apply_rope(q, pos, 130000.0),
                                   apply_rope(k, pos, 130000.0)), q, k)
    for got, want in zip(vjp((gq, gk)), ref((gq, gk))):
        assert got.dtype == jnp.bfloat16
        _ulp_close(got, want)


@pytest.mark.parametrize("rows,nq,nk,d,fits", [
    (32768, 1024, 1024, 64, True),     # the train cell's share
    (8192, 2048, 512, 64, True),       # Llama-3 1B, grouped keys
    (1024, 4096, 1024, 128, True),     # Mistral's / Llama-3 8B's heads
    (16384, 1024, 64, 64, False),      # one key head: half a lane tile
    (24, 64, 32, 16, False),           # the CPU suite's toy widths
    (100, 1024, 1024, 64, False),      # rows that no block divides
    (2048, 960, 960, 96, False),       # 384 lanes hold whole heads of 96
    (2048, 1536, 384, 96, True),
])
def test_qk_rope_kernel_fits_whole_lane_tiles_of_heads(rows, nq, nk, d, fits):
    from ray_tpu.ops.fused import qk_rope_kernel_fits

    assert qk_rope_kernel_fits(rows, nq, nk, d) is fits


def _mesh_loss_and_grads(cfg, mesh, params, tokens):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import mesh_context, param_shardings

    def loss(p, t):
        return llama.loss_fn(p, t, cfg, mesh=mesh)[0]

    with mesh_context(mesh):
        p = jax.device_put(params, param_shardings(
            mesh, llama.param_logical_axes(cfg)))
        t = jax.device_put(tokens, NamedSharding(
            mesh, P(("dp", "fsdp"), "sp")))
        jaxpr = str(jax.make_jaxpr(jax.value_and_grad(loss))(p, t))
        return jax.jit(jax.value_and_grad(loss))(p, t), jaxpr


def test_rope_kernel_under_the_fsdp2_tp2_mesh_matches_the_twin():
    """The sharded seam numerically, CPU mesh fsdp 2 x tp 2 with the tp
    ring ON: the step's loss and gradients with the kernel (interpreted:
    a device's share is 2 heads of 64 of q and of k, one lane tile)
    against `apply_rope` as the ring's rowwise. The kernel is chosen by
    what the code observes (the interpreter asked for by the tests' own
    hook, the mesh, the shapes), and the ring's hops are the same."""
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import mesh_2d

    cfg = llama.tiny_config(d_model=256, n_heads=4, n_kv_heads=4, d_ff=256,
                            remat=True, max_seq_len=64)
    mesh = mesh_2d(4, tp=2, devices=jax.devices()[:4])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    (l0, g0), twin = _mesh_loss_and_grads(cfg, mesh, params, tokens)
    (l1, g1), kernel = _mesh_loss_and_grads(
        dataclasses.replace(cfg, interpret_kernels=True), mesh, params,
        tokens)
    # Forward and backward of each of the 2 layers' scan body.
    assert "rtpu_fused_qk_rope" not in twin
    assert kernel.count("name=rtpu_fused_qk_rope") == 2
    assert kernel.count("ppermute") == twin.count("ppermute") > 0
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


# ----------------------------------------------------- model dispatch


def test_llama_fused_forward_matches_unfused():
    """`LlamaConfig.fused_ops="interpret"` routes the block's norms and
    SwiGLU through the fused kernels (its whole-sequence rope is chosen
    by what the code observes: the twin at these toy widths); logits
    must match the unfused model exactly on f32 (identical math, one
    pass)."""
    from ray_tpu.models import llama

    cfg = llama.tiny_config()
    cfg_f = dataclasses.replace(cfg, fused_ops="interpret")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    ref = llama.forward(params, tokens, cfg)
    got = llama.forward(params, tokens, cfg_f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_llama_fused_decode_matches_unfused():
    """KV-cache prefill + decode with fused_ops on: same logits, step by
    step (covers the [B,1]-shaped kernel calls inside the cache path)."""
    from ray_tpu.models import llama

    cfg = llama.tiny_config()
    cfg_f = dataclasses.replace(cfg, fused_ops="interpret")
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    prompt = jnp.asarray([[5, 9, 3, 7], [2, 8, 1, 4]], jnp.int32)
    cache = llama.init_kv_cache(cfg, 2, 16)
    cache_f = llama.init_kv_cache(cfg_f, 2, 16)
    l0, cache = llama.forward_with_cache(params, prompt, cache, 0, cfg)
    l1, cache_f = llama.forward_with_cache(params, prompt, cache_f, 0,
                                           cfg_f)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=1e-6, atol=1e-6)
    tok = jnp.argmax(l0[:, -1], -1)[:, None].astype(jnp.int32)
    for step in range(3):
        l0, cache = llama.forward_with_cache(params, tok, cache,
                                             4 + step, cfg)
        l1, cache_f = llama.forward_with_cache(params, tok, cache_f,
                                               4 + step, cfg_f)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                                   rtol=1e-6, atol=1e-6)
        tok = jnp.argmax(l0[:, -1], -1)[:, None].astype(jnp.int32)


def test_llama_fused_train_step_grads_match():
    """One full value_and_grad through the scanned, rematted, fused
    block stack: the custom VJPs must agree with autodiff end to end."""
    from ray_tpu.models import llama

    cfg = llama.tiny_config(remat=True)
    cfg_f = dataclasses.replace(cfg, fused_ops="interpret")
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0,
                                cfg.vocab_size)

    def loss(p, c):
        return llama.loss_fn(p, tokens, c)[0]

    (l0, g0) = jax.value_and_grad(loss)(params, cfg)
    (l1, g1) = jax.value_and_grad(loss)(params, cfg_f)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


# The three programs of the Mistral cells (`mistral7b.chat.steady`,
# `.chat.flood`, `.doc.steady`: widths of benchmark/configs/
# mistral-7b-v0.3-l16.json, 2 of its scanned layers) as PR 62 traced
# them: sha256 of the jaxpr's text, first 16 hex digits. The kernel that
# the whole-sequence block takes by what it observes (PR 63) is for the
# path WITHOUT a cache; with one, `_block` and the step do what they
# did, and every serving check pins their logits to these programs.
_CACHE_PROGRAMS = {
    "prefill_1x1024": "2f74a7e3f4e790e6",
    "paired_prefill_2x512": "3ae4b53871b6753d",
    "step_32x1": "43e0ab1248b93fb6",
}


@pytest.mark.parametrize("program", sorted(_CACHE_PROGRAMS))
def test_llama_cache_paths_trace_the_programs_they_did(program):
    import functools
    import hashlib

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=32768, d_model=4096, n_layers=2,
                            n_heads=32, n_kv_heads=8, d_ff=14336,
                            max_seq_len=1024, rope_theta=1e6)
    params = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    cache = lambda slots: jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, slots, 1024))
    fn, args = {
        "prefill_1x1024": (llama.forward_last_with_cache,
                           (ints(1, 1024), cache(1), ints(), ints())),
        "paired_prefill_2x512": (llama.forward_last_rows_with_cache,
                                 (ints(2, 512), cache(2), ints(2), ints(2))),
        "step_32x1": (llama.decode_step_with_cache,
                      (ints(32, 1), cache(32), ints(32))),
    }[program]
    jaxpr = str(jax.make_jaxpr(lambda p, *a: fn(p, *a, cfg))(params, *args))
    assert "rtpu_fused_qk_rope" not in jaxpr
    assert (hashlib.sha256(jaxpr.encode()).hexdigest()[:16]
            == _CACHE_PROGRAMS[program]), (
        f"{program} traces another program than PR 62's: a change to the "
        "cache paths moves the three Mistral cells")
