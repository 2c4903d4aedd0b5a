"""Model + sharding tests on the virtual 8-device CPU mesh (SURVEY.md §4.3:
the reference tests accelerator topology on CPU with mocked detection; here
the analog is an 8-device host-platform mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel import spmd
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from tests.test_flash_attention import pallas_kernels


@pytest.fixture(scope="module")
def tiny_cfg():
    return llama.tiny_config()


def test_forward_shapes(tiny_cfg):
    params = llama.init_params(tiny_cfg, jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, tiny_cfg)
    assert logits.shape == (2, 16, tiny_cfg.vocab_size)
    assert jnp.isfinite(logits).all()


def test_loss_decreases_with_training(tiny_cfg):
    key = jax.random.key(1)
    params = llama.init_params(tiny_cfg, key)
    tokens = jax.random.randint(key, (4, 32), 0, tiny_cfg.vocab_size)
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        (loss, _), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(
            params, tokens, tiny_cfg)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9


def test_ring_attention_matches_dense(cpu_mesh8):
    """Ring attention over sp=8 must agree with single-device attention."""
    mesh = make_mesh(MeshSpec(sp=8), cpu_mesh8)
    b, s, h, d = 2, 64, 4, 16
    key = jax.random.key(0)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
               for kk in jax.random.split(key, 3))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = causal_attention(q, k, v, q_positions=pos, kv_positions=pos)
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, pos, pos, mesh=mesh, batch_spec=None, heads_axis=None))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_gqa_kv_cache_decode_matches_forward(tiny_cfg):
    """Prefill+decode against the KV cache must equal the full forward."""
    cfg = tiny_cfg
    params = llama.init_params(cfg, jax.random.key(2))
    tokens = jax.random.randint(jax.random.key(3), (2, 12), 0, cfg.vocab_size)
    full = llama.forward(params, tokens, cfg)

    cache = llama.init_kv_cache(cfg, 2, 16)
    logits_p, cache = llama.forward_with_cache(params, tokens[:, :8], cache, 0, cfg)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(full[:, :8]),
                               rtol=2e-3, atol=2e-3)
    for i in range(8, 12):
        logits_d, cache = llama.forward_with_cache(
            params, tokens[:, i:i + 1], cache, i, cfg)
        np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                                   np.asarray(full[:, i]), rtol=2e-3, atol=2e-3)


def test_spmd_train_step_multichip(cpu_mesh8):
    """Full dp×fsdp×sp×tp train step compiles and runs on the 8-dev mesh."""
    mesh = make_mesh(MeshSpec(fsdp=2, sp=2, tp=2), cpu_mesh8)
    cfg = llama.tiny_config(n_heads=4, n_kv_heads=2, d_ff=128)
    tx = spmd.default_optimizer(lr=1e-3)
    state = spmd.sharded_init(cfg, mesh, jax.random.key(0), tx)
    step = spmd.make_train_step(cfg, mesh, tx)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size),
        spmd.data_sharding(mesh))
    state, metrics = step(state, tokens)
    state, metrics = step(state, tokens)
    assert int(state.step) == 2
    assert np.isfinite(float(metrics["loss"]))


def test_param_count_llama3_8b():
    assert abs(llama.LLAMA3_8B.param_count() - 8.03e9) / 8.03e9 < 0.01

@pytest.mark.slow  # tier-1 budget relief (PR 12): 24.1s measured on a quiet box;
# long-seq equivalence — short-seq blockwise equivalence stays tier-1
def test_long_seq_blockwise_and_chunked_ce_match_dense():
    """s=1024 exercises the production paths: blockwise online-softmax
    attention (sk>=1024) and lax.map-chunked cross-entropy (s > logits_chunk).
    Both must match the short-sequence dense implementations."""
    from ray_tpu.ops.attention import blockwise_attention

    cfg = llama.tiny_config(max_seq_len=1024)
    b, s = 2, 1024
    key = jax.random.key(7)
    params = llama.init_params(cfg, key)
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)

    # Attention: blockwise vs dense, values and grads.
    h, d = 4, 16
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
               for kk in jax.random.split(key, 3))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    dense = causal_attention(q, k, v, q_positions=pos, kv_positions=pos)
    blk = blockwise_attention(q, k, v, q_positions=pos, kv_positions=pos)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    g_dense = jax.grad(lambda q: causal_attention(
        q, k, v, q_positions=pos, kv_positions=pos).sum())(q)
    g_blk = jax.grad(lambda q: blockwise_attention(
        q, k, v, q_positions=pos, kv_positions=pos).sum())(q)
    np.testing.assert_allclose(np.asarray(g_blk), np.asarray(g_dense),
                               rtol=2e-4, atol=2e-4)

    # Loss: chunked (512) vs unchunked (chunk >= s disables chunking).
    l_chunked, _ = llama.loss_fn(params, tokens, cfg, logits_chunk=512)
    l_dense, _ = llama.loss_fn(params, tokens, cfg, logits_chunk=s)
    np.testing.assert_allclose(float(l_chunked), float(l_dense),
                               rtol=1e-5, atol=1e-5)
    gc = jax.grad(lambda p: llama.loss_fn(p, tokens, cfg, logits_chunk=512)[0])(
        params)["blocks"]["wq"]
    gd = jax.grad(lambda p: llama.loss_fn(p, tokens, cfg, logits_chunk=s)[0])(
        params)["blocks"]["wq"]
    np.testing.assert_allclose(np.asarray(gc), np.asarray(gd),
                               rtol=2e-4, atol=2e-4)


def test_explicit_positions_route_position_masked_path():
    """forward(positions=arange) takes the explicit-position dispatch branch
    and must agree exactly with forward(positions=None) (fused-causal branch).
    Note position-based masking serves chunked prefill/decode; packed-document
    isolation needs segment ids (not yet supported)."""
    cfg = llama.tiny_config(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.key(4))
    tokens = jax.random.randint(jax.random.key(5), (2, 64), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
    np.testing.assert_allclose(
        np.asarray(llama.forward(params, tokens, cfg, positions=pos)),
        np.asarray(llama.forward(params, tokens, cfg)),
        rtol=1e-5, atol=1e-5)


def test_fused_kernel_gate_covers_llama_head_dims():
    """The TPU flash-kernel dispatch must engage for every Llama-family
    benchmarked config — round 1 shipped a gate requiring d % 128 == 0,
    which silently excluded head_dim=64 (Llama-1B) from the fused path."""
    from ray_tpu.models.llama import LLAMA3_1B, LLAMA3_8B, LLAMA3_70B
    from ray_tpu.ops.attention import use_fused_kernel

    for cfg in (LLAMA3_1B, LLAMA3_8B, LLAMA3_70B):
        assert use_fused_kernel(True, True, 2048, cfg.head_dim), cfg
    # Ragged/odd shapes still take the portable path.
    assert not use_fused_kernel(True, True, 2048 + 17, 64)
    assert not use_fused_kernel(True, True, 128, 64)      # too short
    assert not use_fused_kernel(True, False, 2048, 64)    # packed positions
    assert not use_fused_kernel(False, True, 2048, 64)    # CPU
    assert not use_fused_kernel(True, True, 2048, 192)    # unpadded mid dim


# ------------------------------------------------- what the remat keeps

REMAT_CASES = {"default": {}, "nothing": {"remat_policy": "nothing"},
               "no_remat": {"remat": False}}


@pytest.fixture
def flash_on_cpu(monkeypatch):
    """The flash kernels under the Pallas interpreter where the chip
    would run them (`ops/attention.py` asks the backend, and imports
    the kernels' entry at each call)."""
    from ray_tpu.ops import attention, flash_attention as kernels

    monkeypatch.setattr(attention, "use_fused_kernel",
                        lambda on_tpu, standard, sq, d: standard)
    monkeypatch.setattr(
        kernels, "flash_attention",
        lambda q, k, v, scale, blocks: kernels._flash_mha(
            q, k, v, scale, blocks, True))
    jax.clear_caches()
    yield
    jax.clear_caches()


def _remat_cfg(case: str) -> llama.LlamaConfig:
    return llama.tiny_config(d_model=128, n_heads=2, n_kv_heads=1,
                             max_seq_len=256, **{"remat": True,
                                                 **REMAT_CASES[case]})


def _loss_and_grad(cfg, differentiate=jax.value_and_grad):
    params = llama.init_params(cfg, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, 256), 0,
                                cfg.vocab_size)
    return differentiate(lambda p: llama.loss_fn(p, tokens, cfg)[0]), params


def test_default_policy_keeps_the_attention_names():
    assert llama.LlamaConfig().remat_policy == "attention"
    assert llama._SAVED == ("q_rope", "k_rope", "flash_out", "flash_lse")


@pytest.mark.parametrize("case", ["nothing", "no_remat"])
def test_what_the_remat_keeps_changes_no_bit(flash_on_cpu, case):
    """Saved and recomputed values come from the same operations: the
    loss and every gradient leaf of the default policy equal full
    recomputation's and no recomputation's."""
    f, params = _loss_and_grad(_remat_cfg("default"))
    loss, grads = jax.jit(f)(params)
    f, params = _loss_and_grad(_remat_cfg(case))
    other_loss, other = jax.jit(f)(params)
    assert jnp.isfinite(loss) and loss == other_loss
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), grads,
                        other)
    assert all(jax.tree.leaves(same)), same


@pytest.mark.parametrize("case,forwards", [("default", 1), ("nothing", 2)])
def test_forward_kernels_in_a_layers_gradient(flash_on_cpu, case, forwards):
    """The layers are one scanned body: under the default policy the
    gradient's program holds the forward kernel once (the forward
    pass's; the backward reads what was kept), under "nothing" twice."""
    f, params = _loss_and_grad(_remat_cfg(case), jax.grad)
    kernels = pallas_kernels(jax.make_jaxpr(f)(params).jaxpr)
    assert sorted(kernels) == sorted(
        ["_fwd_kernel"] * forwards + ["_dkv_kernel", "_dq_kernel"])


def test_unknown_remat_policy_is_refused_by_name():
    cfg = llama.tiny_config(remat=True, remat_policy="everything")
    f, params = _loss_and_grad(cfg)
    with pytest.raises(ValueError, match="unknown remat_policy 'everything'"):
        f(params)
