"""The plain reference against the system's own model at a tiny size:
tied and untied heads, grouped-query and multi-head attention; and the
first run of ``tie_embeddings`` through the sharded train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import dense_llama
from benchmark.reference import dense_decoder
from ray_tpu.models import llama


def _config(tied, kv_heads):
    return {"hidden_size": 64, "intermediate_size": 160,
            "num_attention_heads": 4, "num_key_value_heads": kv_heads,
            "num_hidden_layers": 3, "vocab_size": 212,
            "max_position_embeddings": 64, "rope_theta": 10000.0,
            "rms_norm_eps": 1e-5, "tie_word_embeddings": tied,
            "torch_dtype": "float32"}


def _params(cfg, seed=0):
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    # The system starts its norm gains at the stored offset 0; move
    # them so that the (1 + stored) convention is really compared.
    bump = lambda k, a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(k),
                                                    a.shape)
    params["blocks"]["ln_attn"] = bump(1, params["blocks"]["ln_attn"])
    params["blocks"]["ln_mlp"] = bump(2, params["blocks"]["ln_mlp"])
    params["ln_out"] = bump(3, params["ln_out"])
    return params


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_reference_agrees_with_the_system(tied, kv_heads):
    config = _config(tied, kv_heads)
    cfg = dense_llama.config(config, remat=False)
    assert cfg.tie_embeddings == tied and cfg.n_kv_heads == kv_heads
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 33), 0, 212)
    with jax.default_matmul_precision("highest"):
        want = llama.forward(params, tokens, cfg)
        want_loss, _ = llama.loss_fn(params, tokens, cfg)
    rows = [(0, 0), (0, 32), (1, 7), (1, 31)]
    got = dense_decoder.logits_at(params, tokens, rows, config)
    np.testing.assert_allclose(
        got, np.stack([want[b, t] for b, t in rows]), rtol=2e-4, atol=2e-4)
    assert float(dense_decoder.loss(params, tokens, config)) == pytest.approx(
        float(want_loss), rel=1e-5)


def test_reference_is_causal():
    config = _config(False, 2)
    params = _params(dense_llama.config(config))
    a = jnp.arange(20, dtype=jnp.int32)[None] % 212
    b = a.at[0, 15:].set(3)                      # change only the tail
    la = dense_decoder.logits_at(params, a, [(0, 14), (0, 19)], config)
    lb = dense_decoder.logits_at(params, b, [(0, 14), (0, 19)], config)
    np.testing.assert_allclose(la[0], lb[0], rtol=1e-6)   # the past is blind
    assert not np.allclose(la[1], lb[1])


def test_tied_embeddings_through_the_sharded_step():
    """fsdp=2 x tp=2 on four virtual devices against the one-device
    step, tied head: the configuration of the train cell."""
    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import mesh_2d, mesh_context, single_device_mesh

    if jax.device_count() < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = dense_llama.config(_config(True, 4))
    tx = spmd.default_optimizer(lr=1e-3, warmup=1)
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0, 212), np.int32)

    def losses(mesh):
        with mesh_context(mesh):
            state = spmd.sharded_init(cfg, mesh, jax.random.PRNGKey(0), tx)
            step = spmd.make_train_step(cfg, mesh, tx)
            placed = jax.device_put(tokens, spmd.data_sharding(mesh))
            out = []
            for _ in range(3):
                state, m = step(state, placed)
                out.append((float(m["loss"]), float(m["grad_norm"])))
        return np.array(out)

    one = losses(single_device_mesh(jax.devices()[0]))
    four = losses(mesh_2d(4, tp=2, devices=jax.devices()[:4]))
    assert np.isfinite(four).all() and four[2, 0] < four[0, 0]
    np.testing.assert_allclose(four, one, rtol=2e-5)
