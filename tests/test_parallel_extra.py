"""Pipeline parallelism (pp) and MoE expert parallelism (ep) — the two
mesh axes declared in parallel/mesh.py, exercised on the 8-CPU mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, mixtral
from ray_tpu.parallel import pipeline, spmd
from ray_tpu.parallel.mesh import (MeshSpec, make_mesh, mesh_context,
                                   param_shardings)


@pytest.fixture(scope="module")
def pp2_mesh():
    return make_mesh(MeshSpec(pp=2, fsdp=2, tp=2), jax.devices("cpu")[:8])


def test_pipeline_matches_dense_forward(pp2_mesh):
    """GPipe is a schedule, not an approximation: same weights => same
    loss as the plain sequential forward."""
    cfg = llama.tiny_config(n_layers=4)
    key = jax.random.PRNGKey(0)
    params = llama.init_params(cfg, key)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 32)),
        jnp.int32)

    dense_loss, _ = jax.jit(
        lambda p, t: llama.loss_fn(p, t, cfg))(params, tokens)

    pcfg = pipeline.PipelineConfig(stages=2, microbatches=4)
    staged = pipeline.stage_params(params, 2)
    with mesh_context(pp2_mesh):
        pipe_loss, _ = jax.jit(
            lambda p, t: pipeline.pipeline_loss_fn(p, t, cfg, pcfg,
                                                   mesh=pp2_mesh))(
            staged, tokens)
    np.testing.assert_allclose(float(pipe_loss), float(dense_loss),
                               rtol=2e-4)


@pytest.mark.slow  # tier-1 budget relief (PR 12): 26.5s measured on a quiet box;
# convergence smoke — pipeline step shape/math stays tier-1
def test_pipeline_train_step_decreases_loss(pp2_mesh):
    cfg = llama.tiny_config(n_layers=4)
    pcfg = pipeline.PipelineConfig(stages=2, microbatches=4)
    tx = spmd.default_optimizer(lr=5e-3, warmup=0, decay_steps=100)
    with mesh_context(pp2_mesh):
        params = pipeline.stage_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)), 2)
        shardings = param_shardings(
            pp2_mesh, pipeline.pipeline_param_logical_axes(cfg))
        params = jax.device_put(params, shardings)
        state = spmd.TrainState(jnp.zeros((), jnp.int32), params,
                                jax.jit(tx.init)(params))
        step = pipeline.make_pipeline_train_step(cfg, pcfg, pp2_mesh, tx)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab_size, (4, 32)),
            jnp.int32)
        losses = []
        for _ in range(8):
            state, metrics = step(state, tokens)
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_pipeline_stage_body_differentiates_under_the_default_policy(
        pp2_mesh):
    """The stage body takes `LlamaConfig.remat_policy`'s default through
    `llama._remat_policy`: keeping the named q and k under the rotor's
    `vmap` changes no gradient."""
    pcfg = pipeline.PipelineConfig(stages=2, microbatches=4)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 256, (4, 32)), jnp.int32)

    def grads(**remat):
        cfg = llama.tiny_config(n_layers=4, **remat)
        staged = pipeline.stage_params(
            llama.init_params(cfg, jax.random.PRNGKey(0)), 2)
        with mesh_context(pp2_mesh):
            return jax.jit(jax.grad(
                lambda p: pipeline.pipeline_loss_fn(
                    p, tokens, cfg, pcfg, mesh=pp2_mesh)[0]))(staged)

    assert llama.tiny_config(remat=True).remat_policy == "attention"
    kept, plain = grads(remat=True), grads(remat=False)
    for name, g in kept["blocks"].items():
        assert jnp.isfinite(g).all() and jnp.any(g != 0), name
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-6), kept, plain)


def test_pipeline_validation_errors():
    cfg = llama.tiny_config(n_layers=4)
    with pytest.raises(ValueError, match="not divisible"):
        pipeline.PipelineConfig(3, 4).validate(cfg, 8)
    with pytest.raises(ValueError, match="microbatches >= stages"):
        pipeline.PipelineConfig(2, 1).validate(cfg, 2)


# ---------------------------------------------------------------- mixtral

def test_moe_capacity_dispatch_math():
    """Under-capacity regime: the dispatched FFN must equal the dense
    gate-weighted mixture of expert FFNs."""
    cfg = mixtral.tiny_moe_config(capacity_factor=8.0)  # no drops
    key = jax.random.PRNGKey(0)
    params = mixtral.init_params(cfg, key)
    layer0 = jax.tree_util.tree_map(lambda v: v[0], params["blocks"])
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, cfg.d_model),
                          jnp.float32)

    out, aux = mixtral.moe_ffn(x, layer0, cfg)

    # Dense reference: run every expert on every token; combine by gates.
    xt = x.reshape(-1, cfg.d_model)
    logits = xt @ layer0["w_router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gv, gi = jax.lax.top_k(probs, cfg.top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    dense = np.zeros_like(xt)
    for e in range(cfg.n_experts):
        h = np.asarray(jax.nn.silu(xt @ layer0["w_gate"][e])
                       * (xt @ layer0["w_up"][e]) @ layer0["w_down"][e])
        for k in range(cfg.top_k):
            sel = np.asarray(gi[:, k] == e)
            dense[sel] += np.asarray(gv[:, k])[sel, None] * h[sel]
    np.testing.assert_allclose(np.asarray(out).reshape(-1, cfg.d_model),
                               dense, rtol=2e-4, atol=2e-5)
    assert float(aux) > 0


def test_moe_overflow_drops_are_bounded():
    """capacity_factor=0 (degenerate) still keeps top_k slots per expert;
    dropped tokens contribute zero (residual carries them)."""
    cfg = mixtral.tiny_moe_config(capacity_factor=0.01)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    layer0 = jax.tree_util.tree_map(lambda v: v[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.float32)
    out, _ = mixtral.moe_ffn(x, layer0, cfg)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow  # tier-1 budget relief (PR 12): 17.7s measured on a quiet box;
# EP-mesh train smoke — MoE dispatch math stays tier-1
def test_mixtral_train_step_ep_mesh():
    """End-to-end MoE training over an ep-sharded mesh."""
    import optax

    mesh = make_mesh(MeshSpec(ep=4, fsdp=2), jax.devices("cpu")[:8])
    cfg = mixtral.tiny_moe_config()
    tx = optax.adam(3e-3)
    with mesh_context(mesh):
        shardings = param_shardings(mesh, mixtral.param_logical_axes(cfg))
        params = jax.device_put(
            mixtral.init_params(cfg, jax.random.PRNGKey(0)), shardings)
        opt_state = jax.jit(tx.init)(params)

        @jax.jit
        def step(params, opt_state, tokens):
            (loss, metrics), grads = jax.value_and_grad(
                mixtral.loss_fn, has_aux=True)(params, tokens, cfg,
                                               mesh=mesh)
            updates, opt_state = tx.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, metrics

        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 32)),
            jnp.int32)
        losses = []
        for _ in range(8):
            params, opt_state, metrics = step(params, opt_state, tokens)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_mixtral_active_params_fraction():
    cfg = mixtral.MIXTRAL_8X7B
    total, active = cfg.param_count(), cfg.active_param_count()
    # 8x7B: ~47B total, ~13B active — the sparse-compute signature.
    assert total / active > 3.0
