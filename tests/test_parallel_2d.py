"""The 2D (FSDP x tensor) sharding story on the forced-8-device CPU
mesh: `mesh_2d` builds the production training mesh, the logical-axis
tables place every Llama weight, `assert_params_sharded` proves the
placement is real (not silently replicated), and the sharded train step
computes the SAME loss as an unsharded single-device step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel import spmd
from ray_tpu.parallel.mesh import (MeshSpec, make_mesh, mesh_2d,
                                   mesh_context, param_shardings)


@pytest.fixture(scope="module")
def cfg():
    return llama.tiny_config(n_heads=4, n_kv_heads=2, d_ff=128)


def test_mesh_2d_shape_and_defaults():
    devs = jax.devices("cpu")[:8]
    m = mesh_2d(8, tp=2, devices=devs)
    assert m.shape["fsdp"] == 4 and m.shape["tp"] == 2
    assert all(m.shape[a] == 1 for a in ("dp", "sp", "pp", "ep"))
    # Default tp: largest pow2 <= min(8, n) dividing n.
    assert mesh_2d(8, devices=devs).shape["tp"] == 8
    assert mesh_2d(4, devices=devs).shape["tp"] == 4
    assert mesh_2d(1, devices=devs).shape == {
        "dp": 1, "fsdp": 1, "sp": 1, "pp": 1, "ep": 1, "tp": 1}
    with pytest.raises(ValueError):
        mesh_2d(8, tp=3, devices=devs)
    with pytest.raises(ValueError):
        mesh_2d(16, devices=devs)


def test_params_land_2d_sharded(cfg):
    """Every leaf carries exactly its table-prescribed NamedSharding,
    and the tp x fsdp split shows up in real shard shapes."""
    mesh = mesh_2d(8, tp=2, devices=jax.devices("cpu")[:8])
    tx = spmd.default_optimizer(lr=1e-3)
    with mesh_context(mesh):
        state = spmd.sharded_init(cfg, mesh, jax.random.key(0), tx)
    logical = llama.param_logical_axes(cfg)
    spmd.assert_params_sharded(state.params, mesh, logical)
    # w_gate [L, d->fsdp, f->tp]: each device holds a (L, d/4, f/2) tile.
    w = state.params["blocks"]["w_gate"]
    l, d, f = w.shape
    assert w.sharding.shard_shape(w.shape) == (l, d // 4, f // 2)
    # wq [L, d->fsdp, h->tp, hd]: heads split over tp, head_dim whole.
    wq = state.params["blocks"]["wq"]
    assert wq.sharding.shard_shape(wq.shape) == (
        cfg.n_layers, cfg.d_model // 4, cfg.n_heads // 2, cfg.head_dim)
    # The summary is a readable map covering every leaf.
    summary = spmd.sharding_summary(state.params, logical)
    assert "blocks/w_gate" in summary
    assert "PartitionSpec" in summary["blocks/w_gate"]


def test_assert_params_sharded_catches_replication(cfg):
    """A fully-replicated tree must FAIL the check — the guard guards."""
    mesh = mesh_2d(8, tp=2, devices=jax.devices("cpu")[:8])
    params = llama.init_params(cfg, jax.random.key(0))  # unsharded host
    with pytest.raises(AssertionError):
        spmd.assert_params_sharded(params, mesh,
                                   llama.param_logical_axes(cfg))


def test_2d_train_step_matches_single_device_loss(cfg):
    """Sharding is a layout, not an approximation: one train step on the
    fsdp=4 x tp=2 mesh reports the same loss as the unsharded step on
    the same params and batch."""
    tokens_np = np.asarray(
        jax.random.randint(jax.random.key(1), (4, 32), 0,
                           cfg.vocab_size), np.int32)
    params0 = llama.init_params(cfg, jax.random.key(0))
    loss_ref = float(jax.jit(
        lambda p, t: llama.loss_fn(p, t, cfg)[0])(
        params0, jnp.asarray(tokens_np)))

    mesh = mesh_2d(8, tp=2, devices=jax.devices("cpu")[:8])
    tx = spmd.default_optimizer(lr=1e-3)
    with mesh_context(mesh):
        p2 = jax.device_put(params0, param_shardings(
            mesh, llama.param_logical_axes(cfg)))
        state = spmd.TrainState(jnp.zeros((), jnp.int32), p2,
                                jax.jit(tx.init)(p2))
        step = spmd.make_train_step(cfg, mesh, tx)
        tokens = jax.device_put(jnp.asarray(tokens_np),
                                spmd.data_sharding(mesh))
        state, metrics = step(state, tokens)
        loss_2d = float(metrics["loss"])
        state, metrics = step(state, tokens)
    assert int(state.step) == 2
    assert np.isfinite(float(metrics["loss"]))
    np.testing.assert_allclose(loss_2d, loss_ref, rtol=2e-4)
    # Updated params keep their 2D placement across steps (donated
    # buffers must not decay to replicated).
    spmd.assert_params_sharded(state.params, mesh,
                               llama.param_logical_axes(cfg))


def test_sharded_init_shards_optimizer_state_and_step_compiles_once(cfg):
    """Adam's moments are born in their param's sharding: zeros carry
    no data dependence, so XLA would otherwise leave the WHOLE optimizer
    state on every device and the step — whose own output is sharded —
    would compile a second program on its second call."""
    mesh = mesh_2d(8, tp=2, devices=jax.devices("cpu")[:8])
    tx = spmd.default_optimizer(lr=1e-3)
    with mesh_context(mesh):
        state = spmd.sharded_init(cfg, mesh, jax.random.key(0), tx)
        step = spmd.make_train_step(cfg, mesh, tx)
        tokens = jax.device_put(jnp.zeros((4, 32), jnp.int32),
                                spmd.data_sharding(mesh))
        logical = llama.param_logical_axes(cfg)
        adam = state.opt_state[1][0]
        spmd.assert_params_sharded(adam.mu, mesh, logical)
        spmd.assert_params_sharded(adam.nu, mesh, logical)
        before = jax.tree.map(lambda x: x.sharding, state)
        state, _ = step(state, tokens)
        after = jax.tree.map(lambda x: x.sharding, state)
        assert jax.tree.all(jax.tree.map(
            lambda a, b, x: a.is_equivalent_to(b, x.ndim),
            before, after, state))


def test_data_sharding_splits_batch_over_fsdp():
    mesh = mesh_2d(8, tp=2, devices=jax.devices("cpu")[:8])
    sh = spmd.data_sharding(mesh)
    assert sh.shard_shape((8, 32)) == (2, 32)  # batch/4 over fsdp, tp replicated


def test_2d_mesh_with_explicit_meshspec_equivalent():
    """mesh_2d is sugar over MeshSpec — same device placement."""
    devs = jax.devices("cpu")[:8]
    a = mesh_2d(8, tp=2, devices=devs)
    b = make_mesh(MeshSpec(fsdp=4, tp=2), devs)
    assert a.devices.tolist() == b.devices.tolist()
    assert a.axis_names == b.axis_names
