"""The 2D (FSDP x tensor) sharding story on the forced-8-device CPU
mesh: `mesh_2d` builds the production training mesh, the logical-axis
tables place every Llama weight, `assert_params_sharded` proves the
placement is real (not silently replicated), and the sharded train step
computes the SAME loss as an unsharded single-device step — also where
its tensor-parallel sums travel as collective matmuls
(`parallel/collective_matmul.py`), which engage on the mesh and the
shapes alone."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel import collective_matmul, spmd
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


# name -> (mesh, sequence length, against a cache, config overrides,
#          whether the ring engages)
TP_RING_CASES = {
    "fsdp4_tp2": (MeshSpec(fsdp=4, tp=2), 32, False, {}, True),
    "fsdp2_tp4": (MeshSpec(fsdp=2, tp=4), 32, False, {}, True),  # 3 hops
    "fsdp2_sp2_tp2": (MeshSpec(fsdp=2, sp=2, tp=2), 32, False, {}, True),
    "sequence_not_divisible_by_tp": (
        MeshSpec(fsdp=4, tp=2), 31, False, {}, False),
    "with_a_cache": (MeshSpec(fsdp=4, tp=2), 32, True, {}, False),
    "tp_1": (MeshSpec(fsdp=4, dp=2), 32, False, {}, False),
    # Pallas calls cannot be traced inside the ring's shard_map.
    "fused_ops": (MeshSpec(fsdp=4, tp=2), 32, False,
                  {"fused_ops": "interpret"}, False),
}


def _jaxpr_text(mesh, fn, *args) -> str:
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        # A fresh function a trace: tracing caches on identity.
        text = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    return re.sub(r" at 0x[0-9a-f]+", "", text)


@pytest.mark.parametrize("case", TP_RING_CASES)
def test_tp_ring_engages_on_mesh_and_shapes_alone(case, monkeypatch,
                                                  cpu_mesh8):
    """Where tp > 1, no cache and a sequence that divides, the block's
    four matmul groups move their own shards (`ppermute` in the jaxpr).
    Everywhere else the jaxpr is, letter for letter, the one traced with
    the ring switched off: what the serving cells compile does not move
    by hope. Engaged or not, loss and EVERY gradient leaf of the sharded
    step are the single device's, remat on. (`cpu_mesh8`: the sharded
    step must not be LOADED from a persistent compile cache that another
    test of the worker switched on; a whole run lost a worker here.)"""
    spec, seq, cached, overrides, engaged = TP_RING_CASES[case]
    cfg = llama.tiny_config(n_heads=4, n_kv_heads=4, d_ff=128, remat=True,
                            **overrides)
    mesh = make_mesh(spec, cpu_mesh8)
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, seq), 0,
                                cfg.vocab_size)
    if cached:
        cache = llama.init_kv_cache(cfg, 8, 64)

        def fn(p, t):
            return llama.forward_with_cache(p, t, cache, 0, cfg)
    else:
        def fn(p, t):
            return jax.value_and_grad(
                lambda p: llama.loss_fn(p, t, cfg, mesh=mesh)[0])(p)

    traced = _jaxpr_text(mesh, fn, params, tokens)
    with monkeypatch.context() as mp:
        mp.setattr(collective_matmul, "ring_size", lambda *a: 1)
        plain = _jaxpr_text(mesh, fn, params, tokens)
    hops = re.compile(r"ppermute\[\s*axis_name=\('tp',\)")
    assert not hops.search(plain)
    assert bool(hops.search(traced)) == engaged
    if not engaged:
        assert traced == plain
    if cached:
        return

    loss_ref, grads_ref = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, cfg)[0]))(params)
    tx = spmd.default_optimizer(lr=1e-3)
    with mesh_context(mesh):
        p2 = jax.device_put(params, param_shardings(
            mesh, llama.param_logical_axes(cfg)))
        t2 = jax.device_put(tokens, spmd.data_sharding(mesh))
        loss, grads = jax.jit(fn)(p2, t2)
        state = spmd.TrainState(jnp.zeros((), jnp.int32), p2,
                                jax.jit(tx.init)(p2))
        _, metrics = spmd.make_train_step(cfg, mesh, tx)(state, t2)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=2e-4)
    np.testing.assert_allclose(float(metrics["loss"]), float(loss_ref),
                               rtol=2e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4,
            atol=2e-4 * float(jnp.max(jnp.abs(want))),
            err_msg=jax.tree_util.keystr(path))


def test_collective_matmul_ring_of_eight_matches_einsum():
    """The two functions themselves on the longest ring the CPU mesh
    has (tp = 8, seven hops): a row-wise epilogue fed the rows' own
    positions, one group assembled in sequence order, one handed to
    `matmul_scatter` in ring order; values and every gradient are the
    plain einsums'."""
    mesh = make_mesh(MeshSpec(tp=8), jax.devices("cpu")[:8])
    keys = jax.random.split(jax.random.key(0), 4)
    h = jax.random.normal(keys[0], (2, 32, 16))
    wa = jax.random.normal(keys[1], (16, 8, 4))
    wb = jax.random.normal(keys[2], (16, 8, 4))
    wo = jax.random.normal(keys[3], (8, 4, 16))
    pos = jnp.broadcast_to(jnp.arange(32.0), (2, 32))

    def rowwise(ab, pos):
        a, b = ab
        return (jnp.tanh(a) * b + pos[..., None, None],)

    def ring(h, wa, wb, wo):
        (seq,) = collective_matmul.gather_matmul(
            "bsd,dhk->bshk", h, (wa, wb), rowwise=rowwise, row_args=(pos,))
        (blocks,) = collective_matmul.gather_matmul(
            "bsd,dhk->bshk", h, (wa, wb), rowwise=rowwise, row_args=(pos,),
            in_sequence=False)
        assert len(blocks) == 8 and blocks[0].shape == (2, 4, 8, 4)
        return (collective_matmul.matmul_scatter("bshk,hkd->bsd", seq, wo)
                + collective_matmul.matmul_scatter("bshk,hkd->bsd", blocks,
                                                   wo))

    def plain(h, wa, wb, wo):
        (y,) = rowwise((jnp.einsum("bsd,dhk->bshk", h, wa),
                        jnp.einsum("bsd,dhk->bshk", h, wb)), pos)
        return 2 * jnp.einsum("bshk,hkd->bsd", y, wo)

    def check(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3))

    with mesh_context(mesh):
        got = jax.jit(check(ring))(*jax.device_put(
            (h, wa, wb, wo), jax.NamedSharding(mesh, jax.P())))
    want = check(plain)(h, wa, wb, wo)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(w))))


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
