"""SPMD training-step construction: sharded init + jitted train step.

This is the TPU-native execution model replacing the reference's per-worker
torch DDP wiring (reference `train/_internal/backend_executor.py:69` +
`train/torch/config.py:94-163`): ONE compiled XLA program over a Mesh instead
of N processes exchanging NCCL messages. Gradient reductions and the fsdp
all-gathers/reduce-scatters are emitted by XLA from sharding annotations.
Two families of collectives are written by hand, as `ppermute` rings under
`shard_map`, because the partitioner emits them blocking: ring attention
over ``sp`` (`ops/ring_attention.py`) and a block's tensor-parallel sums
over ``tp``, which travel under the matmuls beside them
(`parallel/collective_matmul.py`; `models/llama.py:_block` enters it on the
mesh and the shapes alone). What is left to the partitioner over ``tp`` is
once a step: the embedding's vocab-sharded lookup, one gather of the final
hidden states, and the loss's reductions.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.devtools import jax_debug
from ray_tpu.models import llama
from ray_tpu.parallel.mesh import logical_spec, param_shardings
from ray_tpu.util import compile_cache


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Token batches: batch over dp+fsdp, sequence over sp."""
    return NamedSharding(mesh, P(("dp", "fsdp"), "sp"))


def _with_mesh_context(mesh: Mesh, fn):
    """Wrap a jitted callable so tracing always sees ``mesh`` as the ambient
    abstract mesh — `constrain()`'s PartitionSpec annotations then apply
    regardless of whether the caller entered `jax.sharding.set_mesh`."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return wrapped


def sharded_init(cfg: llama.LlamaConfig, mesh: Mesh, key: jax.Array,
                 tx: optax.GradientTransformation) -> TrainState:
    """Initialize params directly INTO their shards (no host-side full copy —
    required for models larger than one host's HBM)."""
    with compile_cache.phase("spmd.sharded_init"):
        shardings = param_shardings(mesh, llama.param_logical_axes(cfg))
        p_init = _with_mesh_context(mesh, jax.jit(
            functools.partial(llama.init_params, cfg),
            out_shardings=shardings))
        params = p_init(key)
        return TrainState(jnp.zeros((), jnp.int32), params,
                          init_opt_state(tx, params, mesh, shardings))


def init_opt_state(tx: optax.GradientTransformation, params: Any,
                   mesh: Mesh, shardings: Any) -> Any:
    """``tx.init(params)`` with every param-shaped leaf (Adam's moments)
    born in its param's sharding and the rest replicated. The zeros
    have no data dependence on the params, so XLA propagates nothing
    to them: left alone, each device would hold the WHOLE optimizer
    state, and the train step would recompile on its second call when
    its own (sharded) output comes back as input."""
    replicated = NamedSharding(mesh, P())
    out = optax.tree_map_params(
        tx, lambda _, sharding: sharding, jax.eval_shape(tx.init, params),
        shardings, transform_non_params=lambda _: replicated)
    return jax.jit(tx.init, out_shardings=out)(params)


def make_train_step(
    cfg: llama.LlamaConfig, mesh: Mesh, tx: optax.GradientTransformation,
) -> Callable[[TrainState, jnp.ndarray], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Returns jitted (state, tokens [B,S]) -> (state, metrics). Buffers are
    donated, so the step is in-place in HBM."""

    def step_fn(state: TrainState, tokens: jnp.ndarray):
        (loss, metrics), grads = jax.value_and_grad(
            llama.loss_fn, has_aux=True)(state.params, tokens, cfg, mesh=mesh)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        metrics = dict(metrics, grad_norm=gnorm)
        return TrainState(state.step + 1, params, opt_state), metrics

    # Budget 1: a steady-state trainer compiles its step ONCE — a
    # recompile per step (shape churn, structure churn from a stray
    # python scalar in the state) is the most expensive silent bug a
    # training loop can have. The RTPU_DEBUG_JAX witness reports it;
    # off, wrap_jit returns the jitted step untouched.
    with compile_cache.phase("spmd.make_train_step"):
        return _with_mesh_context(mesh, jax_debug.wrap_jit(
            jax.jit(step_fn, donate_argnums=(0,)), "spmd.train_step",
            budget=1))


def make_eval_step(cfg: llama.LlamaConfig, mesh: Mesh):
    def eval_fn(params, tokens):
        loss, metrics = llama.loss_fn(params, tokens, cfg, mesh=mesh)
        return metrics
    with compile_cache.phase("spmd.make_eval_step"):
        return _with_mesh_context(mesh, jax_debug.wrap_jit(
            jax.jit(eval_fn), "spmd.eval_step", budget=1))


def sharding_summary(params: Any, logical_tree: Any) -> Dict[str, str]:
    """Flat ``{param path: "logical names -> PartitionSpec @ shard
    shape"}`` map for dryrun/debug output — the human-readable view of
    where every weight actually lives on the mesh."""
    flat_p = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: hasattr(x, "sharding"))[0]
    flat_l = jax.tree_util.tree_flatten_with_path(
        logical_tree, is_leaf=lambda x: isinstance(x, tuple))[0]
    if len(flat_p) != len(flat_l):
        raise ValueError(
            f"params tree has {len(flat_p)} leaves but logical tree has "
            f"{len(flat_l)} — structures diverge (quantized trees and "
            "extra keys are not summarizable)")
    out: Dict[str, str] = {}
    for (path, leaf), (_, names) in zip(flat_p, flat_l):
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        shard_shape = getattr(
            leaf.sharding, "shard_shape", lambda s: s)(leaf.shape)
        out[key] = (f"{names} -> {logical_spec(names)} "
                    f"@ {tuple(shard_shape)}")
    return out


def assert_params_sharded(params: Any, mesh: Mesh, logical_tree: Any,
                          ) -> None:
    """Verify every param leaf carries EXACTLY the NamedSharding its
    logical axis names prescribe — the "is the 2D story real" check the
    MULTICHIP dryrun and the CPU multi-device test both run. Raises
    AssertionError naming the first offending leaf."""
    expected = param_shardings(mesh, logical_tree)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_e = jax.tree_util.tree_flatten_with_path(
        expected, is_leaf=lambda x: isinstance(x, NamedSharding))[0]
    # A silent zip truncation would let leaves after a structure
    # divergence go unchecked — in the function whose job is checking.
    assert len(flat_p) == len(flat_e), (
        f"params tree has {len(flat_p)} leaves but the logical tree "
        f"prescribes {len(flat_e)} — structures diverge")
    for (path, leaf), (_, want) in zip(flat_p, flat_e):
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        got = getattr(leaf, "sharding", None)
        assert got is not None, f"{key}: leaf has no sharding"
        ok = got.is_equivalent_to(want, leaf.ndim) \
            if hasattr(got, "is_equivalent_to") else got == want
        assert ok, f"{key}: sharding {got} != expected {want}"
        # And the shards really are smaller than the array on >1-way axes.
        shard = got.shard_shape(leaf.shape)
        want_shard = want.shard_shape(leaf.shape)
        assert tuple(shard) == tuple(want_shard), (
            f"{key}: shard shape {shard} != expected {want_shard}")


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, decay_steps: int = 10000,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, warmup, decay_steps,
                                               end_value=lr * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )
