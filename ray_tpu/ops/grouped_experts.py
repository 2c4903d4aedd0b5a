"""The grouped product of a dropless expert layer, for every routed
family (``models/glm_moe_lite.py``, ``models/zaya.py``,
``models/dots3_note.py``): each token's
chosen experts are given, the (token, expert) pairs are sorted by
expert and each group multiplied by its own expert's SwiGLU matrices
with ``jax.lax.ragged_dot`` (the chip's compiler has a grouped-matmul
kernel for it; elsewhere it is a masked dense product, fine at test
sizes). No capacity: no pair is dropped however skewed the routing.
What a family keeps for itself is its router (`route`: which experts,
with what weights) and whatever it adds to the sum (a shared expert, a
scaling factor). A chip that holds a SHARE of a layer's experts (expert
parallelism, its one-chip half) says which (``held``): the router keeps
its width, pairs on absent experts go to no group.
"""

from __future__ import annotations

import jax
from jax import lax
import jax.numpy as jnp

EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def expert_stacks(layers) -> dict:
    """The expert layers' matrices ``[layers, E, ..]`` as ONE run of
    groups, ``[layers * E, ..]``: a free view of the stacked parameters.
    The grouped product takes the whole of it and finds a layer's
    experts by their group sizes (all other groups are empty), so no
    layer's gigabyte is sliced out of the stack first: a slice feeding
    a kernel is a copy, and at decode those copies took more of the
    step than everything else in it (v5e trace, PR 29). The layer scans
    close over this, and scan the rest."""
    return {k: layers[k].reshape((-1,) + layers[k].shape[2:])
            for k in EXPERT_STACKS}


def split_expert_stacks(layers):
    """A stack of expert layers' parameters as (what the grouped
    products read whole, `expert_stacks`; what a layer scan slices a
    layer at a time: everything else)."""
    return expert_stacks(layers), {k: v for k, v in layers.items()
                                   if k not in EXPERT_STACKS}


def grouped_swiglu(x, experts, stacks, layer_idx, n_experts: int,
                   valid=None, held=None):
    """x [T, d], experts [T, k] int32 (each token's chosen experts of
    expert layer ``layer_idx``, among the router's ``n_experts``),
    ``stacks`` every expert layer's experts (`expert_stacks`) -> (y
    [T, k, d]: each pair's expert applied to its token, load [E] int32:
    each group's size).

    ``valid`` [T] (a prefill bucket's real tokens) keeps padding out of
    every group: such pairs sort last, past the groups' total, and
    their rows are zeroed.

    ``held`` = (first, count): this chip's share of an expert-parallel
    layer. The stacks hold experts ``first .. first + count - 1`` of
    every layer and no others; the router still ranks all ``n_experts``
    and a pair on an absent expert goes to no group, as padding does
    (its row comes back zero: what the absent chips would add is left
    out, no code stands in for them). ``load`` is then over the
    ``count`` held experts. Absent, every expert is held."""
    t, d = x.shape
    k, e = experts.shape[1], n_experts
    flat = experts.reshape(t * k)
    if held is not None:
        first, e = held
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    load = jnp.sum(flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :],
                   axis=0, dtype=jnp.int32)
    n_groups = stacks["w_gate"].shape[0]
    # layer_idx < n_groups / e by construction (the scan's own index).
    sizes = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        jnp.zeros((n_groups,), jnp.int32), load, (layer_idx * e,))
    xs = jnp.take(x, order // k, axis=0)                     # [T*k, d]
    hidden = (jax.nn.silu(lax.ragged_dot(xs, stacks["w_gate"], sizes))
              * lax.ragged_dot(xs, stacks["w_up"], sizes))
    ys = lax.ragged_dot(hidden, stacks["w_down"], sizes)     # [T*k, d]
    if valid is not None or held is not None:
        ys = jnp.where((jnp.take(flat, order) < e)[:, None], ys, 0)
    back = jnp.argsort(order)                # pair i sits at row back[i]
    return jnp.take(ys, back, axis=0).reshape(t, k, d), load
