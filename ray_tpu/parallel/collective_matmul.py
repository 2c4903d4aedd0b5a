"""Collective matmul over ``tp``: a tensor-parallel block's transfers
travel under the products beside them.

Megatron-style ``tp`` left to the partitioner costs a block two blocking
all-reduces of the residual stream forward and three backward: nothing
independent exists to run beside them, because the next norm needs the
whole sum. Here the residual stream, its norms and its additions live on
a SEQUENCE shard of ``tp`` (logical name ``res_seq``) between a block's
matmul groups, and each group moves the other shards itself, one ring
hop at a time, while it multiplies the shard it has:

- `gather_matmul` enters a column-parallel group (q/k/v; gate/up):
  all-gather ∘ matmul. The local rows are multiplied while `ppermute`
  brings the next shard; after ``tp - 1`` hops every row has been.
- `matmul_scatter` leaves a row-parallel group (``wo``; ``w_down``):
  matmul ∘ reduce-scatter. The partial product of the shard that must
  travel furthest comes first and is sent, the next is computed while it
  travels and added on arrival; the own shard's comes last.

The same bytes cross the link in the same dtype as the all-reduce's
halves did; JAX's transposition gives the backward the same shape (the
transpose of a `ppermute` is a `ppermute`). Only ``tp`` is manual under
the `shard_map`s: the fsdp all-gathers of the weights and the batch
split stay the partitioner's.

A device meets the shards in RING order (its own, then its left
neighbour's, ...), which is another order on every device. Putting the
products back into sequence order is a pass over the block's largest
arrays (measured on a v5e: 10 % of a train step when every group did
it), so what treats rows alike can be applied shard by shard behind
the product (``rowwise``: SwiGLU), a group's results can go to
`matmul_scatter` in ring order as they are, and only attention gets the
true order. Behind the product is not free by itself: rotary embedding
as a ``rowwise`` of plain XLA cost q and k ten times their bytes' time
at head size 64 (half a lane tile: widened, split, joined), and the
chip showed it cheaper to write q's and k's products with a row's heads
side by side, let the ring assemble them (0.24 ms an array of 67 MB)
and rotate both in ONE Pallas call on the assembled sequence (0.35 ms;
`models/llama.py` ``_rope_kernel``, PERF.md section 6, PR 63): a
Mosaic call cannot be traced inside the ring's partly manual
`shard_map`, so it sits in one of its own, manual over every axis.

`ring_size` says whether a caller's shapes engage it, from the ambient
mesh alone: there is no option to set.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

AXIS = "tp"


def ring_size(seq_len: int, *split_dims: int) -> int:
    """``tp`` where the ring engages, else 1: the ambient mesh has
    ``tp > 1`` that the partitioner still owns, the sequence divides by
    ``sp x tp``, and so does every dimension the weights split over
    ``tp``."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or AXIS in mesh.manual_axes:
        return 1
    tp, sp = mesh.shape.get(AXIS, 1), mesh.shape.get("sp", 1)
    if tp == 1 or seq_len % (sp * tp) or any(d % tp for d in split_dims):
        return 1
    return tp


def _shard_index(hops: int):
    """Whose rows a device holds after ``hops`` ring hops."""
    return (lax.axis_index(AXIS) - hops) % lax.axis_size(AXIS)


def _rows_of_shard(y, hops: int):
    """The rows [B, S / n, ...] of the shard a device holds after
    ``hops`` ring hops, out of ``y`` [B, S, ...] in sequence order."""
    rows = y.shape[1] // lax.axis_size(AXIS)
    return lax.dynamic_slice_in_dim(y, _shard_index(hops) * rows, rows,
                                    axis=1)


@jax.custom_vjp
def _ring_to_seq(*chunks):
    """``chunks[t]`` holds the rows of shard ``(i - t) % n`` on device
    ``i`` -> their concatenation in sequence order: one static
    concatenation in reversed ring order, rolled by ``i`` chunks (a
    dynamic slice of the doubled list, which fuses into its consumer).
    Its transpose is `_seq_to_ring`, told to JAX: the one derived from a
    dynamic slice pads into zeros and adds, passes over the largest
    arrays of the block."""
    n, rows = len(chunks), chunks[0].shape[1]
    rev = [chunks[-j % n] for j in range(n)]
    doubled = jnp.concatenate(rev + rev[:-1], axis=1)
    return lax.dynamic_slice_in_dim(
        doubled, -lax.axis_index(AXIS) % n * rows, n * rows, axis=1)


@jax.custom_vjp
def _seq_to_ring(y):
    """The inverse: rows in sequence order -> the ``n`` chunks in ring
    order, ``chunks[t]`` the rows of shard ``(i - t) % n``."""
    return tuple(_rows_of_shard(y, t) for t in range(lax.axis_size(AXIS)))


_ring_to_seq.defvjp(lambda *chunks: (_ring_to_seq(*chunks), None),
                    lambda _, ct: _seq_to_ring(ct))
_seq_to_ring.defvjp(lambda y: (_seq_to_ring(y), None),
                    lambda _, cts: (_ring_to_seq(*cts),))


def _hop(x):
    n = lax.axis_size(AXIS)
    return lax.ppermute(x, AXIS, [(j, (j + 1) % n) for j in range(n)])


def _gather_matmul_local(eqn: str, rowwise, in_sequence: bool, h, ws,
                         row_args):
    n = lax.axis_size(AXIS)
    parts = []
    for t in range(n):
        nxt = _hop(h) if t + 1 < n else None
        # The barrier's transpose keeps the backward's products for
        # these rows an operation of their own: fused with the addition
        # of the cotangent that arrives over the ring, they would wait
        # for the transfer they are there to hide.
        rows = lax.optimization_barrier(h)
        outs = tuple(jnp.einsum(eqn, rows, w) for w in ws)
        if rowwise is not None:
            outs = rowwise(outs, *[_rows_of_shard(a, t) for a in row_args])
        parts.append(outs)
        h = nxt
    outs = tuple(zip(*parts))       # per product, its chunks in ring order
    return tuple(_ring_to_seq(*c) for c in outs) if in_sequence else outs


def _matmul_scatter_local(eqn: str, w, chunks):
    n = lax.axis_size(AXIS)
    if len(chunks) == 1:
        chunks = _seq_to_ring(chunks[0])
    # The sum for shard c starts on device c + 1 and ends on c: at step
    # u a device adds its product for shard (i - 1 - u) % n.
    acc = jnp.einsum(eqn, chunks[1 % n], w)
    for u in range(1, n):
        acc = _hop(acc)
        # A product of its own, not an epilogue of the addition: it is
        # computed while the sum it joins is still on the link.
        part = lax.optimization_barrier(
            jnp.einsum(eqn, chunks[(u + 1) % n], w))
        acc = acc + part
    return acc


def _fold_sp(x):
    """[B, S, ...] -> [B x sp, S / sp, ...]: a sequence split over
    ``(sp, tp)`` becomes a batch split over sp and rows split over tp
    without moving a byte, so the ring runs inside each sp block."""
    sp = jax.sharding.get_abstract_mesh().shape.get("sp", 1)
    if sp == 1:
        return x
    return x.reshape(x.shape[0] * sp, x.shape[1] // sp, *x.shape[2:])


def _spec(ndim: int, split: Optional[int]) -> P:
    return P(*[AXIS if i == split else None for i in range(ndim)])


def gather_matmul(eqn: str, h, ws: Sequence, *,
                  rowwise: Optional[Callable] = None, row_args: Sequence = (),
                  in_sequence: bool = True) -> Tuple:
    """``einsum(eqn, h, w)`` for each ``w`` of a column-parallel group.
    ``h`` [B, S, D] is sequence-sharded over tp; each ``w`` [D, N, ...]
    has N split over tp; the products [B, S, N, ...] hold the whole
    sequence and the device's columns. One travelling copy of ``h``
    serves every ``w``.

    ``rowwise(products, *rows_of_row_args) -> tuple`` is applied to each
    shard's products as they are made (rotary embedding, an activation:
    anything that treats rows alike), with the same rows of each of
    ``row_args`` [B, S, ...]: where XLA fuses it with the product's
    neighbours (SwiGLU) it costs no pass of its own; rotary embedding
    did, several (see the module's note), and left for a kernel behind
    the assembled sequence.

    ``in_sequence=False`` leaves each result as its ``tp`` row blocks in
    RING order (block t on device i holds the rows of shard (i - t) %
    tp): what `matmul_scatter` takes. Assembling them is a pass over the
    block's largest arrays; only attention needs the true order."""
    ws, row_args = tuple(ws), tuple(row_args)
    in_specs = (_spec(h.ndim, 1), tuple(_spec(w.ndim, 1) for w in ws),
                tuple(_spec(a.ndim, None) for a in row_args))
    with jax.named_scope("tp_gather_matmul"):
        # One spec for every result: the device's columns on dimension 2.
        outs = jax.shard_map(
            functools.partial(_gather_matmul_local, eqn, rowwise,
                              in_sequence),
            in_specs=in_specs, out_specs=_spec(3, 2), axis_names={AXIS},
        )(_fold_sp(h), ws, tuple(map(_fold_sp, row_args)))
    return jax.tree.map(
        lambda o: o.reshape(h.shape[0], -1, *o.shape[2:]), outs)


def matmul_scatter(eqn: str, y, w):
    """``einsum(eqn, y, w)`` summed over tp, for a row-parallel ``w``
    [N, ..., D] with N split over tp: the sum [B, S, D] comes back
    sequence-sharded over tp. ``y`` [B, S, N, ...] holds the whole
    sequence, or is its row blocks in ring order as
    `gather_matmul(..., in_sequence=False)` leaves them."""
    chunks = tuple(y) if isinstance(y, (tuple, list)) else (y,)
    batch = chunks[0].shape[0]
    with jax.named_scope("tp_matmul_scatter"):
        out = jax.shard_map(
            functools.partial(_matmul_scatter_local, eqn),
            in_specs=(_spec(w.ndim, 0),
                      tuple(_spec(c.ndim, 2) for c in chunks)),
            out_specs=_spec(3, 1),
            axis_names={AXIS},
        )(w, tuple(map(_fold_sp, chunks)))
    return out.reshape(batch, -1, out.shape[-1])
