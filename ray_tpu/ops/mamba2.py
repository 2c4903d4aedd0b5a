"""Mamba-2 (state-space duality) selective scan, as the published
``granitemoehybrid`` Mamba layer has it: the recurrence, its chunked
form (SSD) for prefill and the one-token step for decode. The causal
depthwise convolution beside them is `gated_delta.causal_conv` (the
published layer adds a bias, outside).

Per head h of H, with ``x_t`` in R^P, a step ``dt_t > 0``, a decay rate
``a < 0`` (``-exp(A_log)``), and ONE input map ``B_t`` and ONE output
map ``C_t`` in R^N for all the heads of a token (one group), the state
``S`` in R^{P x N} (float32) follows

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t

(the skip ``D x_t``, the gate and the norm are the caller's).
`recurrence` is that, a token at a time. `chunk_scan` is the same
mathematics in chunks of C tokens: with ``l_i = sum_{j<=i} dt_j a``
inside a chunk that starts from ``S_0``,

    y_i = exp(l_i) S_0 C_i + sum_{j<=i} exp(l_i - l_j) (C_i . B_j) dt_j x_j
    S_C = exp(l_C) S_0 + sum_j exp(l_C - l_j) dt_j x_j (x) B_j

so everything but the terms in ``S_0`` is made for all chunks at once
(``C B^T`` is ONE [C, C] matrix a chunk for all heads), and the loop
over chunks carries only ``S``. A token with ``dt = 0`` leaves the state
as it was (decay 1, write 0): that is how a prefill bucket's padding
and a decode step's idle slots pass through; ``softplus`` never gives
0, so the caller masks.

**The state as the cache holds it** is ``[.., H/G, N, G*P]``: ``S^T`` of
G heads side by side (`gated_delta.pack_state`), G the fewest heads
whose P fill whole 128-lane tiles (2 at the published P = 64). Kept as
``[.., H, P, N]`` the minor dimension is whole too, but the step then
sums over LANES (``S C``: a cross-lane reduction a vreg) and needs
``x`` as a column a head; transposed, ``B`` and ``C`` are columns shared
by every head of the slot (spread over lanes ONCE a slot), ``dt x`` and
the decay are rows, and the step is elementwise with sums over
sublanes: the form `gated_delta.gdn_decode` keeps, whose kernel this
one follows. `mamba2_decode` steps every slot's state of one layer
where it lies: one Pallas call (``rtpu_mamba2_decode``) over the WHOLE
``[L, B, ..]`` array, the layer scalar-prefetched, input aliased to
output; its ``jnp`` twin runs off the chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.ops import gated_delta
# The stored layout is the gated delta-rule layer's with P for its dv
# and N for its dk: S^T of G heads side by side.
from ray_tpu.ops.gated_delta import (  # noqa: F401
    pack_state, state_group, unpack_state)

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
CHUNK = 256


# The recurrence and its chunked form --------------------------------------

def recurrence(x, dt, a, bm, cm, state):
    """A token at a time. x [B,T,H,P], dt [B,T,H], a [H], bm, cm
    [B,T,N], state [B,H,P,N] -> (y [B,T,H,P] float32, state)."""

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = (s * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HIGHEST)

    xs = jax.tree.map(lambda v: jnp.moveaxis(v.astype(F32), 1, 0),
                      (x, dt, bm, cm))
    state, y = lax.scan(step, state.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1), state


def chunk_scan(x, dt, a, bm, cm, state, *, chunk: int = CHUNK):
    """`recurrence` in chunks of ``chunk`` tokens (this module's
    header); the same arguments and results. T is padded up to whole
    chunks with tokens of ``dt = 0``, which leave the state alone; a
    sequence shorter than a chunk is one chunk of its own length."""
    b, t, h, p = x.shape
    chunk = min(chunk, t)
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(v):          # [B,T,..] -> [B,N,C,..]
        v = jnp.pad(v.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) *
                    (v.ndim - 2))
        return v.reshape((b, n, chunk) + v.shape[2:])

    x, dt, bm, cm = map(chunks, (x, dt, bm, cm))
    x, dt = jnp.moveaxis(x, 3, 2), jnp.moveaxis(dt, 3, 2)   # heads first
    dot = functools.partial(jnp.einsum, precision=HIGHEST)
    cum = jnp.cumsum(dt * a[:, None], axis=-1)          # l_i [B,N,H,C]
    dtx = dt[..., None] * x                             # [B,N,H,C,P]
    i = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # exp(l_i - l_j) for j <= i, else 0 (masked before the exp: the
    # other triangle's difference is not bounded).
    ratio = jnp.exp(jnp.where(i >= j, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    scores = dot("bnic,bnjc->bnij", cm, bm)[:, :, None] * ratio
    y_in = dot("bnhij,bnhjp->bnhip", scores, dtx)
    # exp(l_C - l_j) dt_j x_j (x) B_j, summed over the chunk.
    wrote = dot("bnhjp,bnjc->bnhpc",
                dtx * jnp.exp(cum[..., -1:] - cum)[..., None], bm)

    def body(s, xs):
        cm, wrote, cum = xs
        y = dot("bic,bhpc->bhip", cm, s) * jnp.exp(cum)[..., None]
        return jnp.exp(cum[..., -1])[..., None, None] * s + wrote, y

    xs = jax.tree.map(lambda v: jnp.moveaxis(v, 1, 0), (cm, wrote, cum))
    state, y_state = lax.scan(body, state.astype(F32), xs)
    y = y_in + jnp.moveaxis(y_state, 0, 1)              # [B,N,H,C,P]
    return jnp.moveaxis(y, 2, 3).reshape(b, n * chunk, h, p)[:, :t], state


# The decode step ----------------------------------------------------------

def _columns(rows, lanes: int):
    """rows [R, N] -> R arrays [N, lanes]: each row turned into a column
    and spread over ``lanes`` lanes, as a product with ones (the MXU;
    `gated_delta._over_lanes` says why, and why it is exact: three bf16
    parts whose sum the row is, added up in the float32 accumulator)."""
    ones = jnp.ones((3, lanes), jnp.bfloat16)
    out = []
    for r in range(rows.shape[0]):
        parts, rest = [], rows[r:r + 1]
        for _ in range(3):
            parts.append(rest.astype(jnp.bfloat16))
            rest = rest - parts[-1].astype(F32)
        out.append(lax.dot_general(
            jnp.concatenate(parts, axis=0), ones, (((0,), (0,)), ((), ())),
            preferred_element_type=F32))
    return out


def _step_group(s, rows, b_x, c_x):
    """One stored tile a step. s [N, G*P] (S^T of G heads), rows
    [2, G*P] (the decay ``exp(dt a)`` spread over its head's P lanes;
    ``dt x``), b_x, c_x [N, G*P] (the slot's B and C as columns) ->
    (y [1, G*P], s). Elementwise on the tile but the sum over N (the
    sublanes)."""
    s = s * rows[0:1] + b_x * rows[1:2]
    return jnp.sum(s * c_x, axis=0, keepdims=True), s


def _step_slot(s, rows, bc):
    """The twin's slot: s [H/G, N, G*P], rows [H/G, 2, G*P], bc [2, N]."""
    b_x, c_x = _columns(bc, s.shape[-1])
    return jax.vmap(_step_group, in_axes=(0, 0, None, None))(s, rows, b_x,
                                                             c_x)


def _mamba2_kernel(layer_ref, rows_ref, bc_ref, s_ref, y_ref, s_out_ref, *,
                   groups: int):
    b_x, c_x = _columns(bc_ref[0], s_ref.shape[-1])
    for g in range(groups):
        y, s = _step_group(s_ref[0, 0, g], rows_ref[0, g], b_x, c_x)
        y_ref[0, g] = y
        s_out_ref[0, 0, g] = s


@functools.partial(jax.jit, static_argnames=("interpret", "block_bytes"))
def mamba2_decode(state, layer, x, dt, a, bm, cm, *,
                  interpret: Optional[bool] = None,
                  block_bytes: int = 1 << 20):
    """One token for every slot. state [L,B,H/G,N,G*P] float32 (the
    whole cache array), ``layer`` a traced int32 scalar, x [B,H,P], dt
    [B,H], a [H], bm, cm [B,N] -> (y [B,H,P] float32, state): the
    layer's tiles stepped where they lie, the rest untouched. The
    Pallas kernel on the TPU (or under ``interpret``), its ``jnp`` twin
    elsewhere. A slot with ``dt = 0`` keeps its state."""
    n_layers, b, ng, n, gp = state.shape
    h, p = x.shape[1], x.shape[2]
    dt = dt.astype(F32)
    rows = jnp.stack(
        [jnp.repeat(jnp.exp(dt * a), p, axis=-1).reshape(b, ng, gp),
         (dt[..., None] * x.astype(F32)).reshape(b, ng, gp)], axis=2)
    bc = jnp.stack([bm.astype(F32), cm.astype(F32)], axis=1)     # [B,2,N]
    if not (jax.default_backend() == "tpu" or interpret):
        tiles = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        y, tiles = jax.vmap(_step_slot)(tiles, rows, bc)
        state = lax.dynamic_update_index_in_dim(state, tiles, layer, 0)
        return y.reshape(b, h, p), state

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    gb = gated_delta._block_groups(ng, n * gp * 4, block_bytes)

    def at_layer(bi, gi, layer):
        return layer[0], bi, gi, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, ng // gb),
        in_specs=[pl.BlockSpec((1, gb, 2, gp), lambda bi, gi, _: (bi, gi, 0, 0)),
                  pl.BlockSpec((1, 2, n), lambda bi, gi, _: (bi, 0, 0)),
                  pl.BlockSpec((1, 1, gb, n, gp), at_layer)],
        out_specs=[pl.BlockSpec((1, gb, 1, gp), lambda bi, gi, _: (bi, gi, 0, 0)),
                   pl.BlockSpec((1, 1, gb, n, gp), at_layer)],
    )
    y, state = pl.pallas_call(
        functools.partial(_mamba2_kernel, groups=gb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, ng, 1, gp), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # Operand 3 (after the prefetched scalar) is the state: written
        # where it was read.
        input_output_aliases={3: 1},
        interpret=bool(interpret),
        name="rtpu_mamba2_decode",
        metadata={"kernel": "rtpu_mamba2_decode"},
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows, bc, state)
    return y.reshape(b, h, p), state
