"""Lightning (linear) attention with a CONSTANT decay a head: the
recurrence, its chunked form for prefill and the one-token state step
for decode.

Per head, ``q_t, k_t`` in R^dk, ``v_t`` in R^dv, ``lambda`` in (0, 1)
(lightning attention's slope table, `log_decays`: no parameter), the
state ``S`` in R^{dk x dv} (float32, zero before the sequence):

    S_t = lambda S_{t-1} + k_t v_t^T
    o_t = S_t^T q_t

(the caller scales q). Beside the gated delta rule of
``ops/gated_delta.py`` the ``- S k`` term is absent and the decay is no
function of the token, so a chunk of C tokens needs no triangular
inverse: with ``c_i`` the sum of the log decays of the chunk's tokens
up to and with i (a token whose log decay is 0 and whose k is 0 leaves
the state alone: a bucket's padding),

    o_i = exp(c_i) S_0^T q_i + sum_{j<=i} exp(c_i - c_j) (q_i . k_j) v_j
    S_C = exp(c_C) S_0 + sum_j exp(c_C - c_j) k_j v_j^T

`chunk_scan` makes the in-chunk products of all chunks at once and
carries only ``S`` through a ``lax.scan``. `lightning_decode` steps
every slot's state of one layer where it lies: one Pallas call
(``rtpu_lightning_decode``) over the WHOLE ``[L, B, H, dk, dv]`` cache
array, the layer scalar-prefetched, input aliased to output; its
``jnp`` twin runs off the chip. dk = dv = 128 fills whole tiles, so the
state is stored as the mathematics has it (the delta-rule family packs
two heads a tile; what the two kernels share is the spreading of a row
over lanes, `gated_delta._over_lanes`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.ops.gated_delta import _block_groups, _over_lanes

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
CHUNK = 128


def log_decays(n_heads: int):
    """``log lambda_h = -2^(-8 (h + 1) / H)``, h = 0 .. H-1: lightning
    attention's slope table. float32 [H]."""
    h = jnp.arange(1, n_heads + 1, dtype=F32)
    return -jnp.exp2(-8.0 * h / n_heads)


def recurrence(q, k, v, g, state):
    """A token at a time. q, k [B,T,H,dk], v [B,T,H,dv], g [B,T,H] (log
    decay of each token), state [B,H,dk,dv] -> (o [B,T,H,dv] float32,
    state)."""

    def step(s, xs):
        q_t, k_t, v_t, g_t = xs
        s = s * jnp.exp(g_t)[..., None, None] + (
            k_t[..., :, None] * v_t[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    xs = jax.tree.map(lambda a: jnp.moveaxis(a.astype(F32), 1, 0),
                      (q, k, v, g))
    state, o = lax.scan(step, state.astype(F32), xs)
    return jnp.moveaxis(o, 0, 1), state


def chunk_scan(q, k, v, g, state, *, chunk: int = CHUNK):
    """`recurrence` in chunks of ``chunk`` tokens (this module's
    header); the same arguments and results. T is padded up to whole
    chunks with tokens that leave the state alone."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(a):          # [B,T,H,..] -> [B,H,N,C,..]
        a = jnp.pad(a.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) *
                    (a.ndim - 2))
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g = map(chunks, (q, k, v, g))
    dot = functools.partial(jnp.einsum, precision=HIGHEST)
    cum = jnp.cumsum(g, axis=-1)                        # c_i
    i = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # exp(c_i - c_j) for j <= i, else 0 (masked before the exp: the
    # other triangle's quotient is not bounded).
    ratio = jnp.exp(jnp.where(i >= j, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    inside = dot("bhnij,bhnjv->bhniv",
                 ratio * dot("bhnik,bhnjk->bhnij", q, k), v)
    q_in = q * jnp.exp(cum)[..., None]                  # exp(c_i) q_i
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]  # exp(c_C - c_j) k_j
    g_out = jnp.exp(cum[..., -1])                       # exp(c_C)

    def body(s, xs):
        inside, q_in, k_out, v, g_out = xs
        o = inside + dot("bhck,bhkv->bhcv", q_in, s)
        s = g_out[..., None, None] * s + dot("bhck,bhcv->bhkv", k_out, v)
        return s, o

    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0),
                      (inside, q_in, k_out, v, g_out))
    state, o = lax.scan(body, state.astype(F32), xs)    # o [N,B,H,C,dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, n * chunk, h, dv)
    return o[:, :t], state


# The decode step ----------------------------------------------------------

def _step_head(s, q, k, rows):
    """One head a step. s [dk, dv], q, k [1, dk], rows [2, dv] (v; the
    head's decay spread over the lanes) -> (o [1, dv], s). The kernel's
    body and its twin's: elementwise on the tile but the sum over dk
    (the sublanes) and the spreading of q and k over lanes."""
    q_x, k_x = _over_lanes(q, s.shape[1]), _over_lanes(k, s.shape[1])
    s = s * rows[1:2] + k_x * rows[0:1]
    return jnp.sum(s * q_x, axis=0, keepdims=True), s


def _lightning_kernel(layer_ref, q_ref, k_ref, rows_ref, s_ref, o_ref,
                      s_out_ref, *, heads: int):
    for p in range(heads):
        o, s = _step_head(s_ref[0, 0, p], q_ref[0, p], k_ref[0, p],
                          rows_ref[0, p])
        o_ref[0, p] = o
        s_out_ref[0, 0, p] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_decode(state, layer, q, k, v, g, *,
                     interpret: Optional[bool] = None):
    """One token for every slot. state [L,B,H,dk,dv] float32 (the whole
    cache array), ``layer`` a traced int32 scalar, q, k [B,H,dk], v
    [B,H,dv], g [B,H] (log decay) -> (o [B,H,dv] float32, state): the
    layer's tiles stepped where they lie, the rest untouched. The
    Pallas kernel on the TPU (or under ``interpret``), its ``jnp`` twin
    elsewhere. A slot with ``g = 0`` and ``k = 0`` keeps its state."""
    n_layers, b, h, dk, dv = state.shape
    q = q.astype(F32).reshape(b, h, 1, dk)
    k = k.astype(F32).reshape(b, h, 1, dk)
    rows = jnp.stack(
        [v.astype(F32), jnp.broadcast_to(jnp.exp(g.astype(F32))[..., None],
                                         (b, h, dv))], axis=2)
    if not (jax.default_backend() == "tpu" or interpret):
        tiles = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        o, tiles = jax.vmap(jax.vmap(_step_head))(tiles, q, k, rows)
        state = lax.dynamic_update_index_in_dim(state, tiles, layer, 0)
        return o.reshape(b, h, dv), state

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hb = _block_groups(h, dk * dv * 4)

    def at_layer(bi, hi, layer):
        return layer[0], bi, hi, 0, 0

    def at_slot(bi, hi, layer):
        return bi, hi, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // hb),
        in_specs=[pl.BlockSpec((1, hb, 1, dk), at_slot),
                  pl.BlockSpec((1, hb, 1, dk), at_slot),
                  pl.BlockSpec((1, hb, 2, dv), at_slot),
                  pl.BlockSpec((1, 1, hb, dk, dv), at_layer)],
        out_specs=[pl.BlockSpec((1, hb, 1, dv), at_slot),
                   pl.BlockSpec((1, 1, hb, dk, dv), at_layer)],
    )
    o, state = pl.pallas_call(
        functools.partial(_lightning_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, 1, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # Operand 4 (after the prefetched scalar) is the state: written
        # where it was read.
        input_output_aliases={4: 1},
        interpret=bool(interpret),
        name="rtpu_lightning_decode",
        metadata={"kernel": "rtpu_lightning_decode"},
    )(jnp.asarray(layer, jnp.int32).reshape(1), q, k, rows, state)
    return o.reshape(b, h, dv), state
