"""Gated delta-rule linear attention (the published gated delta-rule
layer: HF ``Qwen3NextGatedDeltaNet`` / FLA ``GatedDeltaNet``): the
recurrence, its chunked form for prefill, the one-token step for decode
and the causal depthwise convolution beside them.

Per head, with ``q_t, k_t`` in R^dk (k of unit length), ``v_t`` in R^dv,
a decay ``alpha_t = exp(g_t)`` in (0, 1] and a write strength
``beta_t``, the state ``S`` in R^{dv x dk} (float32) follows

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t

`recurrence` is that, a token at a time. `chunk_scan` is the same
mathematics in chunks of C tokens (the WY form): with
``gamma_i = prod_{j<=i} alpha_j`` inside a chunk that starts from
``S_0``, and ``u_j = beta_j (v_j - alpha_j S_{j-1} k_j)``,

    S_i = gamma_i S_0 + sum_{j<=i} (gamma_i / gamma_j) u_j k_j^T
    (I + diag(beta) A) U = diag(beta) (V - diag(gamma) K S_0^T),
        A_ij = (gamma_i / gamma_j) k_i . k_j  for j < i, else 0

so ``U = U_v - W S_0^T`` with ``U_v`` and ``W`` from ONE triangular
inverse a chunk that needs no state (all chunks at once), and the loop
over chunks carries only ``S``: three products of ``C x dk x dv`` a
chunk (``W S_0^T``, ``Q S_0^T``, ``U^T K``) and one of ``C x C x dv``.
A token with ``g = 0, beta = 0`` leaves the state as it was: that is
how a prefill bucket's padding passes through.

**The state as the cache holds it** is ``[.., H/G, dk, G*dv]``: ``S^T``
of G heads side by side, G the fewest heads whose values fill whole
128-lane tiles (2 at the published dv = 192: 384 lanes; dk = 96 is 12
whole sublane tiles). Kept as ``[.., H, dv, dk]`` the chip pads the
minor 96 to 128 lanes (``[.., dk, dv]``: 192 to 256) and every step
moves 2.95 MB a slot a layer where the state is 2.21 MB. `gdn_decode`
steps every slot's state of one layer where it lies: one Pallas call
(``rtpu_gdn_decode``) over the WHOLE ``[L, B, ..]`` array, the layer
scalar-prefetched, input aliased to output; its ``jnp`` twin runs off
the chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
CHUNK = 64


def l2_normalize(x, eps: float = 1e-6):
    """x / ||x||_2 over the last axis, in float32 (eps under the root,
    as the published kernels have it: an all-zero row stays zero)."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


# The convolution ----------------------------------------------------------

def causal_conv(u, tail, weight, n_real=None):
    """Causal depthwise convolution over time, width W = weight.shape[1]:
    ``y_t = sum_i weight[:, i] * u_{t-W+1+i}``, the W-1 inputs before the
    sequence read from ``tail``.

    u [B,T,C], tail [B,(W-1)*C] (oldest first, flat: whole lane tiles),
    weight [C,W] -> (y [B,T,C], new tail): the last W-1 inputs up to
    and with token ``n_real - 1`` (``n_real`` None: T), so that a
    bucket's padding leaves no trace and a prompt shorter than W-1
    keeps what the tail held."""
    b, t, c = u.shape
    w = weight.shape[1]
    ext = jnp.concatenate([tail.reshape(b, w - 1, c).astype(u.dtype), u], 1)
    y = sum(ext[:, i:i + t].astype(F32) * weight[:, i].astype(F32)
            for i in range(w))
    n_real = t if n_real is None else n_real
    new_tail = lax.dynamic_slice_in_dim(ext, n_real, w - 1, axis=1)
    return y.astype(u.dtype), new_tail.reshape(b, (w - 1) * c)


def causal_conv_step(u, tail, weight):
    """One token: u [B,C], tail [B,(W-1)*C] -> (y [B,C], new tail)."""
    c, w = weight.shape
    window = jnp.concatenate([tail.astype(u.dtype), u], axis=-1)  # [B,W*C]
    y = sum(window[:, i * c:(i + 1) * c].astype(F32)
            * weight[:, i].astype(F32) for i in range(w))
    return y.astype(u.dtype), window[:, c:]


# The recurrence and its chunked form --------------------------------------

def recurrence(q, k, v, g, beta, state):
    """A token at a time. q, k [B,T,H,dk], v [B,T,H,dv], g, beta
    [B,T,H], state [B,H,dv,dk] -> (o [B,T,H,dv] float32, state)."""

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        pred = jnp.einsum("bhvk,bhk->bhv", s, k_t, precision=HIGHEST)
        u = b_t[..., None] * (v_t - pred)
        s = s + u[..., :, None] * k_t[..., None, :]
        return s, jnp.einsum("bhvk,bhk->bhv", s, q_t, precision=HIGHEST)

    xs = jax.tree.map(lambda a: jnp.moveaxis(a.astype(F32), 1, 0),
                      (q, k, v, g, beta))
    state, o = lax.scan(step, state.astype(F32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(lower):
    """``(I + lower)^-1`` for strictly lower-triangular [.., n, n], n a
    power of two, in products alone (the chip's triangular solve took a
    quarter of a prefill: trace, PR 33). Diagonal blocks of 16 by the
    finite series ``(I - L)(I + L^2)(I + L^4)(I + L^8)`` (``L^16 = 0``;
    at 16 rows its terms stay small, at 64 they would cancel
    catastrophically), then ``[[A, 0], [C, B]]^-1 = [[A^-1, 0],
    [-B^-1 C A^-1, B^-1]]`` twice over."""
    n = lower.shape[-1]
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    if n <= 16:
        power = -lower
        inverse = jnp.eye(n, dtype=lower.dtype) + power
        for _ in range(max(n.bit_length() - 2, 0)):
            power = dot(power, power)
            inverse = inverse + dot(inverse, power)
        return inverse
    h = n // 2
    a = _unit_lower_inverse(lower[..., :h, :h])
    b = _unit_lower_inverse(lower[..., h:, h:])
    c = -dot(dot(b, lower[..., h:, :h]), a)
    return jnp.concatenate(
        [jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
         jnp.concatenate([c, b], axis=-1)], axis=-2)


def chunk_scan(q, k, v, g, beta, state, *, chunk: int = CHUNK):
    """`recurrence` in chunks of ``chunk`` tokens (this module's
    header); the same arguments and results. T is padded up to whole
    chunks with tokens that leave the state alone."""
    if chunk & (chunk - 1):
        raise ValueError("chunk must be a power of two")
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(a):          # [B,T,H,..] -> [B,H,N,C,..]
        a = jnp.pad(a.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) *
                    (a.ndim - 2))
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    dot = functools.partial(jnp.einsum, precision=HIGHEST)
    cum = jnp.cumsum(g, axis=-1)                        # log gamma_i
    i = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # gamma_i / gamma_j for j <= i, else 0 (masked before the exp: the
    # other triangle's quotient is not bounded).
    ratio = jnp.exp(jnp.where(i >= j, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    kk = dot("bhnik,bhnjk->bhnij", k, k)
    lower = jnp.where(i > j, ratio * kk, 0.0) * beta[..., None]
    rhs = jnp.concatenate([v * beta[..., None],
                           k * (beta * jnp.exp(cum))[..., None]], axis=-1)
    solved = dot("...ij,...jc->...ic", _unit_lower_inverse(lower), rhs)
    u_v, w = solved[..., :dv], solved[..., dv:]
    p = ratio * dot("bhnik,bhnjk->bhnij", q, k)
    q_in = q * jnp.exp(cum)[..., None]                  # gamma_i q_i
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]  # gamma_C/gamma_j k_j
    g_out = jnp.exp(cum[..., -1])                       # gamma_C

    def body(s, xs):
        u_v, w, p, q_in, k_out, g_out = xs
        u = u_v - dot("bhck,bhvk->bhcv", w, s)
        o = dot("bhck,bhvk->bhcv", q_in, s) + dot("bhij,bhjv->bhiv", p, u)
        s = g_out[..., None, None] * s + dot("bhcv,bhck->bhvk", u, k_out)
        return s, o

    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0),
                      (u_v, w, p, q_in, k_out, g_out))
    state, o = lax.scan(body, state.astype(F32), xs)    # o [N,B,H,C,dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, n * chunk, h, dv)
    return o[:, :t], state


# The state as the cache holds it ------------------------------------------

def state_group(n_heads: int, dv: int) -> int:
    """Heads side by side in one stored tile: the fewest whose values
    fill whole 128-lane tiles (1 where no divisor of H does)."""
    for group in range(1, n_heads + 1):
        if n_heads % group == 0 and group * dv % 128 == 0:
            return group
    return 1


def pack_state(state, group: int):
    """[B,H,dv,dk] -> [B,H/G,dk,G*dv]: S^T of G heads side by side."""
    b, h, dv, dk = state.shape
    s = state.reshape(b, h // group, group, dv, dk)
    return s.transpose(0, 1, 4, 2, 3).reshape(b, h // group, dk, group * dv)


def unpack_state(packed, group: int):
    """`pack_state`'s inverse."""
    b, ng, dk, gdv = packed.shape
    s = packed.reshape(b, ng, dk, group, gdv // group)
    return s.transpose(0, 1, 3, 4, 2).reshape(b, ng * group,
                                              gdv // group, dk)


# The decode step ----------------------------------------------------------

def _over_lanes(x, gdv: int):
    """x [G, dk] -> [dk, G*dv]: head g's column spread over its dv
    lanes, as a product with a 0/1 matrix (the MXU; the VPU has no
    cheap way to turn a row into columns). Exact: x is split into three
    bf16 parts whose sum it is, each part times 1 is itself, and the
    parts add up in the float32 accumulator. One bf16 pass over K = 3G
    rows; asked for at `HIGHEST` the same product took a quarter of the
    kernel's time (the chip, PR 33)."""
    group = x.shape[0]
    parts, rest = [], x
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(F32)
    rows = lax.broadcasted_iota(jnp.int32, (3 * group, gdv), 0) % group
    head = lax.broadcasted_iota(jnp.int32, (3 * group, gdv), 1) // (
        gdv // group)
    return lax.dot_general(
        jnp.concatenate(parts, axis=0), (rows == head).astype(jnp.bfloat16),
        (((0,), (0,)), ((), ())), preferred_element_type=F32)


def _step_group(s, q, k, rows):
    """One stored tile a step. s [dk, G*dv] (S^T of G heads), q, k
    [G, dk], rows [3, G*dv] (v, alpha, beta, each head's scalar spread
    over its dv lanes) -> (o [1, G*dv], s). The kernel's body and its
    twin's: everything is elementwise on the tile but the sums over dk
    (the sublanes) and the spreading of q and k over lanes."""
    q_x, k_x = _over_lanes(q, s.shape[1]), _over_lanes(k, s.shape[1])
    v, alpha, beta = rows[0:1], rows[1:2], rows[2:3]
    s = s * alpha
    u = beta * (v - jnp.sum(s * k_x, axis=0, keepdims=True))
    s = s + k_x * u
    return jnp.sum(s * q_x, axis=0, keepdims=True), s


def _gdn_kernel(layer_ref, q_ref, k_ref, rows_ref, s_ref, o_ref, s_out_ref,
                *, groups: int):
    for p in range(groups):
        o, s = _step_group(s_ref[0, 0, p], q_ref[0, p], k_ref[0, p],
                           rows_ref[0, p])
        o_ref[0, p] = o
        s_out_ref[0, 0, p] = s


def _block_groups(n_groups: int, tile_bytes: int, limit: int = 1 << 20):
    """Stored tiles a kernel block: the most that divide H/G and stay
    under ``limit`` bytes (a block is held four times: in and out,
    each double-buffered)."""
    best = 1
    for n in range(1, n_groups + 1):
        if n_groups % n == 0 and n * tile_bytes <= limit:
            best = n
    return best


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode(state, layer, q, k, v, g, beta, *,
               interpret: Optional[bool] = None):
    """One token for every slot. state [L,B,H/G,dk,G*dv] float32 (the
    whole cache array), ``layer`` a traced int32 scalar, q, k [B,H,dk],
    v [B,H,dv], g, beta [B,H] -> (o [B,H,dv] float32, state): the
    layer's tiles stepped where they lie, the rest untouched. The
    Pallas kernel on the TPU (or under ``interpret``), its ``jnp`` twin
    elsewhere. A slot with ``g = 0, beta = 0`` keeps its state."""
    n_layers, b, ng, dk, gdv = state.shape
    h, dv = v.shape[1], v.shape[2]
    group = h // ng
    q = q.astype(F32).reshape(b, ng, group, dk)
    k = k.astype(F32).reshape(b, ng, group, dk)

    def spread(a):          # [B,H] -> [B,H/G,G*dv]
        return jnp.repeat(a.astype(F32), dv, axis=-1).reshape(b, ng, gdv)

    rows = jnp.stack([v.astype(F32).reshape(b, ng, gdv),
                      spread(jnp.exp(g)), spread(beta)], axis=2)
    if not (jax.default_backend() == "tpu" or interpret):
        tiles = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        o, tiles = jax.vmap(jax.vmap(_step_group))(tiles, q, k, rows)
        state = lax.dynamic_update_index_in_dim(state, tiles, layer, 0)
        return o.reshape(b, h, dv), state

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    gb = _block_groups(ng, dk * gdv * 4)

    def at_layer(bi, gi, layer):
        return layer[0], bi, gi, 0, 0

    def at_slot(bi, gi, layer):
        return bi, gi, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, ng // gb),
        in_specs=[pl.BlockSpec((1, gb, group, dk), at_slot),
                  pl.BlockSpec((1, gb, group, dk), at_slot),
                  pl.BlockSpec((1, gb, 3, gdv), at_slot),
                  pl.BlockSpec((1, 1, gb, dk, gdv), at_layer)],
        out_specs=[pl.BlockSpec((1, gb, 1, gdv), at_slot),
                   pl.BlockSpec((1, 1, gb, dk, gdv), at_layer)],
    )
    o, state = pl.pallas_call(
        functools.partial(_gdn_kernel, groups=gb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, ng, 1, gdv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # Operand 4 (after the prefetched scalar) is the state: written
        # where it was read.
        input_output_aliases={4: 1},
        interpret=bool(interpret),
        name="rtpu_gdn_decode",
        metadata={"kernel": "rtpu_gdn_decode"},
    )(jnp.asarray(layer, jnp.int32).reshape(1), q, k, rows, state)
    return o.reshape(b, h, dv), state
