"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): a residual of ``n`` STREAMS a
token, ``X`` in R^{n x C}, around every sub-layer ``F``. Three maps are
computed from the streams themselves, all in float32:

    u            = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)
    [p | q | r]  = u Phi                       Phi in R^{nC x (2n + n^2)}
    H_pre        = sigmoid(a_pre p + b_pre)                      in R^n
    H_post       = 2 sigmoid(a_post q + b_post)                  in R^n
    H_res        = SK(clip(a_res mat(r) + B_res, lo, hi))        in R^{n x n}

``SK`` starts from ``exp(.)`` and ``iters`` times divides every row by
its sum and then every column by its sum (each sum + ``hc_eps``): a
matrix whose rows and columns sum to one (Sinkhorn-Knopp). Then

    x  = H_pre X              (`mhc_pre`:  the sub-layer reads it, normed)
    X' = H_res X + H_post^T y (`mhc_post`: ``y`` the sub-layer's output)

Both are passes over ``X``, 4 x the bytes of an ordinary residual, and
little else: ``rtpu_mhc_pre`` reads a block of rows once (the norm's
sum; the product with ``Phi`` on the MXU at float32 precision, its six
bf16 terms in three passes; the sigmoids and the Sinkhorn passes in
fast memory with the block's rows on the lanes; the weighted sum of the
streams) and ``rtpu_mhc_post`` rewrites ``X`` where it lies. A
decode step's 32 rows and a prefill bucket's tokens are the same two
kernels over other grids. The kernels run on the TPU (or under
``interpret``), their ``jnp`` twins elsewhere.

Storage (what a family's ``init_params`` draws): ``phi_t`` [2n + n^2,
n C] float32, OUTPUT-major (24 columns would be padded to 128 lanes in
HBM, five times the bytes), its rows in the order ``p``, ``q``,
``vec(r)`` (row-major: ``r[i, j]`` mixes stream j into stream i);
``alpha`` [3] = (a_pre, a_post, a_res); ``bias`` [2n + n^2] = b_pre ++
b_post ++ vec(B_res). The maps travel between the two kernels as ONE
array ``[rows, 128]`` float32 whose first ``2n + n^2`` lanes are H_pre
++ H_post ++ vec(H_res) (`split_maps`). The streams travel FLAT,
``[rows, n C]`` (stream i in columns ``i C .. (i + 1) C``): as ``[rows,
n, C]`` the chip tiles the last two axes and pads 4 streams to 8
sublanes, and every call re-laid 117 MB of a 2,048-token bucket out
twice (0.38 ms a sub-layer beside 0.87 ms of kernels: the chip's trace,
PR 54).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
from jax import lax
import jax.numpy as jnp
import numpy as np

F32, BF16 = jnp.float32, jnp.bfloat16
LANES = 128
ROW_TILE = 128                  # rows a grid step (fewer where there are fewer)
COLUMN_CHUNK = 512              # lanes of a stream an inner step works on
VMEM_LIMIT_BYTES = 100 * 2 ** 20
PRE, POST = "rtpu_mhc_pre", "rtpu_mhc_post"


@dataclasses.dataclass(frozen=True)
class MhcSpec:
    """The published keys of the residual: ``hc_mult``,
    ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max`` and
    the norm's ``rms_norm_eps``."""
    n: int = 4
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    norm_eps: float = 1e-6

    @property
    def n_maps(self) -> int:
        return 2 * self.n + self.n * self.n


def split_maps(maps, n: int):
    """maps [..., >= 2n + n^2] -> (H_pre [..., n], H_post [..., n],
    H_res [..., n, n])."""
    return (maps[..., :n], maps[..., n:2 * n],
            maps[..., 2 * n:2 * n + n * n].reshape(maps.shape[:-1] + (n, n)))


def sinkhorn_error(maps, n: int):
    """The largest |row or column sum - 1| of any H_res in ``maps``."""
    h_res = split_maps(maps, n)[2]
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=-1) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(h_res, axis=-2) - 1.0)))


def _expanded(alpha, bias, n: int):
    """(alpha [3], bias [M]) -> [2, 128]: each map column's scale and
    bias, zero past the ``M`` columns."""
    scale = jnp.repeat(alpha.astype(F32), np.array([n, n, n * n]))
    both = jnp.stack([scale, bias.astype(F32)])
    return jnp.pad(both, ((0, 0), (0, LANES - both.shape[1])))


# The jnp twins ------------------------------------------------------------

def sinkhorn(logits, spec: MhcSpec):
    """logits [..., n, n] -> doubly stochastic [..., n, n], float32."""
    m = jnp.exp(jnp.clip(logits.astype(F32), spec.clamp_min, spec.clamp_max))

    def once(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + spec.hc_eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + spec.hc_eps)

    return lax.fori_loop(0, spec.sinkhorn_iters, once, m)


def maps_from_logits(raw, spec: MhcSpec):
    """raw [R, 2n + n^2] (``a p + b`` and so on, before any sigmoid) ->
    maps [R, 128]: the sigmoids, the factor 2 and the Sinkhorn passes."""
    n = spec.n
    p, q, r = split_maps(raw.astype(F32), n)
    maps = jnp.concatenate(
        [jax.nn.sigmoid(p), 2.0 * jax.nn.sigmoid(q),
         sinkhorn(r, spec).reshape(r.shape[0], n * n)], axis=-1)
    return jnp.pad(maps, ((0, 0), (0, LANES - spec.n_maps)))


def map_logits(streams, phi_t, alpha, bias, spec: MhcSpec):
    """streams [R, n C] -> [R, 2n + n^2]: the normed streams through
    ``Phi``, scaled and biased."""
    flat = streams.astype(F32)
    u = flat * lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                         + spec.norm_eps)
    scale, offset = _expanded(alpha, bias, spec.n)[:, :spec.n_maps]
    return jnp.einsum("rk,mk->rm", u, phi_t.astype(F32),
                      precision=lax.Precision.HIGHEST) * scale + offset


def maps_reference(streams, phi_t, alpha, bias, spec: MhcSpec):
    """streams [R, n C] -> maps [R, 128] (this module's header)."""
    return maps_from_logits(map_logits(streams, phi_t, alpha, bias, spec),
                            spec)


def _apart(streams, spec: MhcSpec):
    """[R, n C] -> [R, n, C] float32."""
    rows = streams.shape[0]
    return streams.astype(F32).reshape(rows, spec.n, -1)


def collapse(streams, maps, spec: MhcSpec):
    """H_pre X: streams [R, n C], maps [R, 128] -> [R, C]."""
    return jnp.einsum("rn,rnc->rc", maps[:, :spec.n], _apart(streams, spec))


def pre_reference(streams, phi_t, alpha, bias, spec: MhcSpec):
    maps = maps_reference(streams, phi_t, alpha, bias, spec)
    return collapse(streams, maps, spec), maps


def post_reference(streams, y, maps, spec: MhcSpec):
    _, h_post, h_res = split_maps(maps, spec.n)
    out = (jnp.einsum("rij,rjc->ric", h_res, _apart(streams, spec))
           + h_post[:, :, None] * y.astype(F32)[:, None, :])
    return out.reshape(streams.shape)


# The kernels --------------------------------------------------------------

def _chunks(c: int):
    step = COLUMN_CHUNK if c % COLUMN_CHUNK == 0 else c
    return [(c0, step) for c0 in range(0, c, step)]


def _bf16_terms(a):
    """float32 -> (hi, mid, lo) bfloat16 with ``a = hi + mid + lo`` to
    2^-24 of it: the terms the MXU's float32 product is made of."""
    terms, rest = [], a
    for _ in range(3):
        terms.append(rest.astype(BF16))
        rest = rest - terms[-1].astype(F32)
    return terms


def _maps_rows_on_lanes(pre_t, spec: MhcSpec, roll):
    """pre_t [128, R]: map logit ``s`` of R rows on sublane ``s``, the
    rows on the lanes -> the maps in that layout (the sigmoids, the
    Sinkhorn passes). Row ``i`` of every H_res is ONE [8, R] array, its
    four entries twice along the sublanes: any four sublanes in a run
    hold the row once, so two rolls and two adds leave its sum on all
    eight, and a column's sum is an add across the four arrays."""
    n = spec.n
    low = lax.broadcasted_iota(jnp.int32, (2 * n, pre_t.shape[1]), 0) < n
    sig = jax.nn.sigmoid(pre_t[0:2 * n])
    m = jnp.exp(jnp.clip(pre_t[2 * n:spec.n_maps], spec.clamp_min,
                         spec.clamp_max))
    rows_of = []
    for pair in (m[0:2 * n], m[2 * n:4 * n]):    # rows (0, 1) and (2, 3)
        other = roll(pair, n, 0)
        rows_of += [jnp.where(low, pair, other), jnp.where(low, other, pair)]

    def once(_, rows_of):
        out = []
        for r in rows_of:
            two = r + roll(r, 1, 0)
            out.append(r * (1.0 / (two + roll(two, 2, 0) + spec.hc_eps)))
        column = 1.0 / ((out[0] + out[1]) + (out[2] + out[3]) + spec.hc_eps)
        return tuple(r * column for r in out)

    r0, r1, r2, r3 = lax.fori_loop(0, spec.sinkhorn_iters, once,
                                   tuple(rows_of))
    return jnp.concatenate(
        [jnp.where(low, sig, 2.0 * sig), jnp.where(low, r0, r1),
         jnp.where(low, r2, r3),
         jnp.zeros((LANES - spec.n_maps, pre_t.shape[1]), F32)], axis=0)


# The float32 product u Phi is six bf16 products of the operands' terms
# (hi.hi, hi.mid, mid.hi, hi.lo, lo.hi, mid.mid) summed in float32:
# what ``Precision.HIGHEST`` computes in six MXU passes, four fifths of
# whose columns multiply the zeros that pad Phi's 24 outputs to a tile.
# Phi's three terms side by side in ONE tile give the six in three.

def _packed_terms(phi_t):
    """phi_t [24, K] float32 -> [128, K] bfloat16: rows 0..23 its hi
    term, 24..47 mid, 48..71 lo, the rest zero."""
    terms = [t.astype(F32) for t in _bf16_terms(phi_t)]
    idle = jnp.zeros((LANES - 3 * phi_t.shape[0], phi_t.shape[1]), F32)
    return jnp.concatenate(terms + [idle], axis=0).astype(BF16)


def _against_packed(sums, part, packed):
    """One chunk of columns more: part [R, k] float32, packed [128, k]
    (`_packed_terms`) -> the three [R, 128] sums, one a term of
    ``part``: lanes 0..23 that term against Phi's hi, 24.. mid, 48.. lo."""
    return [acc + lax.dot_general(term, packed, (((1,), (1,)), ((), ())),
                                  preferred_element_type=F32)
            for acc, term in zip(sums, _bf16_terms(part))]


def _six_terms(sums, roll, m_cols: int):
    """`_against_packed`'s sums -> [R, 128] whose lanes 0..23 are the
    float32 product, the smallest terms summed first (the other lanes
    hold sums nobody reads)."""
    hi, mid, lo = sums
    onto = lambda a, term: roll(a, LANES - term * m_cols, 1)
    return ((lo + onto(hi, 2) + onto(mid, 1)) + (mid + onto(hi, 1))) + hi


def _pre_kernel(x_ref, phi_ref, ab_ref, o_ref, maps_ref, packed, *,
                spec: MhcSpec, c: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = spec.n

    @pl.when(pl.program_id(0) == 0)
    def _lay_out_phi():
        packed[...] = _packed_terms(phi_ref[...])

    rows = x_ref.shape[0]
    squares = jnp.zeros((rows, LANES), F32)
    sums = [jnp.zeros((rows, LANES), F32)] * 3
    for k0, step in _chunks(n * c):
        part = x_ref[:, k0:k0 + step]
        square = part * part
        for l0 in range(0, step, LANES):
            squares = squares + square[:, l0:l0 + LANES]
        sums = _against_packed(sums, part, packed[:, k0:k0 + step])
    raw = _six_terms(sums, pltpu.roll, spec.n_maps)
    rs = lax.rsqrt(jnp.sum(squares, axis=1, keepdims=True) / (n * c)
                   + spec.norm_eps)
    # Past the 24 map columns the scale and the bias are zero.
    pre = raw * rs * ab_ref[0:1, :] + ab_ref[1:2, :]
    maps = _maps_rows_on_lanes(_padded_rows(pre, LANES).T, spec,
                               pltpu.roll).T[0:rows]
    maps_ref[...] = maps
    for c0, step in _chunks(c):
        acc = maps[:, 0:1] * x_ref[:, c0:c0 + step]
        for i in range(1, n):
            acc = acc + maps[:, i:i + 1] * x_ref[:, i * c + c0:
                                                 i * c + c0 + step]
        o_ref[:, c0:c0 + step] = acc


def _post_kernel(x_ref, y_ref, maps_ref, o_ref, *, spec: MhcSpec, c: int):
    n = spec.n
    maps = maps_ref[...]
    for c0, step in _chunks(c):
        streams = [x_ref[:, j * c + c0:j * c + c0 + step] for j in range(n)]
        y = y_ref[:, c0:c0 + step].astype(F32)
        for i in range(n):
            acc = maps[:, n + i:n + i + 1] * y
            for j in range(n):
                at = 2 * n + n * i + j
                acc = acc + maps[:, at:at + 1] * streams[j]
            o_ref[:, i * c + c0:i * c + c0 + step] = acc


def _row_tile(rows: int):
    """(rows a grid step, rows after padding): whole sublane tiles."""
    tile = min(ROW_TILE, -(-rows // 8) * 8)
    return tile, -(-rows // tile) * tile


def _padded_rows(a, rows: int):
    return a if a.shape[0] == rows else jnp.pad(
        a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _takes_kernels(spec: MhcSpec, c: int, interpret) -> bool:
    """The kernels' lane arithmetic is written for four streams of
    whole 128-lane tiles."""
    return bool((interpret or jax.default_backend() == "tpu")
                and spec.n == 4 and c % LANES == 0)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def mhc_pre(streams, phi_t, alpha, bias, *, spec: MhcSpec,
            interpret: Optional[bool] = None):
    """streams [R, n C] float32 -> (x [R, C] float32: the streams'
    weighted sum H_pre X, what the sub-layer's norm reads; maps [R,
    128] float32: H_pre ++ H_post ++ vec(H_res) ++ zeros, for
    `mhc_post`)."""
    rows, n = streams.shape[0], spec.n
    c = streams.shape[1] // n
    if not _takes_kernels(spec, c, interpret):
        return pre_reference(streams, phi_t, alpha, bias, spec)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, padded = _row_tile(rows)
    flat = _padded_rows(streams.astype(F32), padded)
    x, maps = pl.pallas_call(
        functools.partial(_pre_kernel, spec=spec, c=c),
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((tile, n * c), lambda r: (r, 0)),
                  pl.BlockSpec((spec.n_maps, n * c), lambda r: (0, 0)),
                  pl.BlockSpec((2, LANES), lambda r: (0, 0))],
        out_specs=[pl.BlockSpec((tile, c), lambda r: (r, 0)),
                   pl.BlockSpec((tile, LANES), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((padded, c), F32),
                   jax.ShapeDtypeStruct((padded, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((LANES, n * c), BF16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=bool(interpret),
        name=PRE,
        metadata={"kernel": PRE},
    )(flat, phi_t.astype(F32), _expanded(alpha, bias, n))
    return x[:rows], maps[:rows]


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def mhc_post(streams, y, maps, *, spec: MhcSpec,
             interpret: Optional[bool] = None):
    """streams [R, n C] float32, y [R, C] (the sub-layer's output),
    maps [R, 128] (`mhc_pre`'s) -> X' [R, n C] float32 = H_res X +
    H_post^T y. The kernel writes X' over X (aliased): donated, a
    sub-layer keeps one copy of the streams."""
    rows, n = streams.shape[0], spec.n
    c = streams.shape[1] // n
    if not _takes_kernels(spec, c, interpret):
        return post_reference(streams, y, maps, spec)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, padded = _row_tile(rows)
    flat = _padded_rows(streams.astype(F32), padded)
    out = pl.pallas_call(
        functools.partial(_post_kernel, spec=spec, c=c),
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((tile, n * c), lambda r: (r, 0)),
                  pl.BlockSpec((tile, c), lambda r: (r, 0)),
                  pl.BlockSpec((tile, LANES), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((tile, n * c), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, n * c), F32),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=bool(interpret),
        name=POST,
        metadata={"kernel": POST},
    )(flat, _padded_rows(y.astype(F32), padded), _padded_rows(maps, padded))
    return out[:rows]
