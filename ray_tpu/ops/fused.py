"""Fused Pallas TPU kernels for the per-layer model-path glue.

The transformer block's non-matmul work — RMSNorm, rotary embedding,
SwiGLU — is memory-bound elementwise/reduction glue between matmuls.
Left to XLA it becomes several HBM round trips per block (norm reads x,
rope reads q and k separately and recomputes cos/sin twice, the silu
and multiply each materialize a [B,S,F] temp). Each op here makes ONE
pass over its operands in VMEM:

- ``fused_rms_norm``          — fp32 normalize + scale in one pass.
- ``fused_rms_norm_residual`` — residual add folded into the next norm:
  returns ``(normed, summed)`` so the block's ``x = x + attn; h =
  rms_norm(x)`` pair reads/writes ``x`` once.
- ``fused_qk_rope``           — one kernel rotates BOTH the q and k
  projection outputs, computing the cos/sin tables once per position
  (the unfused path recomputes them per tensor). The one of the four
  that a cell runs: `models/llama.py`'s whole-sequence block takes it by
  what it observes, under a multi-device mesh too (`fused_qk_rope`).
- ``fused_swiglu``            — ``silu(gate) * up`` in fp32 without a
  materialized intermediate.

Each op follows the ``ops/decode_attention.py`` idiom: a pure-jnp
reference (the exact pre-fusion formulation), a Pallas kernel, and a
dispatcher that runs the kernel on TPU (or under ``interpret=True`` on
CPU — the test suite checks kernel-vs-reference equivalence that way)
and the reference elsewhere. Every op carries a custom VJP (backward in
plain jnp, checked against autodiff of the reference) so the TRAINING
path can use the fused forward under ``jax.checkpoint``; models opt in
via ``LlamaConfig.fused_ops`` (rope's VJP is its kernel again).

Kernel-body discipline (now ENFORCED by jax-lint's
``pallas-shape-rules`` — ``python -m ray_tpu.devtools.lint --family
jax``): every intermediate stays >= 2D (reductions carry
``keepdims=True``), iota is an INTEGER ``lax.broadcasted_iota`` (never
a 1D ``jnp.arange``, never a float one), and no reshape happens inside
a kernel body — relayouts belong to the host-side wrappers and
BlockSpecs, whose last two block dims are multiples of (8, 128) or the
whole dim. Every kernel here therefore works on a 2D [rows, lanes] view
of its operands. The interpreter checks none of this:
``tests/test_chip_compile.py`` asks the chip's compiler.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.norms import rms_norm as rms_norm_reference
from ray_tpu.ops.rotary import apply_rope as apply_rope_reference
from ray_tpu.ops.rotary import rope_frequencies

_ROW_BLOCKS = (256, 128, 64, 32, 16, 8)
_COL_BLOCKS = (1024, 512, 256, 128)


def _row_block(n: int, cap: int = 128) -> int:
    """Largest sublane-aligned block (a multiple of 8, at most ``cap``)
    that divides ``n`` rows; one block spanning all rows when none does
    (a block dim must be a multiple of 8 or the whole dim)."""
    return next((c for c in _ROW_BLOCKS if c <= cap and n % c == 0), n)


def _col_block(n: int) -> int:
    for c in _COL_BLOCKS:
        if n % c == 0:
            return c
    return n  # small/ragged feature dim: one block spans it


def _use_kernel(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "tpu"


# ---------------------------------------------------------------- RMSNorm

def _rms_kernel(x_ref, s_ref, o_ref, *, eps: float):
    xf = x_ref[...].astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(jnp.float32))
                  ).astype(o_ref.dtype)


def _rms_res_kernel(x_ref, r_ref, s_ref, o_ref, sum_ref, *, eps: float):
    # The residual add happens in the STORAGE dtype (matching the
    # unfused ``x = x + attn`` it replaces), then the norm upcasts.
    u = x_ref[...] + r_ref[...]
    sum_ref[...] = u
    uf = u.astype(jnp.float32)
    var = jnp.mean(uf * uf, axis=-1, keepdims=True)
    y = uf * lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(jnp.float32))
                  ).astype(o_ref.dtype)


def _rms_impl(x, scale, eps, interpret, residual=None):
    import jax.experimental.pallas as pl

    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    bn = _row_block(n)
    s2 = scale.reshape(1, d)
    row_spec = pl.BlockSpec((bn, d), lambda i: (i, 0))
    scale_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    if residual is None:
        out = pl.pallas_call(
            functools.partial(_rms_kernel, eps=eps),
            grid=(n // bn,),
            in_specs=[row_spec, scale_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
            interpret=interpret,
            name="rtpu_fused_rms_norm",
            metadata={"kernel": "rtpu_fused_rms_norm"},
        )(x2, s2)
        return out.reshape(shape)
    r2 = residual.reshape(-1, d)
    out, summed = pl.pallas_call(
        functools.partial(_rms_res_kernel, eps=eps),
        grid=(n // bn,),
        in_specs=[row_spec, row_spec, scale_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((n, d), x.dtype),
                   jax.ShapeDtypeStruct((n, d), residual.dtype)],
        interpret=interpret,
        name="rtpu_fused_rms_norm_residual",
        metadata={"kernel": "rtpu_fused_rms_norm_residual"},
    )(x2, r2, s2)
    return out.reshape(shape), summed.reshape(shape)


def _rms_bwd_math(u, scale, gy, eps):
    """Backward of y = rms_norm(u) * (1 + scale) w.r.t. (u, scale)."""
    uf = u.astype(jnp.float32)
    gf = gy.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(uf * uf, axis=-1, keepdims=True) + eps)
    n_ = uf * r
    dn = gf * (1.0 + scale.astype(jnp.float32))
    du = r * (dn - n_ * jnp.mean(dn * n_, axis=-1, keepdims=True))
    ds = jnp.sum(gf * n_, axis=tuple(range(u.ndim - 1)))
    return du, ds


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_p(x, scale, eps, interpret):
    if not _use_kernel(interpret):
        return rms_norm_reference(x, scale, eps)
    return _rms_impl(x, scale, eps, interpret)


def _rms_fwd(x, scale, eps, interpret):
    return _rms_p(x, scale, eps, interpret), (x, scale)


def _rms_bwd(eps, interpret, res, gy):
    x, scale = res
    du, ds = _rms_bwd_math(x, scale, gy, eps)
    return du.astype(x.dtype), ds.astype(scale.dtype)


_rms_p.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rms_res_p(x, residual, scale, eps, interpret):
    if not _use_kernel(interpret):
        u = x + residual
        return rms_norm_reference(u, scale, eps), u
    return _rms_impl(x, scale, eps, interpret, residual=residual)


def _rms_res_fwd(x, residual, scale, eps, interpret):
    y, u = _rms_res_p(x, residual, scale, eps, interpret)
    return (y, u), (u, scale)


def _rms_res_bwd(eps, interpret, res, gs):
    u, scale = res
    gy, gsum = gs
    du, ds = _rms_bwd_math(u, scale, gy, eps)
    du = du + gsum.astype(jnp.float32)
    return (du.astype(u.dtype), du.astype(u.dtype), ds.astype(scale.dtype))


_rms_res_p.defvjp(_rms_res_fwd, _rms_res_bwd)


def fused_rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5,
                   *, interpret: bool = False) -> jnp.ndarray:
    """One-pass RMSNorm (fp32 compute): Pallas kernel on TPU / under
    ``interpret``; the exact ``ops.norms.rms_norm`` reference elsewhere.
    Differentiable (custom VJP) either way."""
    return _rms_p(x, scale, float(eps), bool(interpret))


def fused_rms_norm_residual(x: jnp.ndarray, residual: jnp.ndarray,
                            scale: jnp.ndarray, eps: float = 1e-5,
                            *, interpret: bool = False):
    """Residual add folded into the norm: returns ``(normed, x +
    residual)`` in one pass over the operands."""
    return _rms_res_p(x, residual, scale, float(eps), bool(interpret))


# ------------------------------------------------------------------ RoPE

def _rope_kernel(pos_ref, inv_ref, q_ref, k_ref, oq_ref, ok_ref, *,
                 d: int):
    # Rows are tokens (sublanes), lanes are the flattened (head,
    # head_dim) axis, so nothing is relaid out inside the kernel: the
    # [rows, 1] positions broadcast along lanes against the [1, w]
    # frequency table, and the half-rotation partner of lane j (j +-
    # d/2 inside its head) comes from two whole-axis lane rolls.
    from jax.experimental.pallas import tpu as pltpu

    half = d // 2
    ang = pos_ref[...].astype(jnp.float32) * inv_ref[...]     # [bn, w]
    w = ang.shape[-1]
    first = lax.broadcasted_iota(jnp.int32, (1, w), 1) % d < half
    cos = jnp.cos(ang)
    sin = jnp.where(first, -jnp.sin(ang), jnp.sin(ang))
    for ref, out in ((q_ref, oq_ref), (k_ref, ok_ref)):
        x = ref[...].astype(jnp.float32)                      # [bn, n]
        n = x.shape[-1]
        reps = n // w
        c = jnp.concatenate([cos] * reps, axis=-1) if reps > 1 else cos
        s = jnp.concatenate([sin] * reps, axis=-1) if reps > 1 else sin
        lo = lax.broadcasted_iota(jnp.int32, (1, n), 1) % d < half
        partner = jnp.where(lo, pltpu.roll(x, n - half, 1),
                            pltpu.roll(x, half, 1))
        out[...] = (x * c + partner * s).astype(out.dtype)


def _rope_table_lanes(d: int) -> int:
    """Lanes of the in-kernel cos/sin table: one whole lane tile of
    heads."""
    return d * 128 // math.gcd(d, 128)


def qk_rope_kernel_fits(rows: int, nq: int, nk: int, d: int) -> bool:
    """Whether ``rows`` tokens of q [rows, nq] and k [rows, nk] (a
    row's heads of size ``d`` side by side) are the kernel's dense
    operands: both widths whole lane tiles of heads, so that no lane of
    a block is padding and the table tiles out lane-aligned, and rows
    that divide into sublane-aligned blocks. `models/llama.py` takes the
    kernel for its whole-sequence blocks where this holds for a device's
    share (`qk_rope_on_mesh_fits`) and `apply_rope` elsewhere."""
    w = _rope_table_lanes(d)
    return rows % 8 == 0 and nq % w == 0 and nk % w == 0


def _rope_impl(q, k, positions, d, theta, interpret):
    import jax.experimental.pallas as pl

    b, s, nq = q.shape
    nk = k.shape[2]
    rows = b * s
    # Cap the f32 working tile at 1 MiB: the kernel holds a handful of
    # [bn, nq] temporaries in VMEM beside the pipelined blocks (256 rows
    # at the train cell's 1,024 lanes: 0.73 ms a call of 32,768 rows on a
    # v5e where 128 rows take 0.78, PERF.md section 6, PR 63).
    bn = _row_block(rows, cap=max(8, (1 << 18) // nq))
    # One whole lane tile of heads (lcm(d, 128)) when both widths are
    # multiples of it — tiling it out is then a lane-aligned
    # concatenate — else one head.
    w = _rope_table_lanes(d)
    if nq % w or nk % w:
        w = d
    # The same frequencies as the reference, tiled over the heads of
    # one table width (lane j rotates with frequency j % (d/2)).
    inv = jnp.tile(rope_frequencies(d, theta), 2 * (w // d)).reshape(1, w)
    qspec = pl.BlockSpec((bn, nq), lambda i: (i, 0))
    kspec = pl.BlockSpec((bn, nk), lambda i: (i, 0))
    oq, ok = pl.pallas_call(
        functools.partial(_rope_kernel, d=d),
        grid=(rows // bn,),
        in_specs=[pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                  pl.BlockSpec((1, w), lambda i: (0, 0)),
                  qspec, kspec],
        out_specs=[qspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((rows, nq), q.dtype),
                   jax.ShapeDtypeStruct((rows, nk), k.dtype)],
        interpret=interpret,
        name="rtpu_fused_qk_rope",
        metadata={"kernel": "rtpu_fused_qk_rope"},
    )(positions.reshape(rows, 1), inv, q.reshape(rows, nq),
      k.reshape(rows, nk))
    return oq.reshape(q.shape), ok.reshape(k.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rope_qk_p(q, k, positions, d, theta, interpret):
    """q [B, S, H·d] and k [B, S, KH·d], a row's heads side by side."""
    if not _use_kernel(interpret):
        return tuple(
            apply_rope_reference(x.reshape(*x.shape[:2], -1, d), positions,
                                 theta).reshape(x.shape) for x in (q, k))
    return _rope_impl(q, k, positions, d, theta, interpret)


def _rope_qk_fwd(q, k, positions, d, theta, interpret):
    return _rope_qk_p(q, k, positions, d, theta, interpret), (positions,)


def _rope_qk_bwd(d, theta, interpret, res, gs):
    # Rotation is orthogonal: the VJP rotates the cotangents by -angle,
    # i.e. the same kernel with negated positions.
    (positions,) = res
    gq, gk = gs
    dq, dk = _rope_qk_p(gq, gk, -positions, d, theta, interpret)
    dpos = np.zeros(positions.shape, jax.dtypes.float0)
    return dq, dk, dpos


_rope_qk_p.defvjp(_rope_qk_fwd, _rope_qk_bwd)

# A whole-sequence q, k and their positions over the repo's mesh: batch
# over the data axes, rows over ``sp``, a row's heads over ``tp``.
_ROPE_ROWS = P(("dp", "fsdp"), "sp")
_ROPE_FLAT = P(("dp", "fsdp"), "sp", "tp")


def qk_rope_on_mesh_fits(batch: int, seq: int, heads: int, kv_heads: int,
                         d: int, mesh: Optional[Mesh]) -> bool:
    """`qk_rope_kernel_fits` for a device's share of q [batch, seq,
    heads·d] and k [.., kv_heads·d] as `fused_qk_rope` splits them over
    ``mesh`` (every device its own rows and heads; none, or one device:
    the whole)."""
    sizes = dict(mesh.shape) if mesh is not None and mesh.size > 1 else {}
    data = sizes.get("dp", 1) * sizes.get("fsdp", 1)
    sp, tp = sizes.get("sp", 1), sizes.get("tp", 1)
    if batch % data or seq % sp or heads % tp or kv_heads % tp:
        return False
    return qk_rope_kernel_fits(batch // data * (seq // sp),
                               heads // tp * d, kv_heads // tp * d, d)


def fused_qk_rope(q: jnp.ndarray, k: jnp.ndarray, positions: jnp.ndarray,
                  theta: float = 500000.0, *, head_dim: Optional[int] = None,
                  interpret: bool = False, mesh: Optional[Mesh] = None):
    """Rotate the q AND k projection outputs in one kernel: q [B,S,H,D],
    k [B,S,KH,D], positions [B,S] int; or, with ``head_dim`` given, q
    [B,S,H·D] and k [B,S,KH·D] with a row's heads side by side, which is
    what the kernel works on (the 4-D form is reshaped to it: on the
    chip a pass over the array where D is half a lane tile). The cos/sin
    tables are computed once per position and lane tile (the unfused
    path recomputes them per tensor and head). Returns ``(q_rot,
    k_rot)`` in the operands' form; matches two
    ``ops.rotary.apply_rope`` calls, and its VJP is the same kernel at
    negated positions.

    Mosaic kernels cannot be auto-partitioned: on a ``mesh`` of more
    than one device the call runs per shard under a `shard_map` that is
    manual over every axis, the seam `ops/attention.py` gives the flash
    kernels (rope treats rows and heads alike: no collective).

    Where it runs: `models/llama.py`'s whole-sequence, no-cache block
    (the train step's layer body, forward and backward, under the tp
    ring too) takes it on the TPU wherever a device's share of q and k
    is dense (`qk_rope_on_mesh_fits`), read off the backend, the mesh
    and the shapes; the cache paths only under
    ``LlamaConfig.fused_ops``."""
    flat = head_dim is not None
    d = head_dim if flat else q.shape[-1]
    shapes = q.shape, k.shape
    if not flat:
        q, k = (x.reshape(*x.shape[:2], -1) for x in (q, k))
    rope = lambda q, k, pos: _rope_qk_p(q, k, pos, int(d), float(theta),
                                        bool(interpret))
    if mesh is not None and mesh.size > 1:
        rope = shard_map(
            rope, mesh=mesh, in_specs=(_ROPE_FLAT, _ROPE_FLAT, _ROPE_ROWS),
            out_specs=(_ROPE_FLAT, _ROPE_FLAT),
            # pallas_call's output carries no varying-axes type.
            check_vma=False)
    q, k = rope(q, k, jnp.broadcast_to(positions, q.shape[:2]))
    return q.reshape(shapes[0]), k.reshape(shapes[1])


# ---------------------------------------------------------------- SwiGLU

def swiglu_reference(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    """The unfused formulation from the block: ``silu(gate) * up``
    computed in fp32 (kernel and reference share the upcast)."""
    out = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return out.astype(gate.dtype)


def _swiglu_kernel(g_ref, u_ref, o_ref):
    gf = g_ref[...].astype(jnp.float32)
    uf = u_ref[...].astype(jnp.float32)
    o_ref[...] = (gf * jax.nn.sigmoid(gf) * uf).astype(o_ref.dtype)


def _swiglu_impl(gate, up, interpret):
    import jax.experimental.pallas as pl

    shape = gate.shape
    f = shape[-1]
    g2 = gate.reshape(-1, f)
    n = g2.shape[0]
    bn = _row_block(n)
    bf = _col_block(f)
    spec = pl.BlockSpec((bn, bf), lambda i, j: (i, j))
    out = pl.pallas_call(
        _swiglu_kernel,
        grid=(n // bn, f // bf),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, f), gate.dtype),
        interpret=interpret,
        name="rtpu_fused_swiglu",
        metadata={"kernel": "rtpu_fused_swiglu"},
    )(g2, up.reshape(-1, f))
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _swiglu_p(gate, up, interpret):
    if not _use_kernel(interpret):
        return swiglu_reference(gate, up)
    return _swiglu_impl(gate, up, interpret)


def _swiglu_fwd(gate, up, interpret):
    return _swiglu_p(gate, up, interpret), (gate, up)


def _swiglu_bwd(interpret, res, g):
    gate, up = res
    gf = gate.astype(jnp.float32)
    uf = up.astype(jnp.float32)
    cot = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(gf)
    dgate = cot * uf * sig * (1.0 + gf * (1.0 - sig))
    dup = cot * gf * sig
    return dgate.astype(gate.dtype), dup.astype(up.dtype)


_swiglu_p.defvjp(_swiglu_fwd, _swiglu_bwd)


def fused_swiglu(gate: jnp.ndarray, up: jnp.ndarray,
                 *, interpret: bool = False) -> jnp.ndarray:
    """``silu(gate) * up`` in one pass (fp32 compute, no materialized
    silu intermediate)."""
    return _swiglu_p(gate, up, bool(interpret))
