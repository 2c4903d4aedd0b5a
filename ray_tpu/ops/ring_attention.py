"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

The reference has NO sequence parallelism (SURVEY.md §2.4: grep over the Ray
tree finds no ring-attention/Ulysses implementation — long context is deferred
to vLLM/torch). Here it is a first-class op: the sequence dimension is sharded
over the ``sp`` mesh axis, and K/V blocks rotate around the ring via
`lax.ppermute` (one ICI hop per step) while each device accumulates its local
queries' attention with a numerically-stable online softmax (flash-attention
style m/l running stats).

Causality is enforced by *global position* comparison, so the blocks never
need re-ordering: a device holding queries at positions [2048:4096) simply
masks out rotated K/V positions above its own.

Used by `models/llama.py` whenever the mesh has sp > 1; compute per step stays
a large [B, Sq/sp, Sk/sp] matmul that tiles onto the MXU.
"""

from __future__ import annotations

import functools
from typing import Optional

from jax import lax, shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import NEG_INF, online_softmax_update


def _ring_attention_local(q, k, v, q_pos, k_pos, *, axis_name: str,
                          scale: Optional[float] = None):
    """Per-shard body (runs inside shard_map). Shapes are the LOCAL shard:
    q [B, Sq, H, D], k/v [B, Sk, KH, D], q_pos/k_pos [B, S*].

    K/V rotate around the ring UN-repeated ([…,KH,D]); GQA expansion to the
    full query-head count happens inside `online_softmax_update`, after the
    ppermute — so each ICI hop carries only KH/H of the naive bytes.
    """
    n = lax.psum(1, axis_name)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, sq, heads, d = q.shape

    # Build the accumulators FROM q so they carry exactly q's varying-axes
    # type (sp plus any dp/fsdp/tp axes the caller sharded over) — required
    # for a well-typed fori_loop carry under shard_map's vma tracking.
    qz = jnp.transpose(q.astype(jnp.float32), (0, 2, 1, 3)) * 0.0  # [B,H,Sq,D]
    m0 = qz[..., 0] + NEG_INF
    l0 = qz[..., 0]
    o0 = qz
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(_, carry):
        m, l, o, kc, vc, kpc = carry
        m, l, o = online_softmax_update(q, kc, vc, q_pos, kpc, m, l, o, scale)
        # Rotate K/V (and their global positions) one hop around the ring.
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        kpc = lax.ppermute(kpc, axis_name, perm)
        return m, l, o, kc, vc, kpc

    m, l, o, _, _, _ = lax.fori_loop(0, n, step, (m0, l0, o0, k, v, k_pos))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)   # [B,Sq,H,D]


def ring_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    q_positions: jnp.ndarray, kv_positions: jnp.ndarray,
    *, mesh: Mesh, sp_axis: str = "sp",
    batch_spec=("dp", "fsdp"), heads_axis: str = "tp",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel causal attention over ``mesh[sp_axis]``.

    Inputs are GLOBAL arrays (inside jit); shard_map splits seq over sp.
    q/k/v: [B, S, H|KH, D]; positions: [B, S] global token positions.
    """
    qkv_spec = P(batch_spec, sp_axis, heads_axis, None)
    pos_spec = P(batch_spec, sp_axis)
    fn = functools.partial(_ring_attention_local, axis_name=sp_axis, scale=scale)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pos_spec, pos_spec),
        out_specs=qkv_spec,
    )(q, k, v, q_positions, kv_positions)
