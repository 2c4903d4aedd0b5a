"""Llama-3-class decoder, TPU-first (pure-functional JAX pytree params).

This is the flagship model for the framework's Train/Serve paths and the
benchmark target from BASELINE.json ("Llama-3 8B ... pretrain + inference").
The reference orchestrates torch models it does not own; here the model is
native so that sharding, remat, and kernels are co-designed:

- Parameters are a pytree with per-dimension *logical names*
  (`param_logical_axes`) mapped to mesh axes by `parallel/mesh.py` —
  fsdp/tp sharding is a table, not code.
- Layers are stacked on a leading ``layers`` dim and executed with
  `lax.scan` + `jax.checkpoint` (one compiled block, O(1) compile time in
  depth, remat for HBM).
- Attention dispatches to ring attention (`ops/ring_attention.py`) when the
  mesh's ``sp`` axis > 1 — long context is a mesh shape, not a code change.
- Decode runs against a preallocated KV cache with position-based masking
  (static shapes; serving reuses the same block code).
"""

from __future__ import annotations

import dataclasses
import math
import sys
from typing import Any, Dict, Optional, Tuple

import jax
from jax import lax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ray_tpu.ops import (
    FLASH_SAVED,
    apply_rope,
    causal_attention,
    decode_attention,
    decode_step_rows,
    full_causal_attention,
    fused_qk_rope,
    fused_rms_norm,
    fused_rms_norm_residual,
    fused_swiglu,
    qk_rope_on_mesh_fits,
    ring_attention,
    rms_norm,
)
from ray_tpu.models.common import _write_rows
from ray_tpu.models.quant import QuantTensor
from ray_tpu.parallel import collective_matmul
from ray_tpu.parallel.mesh import constrain

Params = Dict[str, Any]
# The optional mechanisms of the serving engine this family offers (the
# default is none): each was written for this module's {k, v} cache and
# weight tree (`serve/engine/decode_loop.py` ``ENGINE_OPTIONS``).
ENGINE_OFFERS = ("quantize", "spec_draft_len", "role", "kv_fleet")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # The tests' hook, one name on every served family's configuration:
    # the decode step's attention kernel (ops/decode_attention.py, which
    # itself picks kernel on the TPU and jnp twin elsewhere) runs under
    # the Pallas interpreter off the TPU.
    interpret_kernels: bool = False
    # Fused Pallas kernels for the per-layer glue (ops/fused.py):
    # RMSNorm(+residual), SwiGLU and, on the cache paths, rotary folded
    # over the QK projection outputs each become one VMEM pass instead
    # of several XLA HBM round trips. True = fused kernels on TPU, jnp
    # references elsewhere (same custom-VJP wrapper either way, so the
    # train path fuses too); "interpret" = run the kernels under the
    # Pallas interpreter off-TPU (equivalence-test escape hatch); False =
    # the plain unfused ops. The whole-sequence block WITHOUT a cache
    # does not ask here for its rope: `_rope_kernel` reads the backend,
    # the mesh and the shapes.
    fused_ops: Any = False
    # jax.checkpoint policy name: what a layer keeps for its backward
    # beside its input, in bytes of a batch B of S tokens over the whole
    # mesh (D = d_model, H = n_heads, KH = n_kv_heads, F = d_ff; bf16):
    # "nothing" = 0: the backward recomputes the whole block;
    # "attention" = 2·B·S·D·(2 + KH/H) + 4·B·H·S: q and k after rope, the
    #   flash kernel's output (each with a row's heads side by side, so
    #   that head size 64 is not padded to 128 lanes) and its row
    #   statistic (`_SAVED`): the backward runs neither q's and k's
    #   products and rope nor the forward kernel again. At B 32, S 2048,
    #   D 2048, H = KH = 32 on four chips 203 MB a layer a device, and
    #   the step program 15.2 GB for 24 layers where "nothing" is 9.7. A
    #   user of "nothing" at the memory's limit sets it back. Off the TPU
    #   there is no kernel, and q and k alone are kept;
    # "dots" = every matmul's output, 2·B·S·(5·D + 2·F) with KH = H:
    #   872 MB a layer a device there, 21 GB, which is why it is not
    #   the default.
    remat_policy: str = "attention"
    tie_embeddings: bool = False

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step (``serve/engine/README.md``)."""
        return sys.modules[__name__]

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def param_count(self) -> int:
        d, f, v, l = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        attn = d * self.n_heads * self.head_dim * 2 + d * self.n_kv_heads * self.head_dim * 2
        mlp = 3 * d * f
        per_layer = attn + mlp + 2 * d
        head = 0 if self.tie_embeddings else d * v
        return v * d + l * per_layer + d + head

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Training FLOPs/token: 6*N_matmul + attention quadratic term.

        The input embedding table is a gather, not a matmul, so it is excluded
        — unless tied, in which case the same table IS the output matmul.
        """
        s = seq_len or self.max_seq_len
        gather_only = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        n_matmul = self.param_count() - gather_only
        attn_flops = 12 * self.n_layers * self.d_model * s  # qk^T + pv, fwd+bwd
        return 6 * n_matmul + attn_flops


# Presets ------------------------------------------------------------------

LLAMA3_8B = LlamaConfig()
LLAMA3_1B = LlamaConfig(vocab_size=128256, d_model=2048, n_layers=16,
                        n_heads=32, n_kv_heads=8, d_ff=8192)
LLAMA3_70B = LlamaConfig(d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                         d_ff=28672)


def tiny_config(**kw) -> LlamaConfig:
    base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=128, max_seq_len=128, dtype=jnp.float32,
                remat=False)
    base.update(kw)
    return LlamaConfig(**base)


# Parameter init + logical sharding ---------------------------------------

def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Per-dimension logical names for every parameter (see
    `parallel.mesh.DEFAULT_RULES` for the mapping to mesh axes)."""
    tree = {
        "embed": ("vocab", "embed"),
        "blocks": {
            "ln_attn": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "ln_mlp": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "ln_out": ("embed",),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = ("embed", "vocab")
    return tree


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    d, hd, h, kh, f, v, l = (cfg.d_model, cfg.head_dim, cfg.n_heads,
                             cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size,
                             cfg.n_layers)
    keys = jax.random.split(key, 8)
    dt = cfg.dtype

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    params: Params = {
        "embed": norm(keys[0], (v, d), d),
        "blocks": {
            "ln_attn": jnp.zeros((l, d), dt),
            "wq": norm(keys[1], (l, d, h, hd), d),
            "wk": norm(keys[2], (l, d, kh, hd), d),
            "wv": norm(keys[3], (l, d, kh, hd), d),
            "wo": norm(keys[4], (l, h, hd, d), h * hd),
            "ln_mlp": jnp.zeros((l, d), dt),
            "w_gate": norm(keys[5], (l, d, f), d),
            "w_up": norm(keys[6], (l, d, f), d),
            "w_down": norm(keys[7], (l, f, d), f),
        },
        "ln_out": jnp.zeros((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(jax.random.fold_in(key, 99), (d, v), d)
    return params


# Forward ------------------------------------------------------------------

# `checkpoint_name`s that `remat_policy="attention"` keeps: q and k after
# rope (`_block`) and what the flash kernels' backward reads of their
# forward (named where the residuals are made, `ops/flash_attention.py`).
_Q_ROPE, _K_ROPE = "q_rope", "k_rope"
_SAVED = (_Q_ROPE, _K_ROPE, *FLASH_SAVED)


def _remat_policy(cfg):
    """The `jax.checkpoint` policy ``cfg.remat_policy`` names (Mixtral's
    and the pipeline's layer bodies ask here too)."""
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "attention": jax.checkpoint_policies.save_only_these_names(*_SAVED),
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    if cfg.remat_policy not in policies:
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}; "
                         f"expected one of {sorted(policies)}")
    return policies[cfg.remat_policy]



def _wdot(eqn: str, x, w):
    """Weight-side einsum accepting dense arrays OR ``QuantTensor``
    (weight-only int8, ``models/quant.py``): the int8 weights widen to
    the activation dtype INSIDE the dot (XLA streams them from HBM at
    one byte/element) and the per-output-channel fp32 scale right-
    broadcasts against the output — every weight einsum in this model
    routes through here so quantized pytrees work engine-wide."""
    if isinstance(w, QuantTensor):
        y = jnp.einsum(eqn, x, w.q.astype(x.dtype))
        return (y.astype(jnp.float32) * w.scale).astype(x.dtype)
    return jnp.einsum(eqn, x, w)


def _head_matmul(x, params, cfg: LlamaConfig):
    """Final LM-head projection (tied embeddings are never quantized)."""
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,dv->bsv", x, params["embed"].T)
    return _wdot("bsd,dv->bsv", x, params["lm_head"])


def _norm(x, scale, cfg: LlamaConfig):
    """RMSNorm with the ``cfg.fused_ops`` dispatch — the SINGLE decode
    point for the flag (train/decode paths must not re-derive it and
    drift)."""
    if cfg.fused_ops:
        return fused_rms_norm(x, scale, cfg.norm_eps,
                              interpret=cfg.fused_ops == "interpret")
    return rms_norm(x, scale, cfg.norm_eps)


def _attention_dispatch(q, k, v, q_pos, kv_pos, cfg, mesh: Optional[Mesh],
                        standard_positions: bool = False):
    """``standard_positions`` is a STATIC flag set by the caller when positions
    are the plain [0..S) arange — that (and only that) unlocks the fused TPU
    kernel's built-in causal mask; custom positions (packed documents, chunked
    prefill) keep explicit position-based masking."""
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        return ring_attention(q, k, v, q_pos, kv_pos, mesh=mesh)
    if standard_positions:
        return full_causal_attention(q, k, v, mesh=mesh)
    return full_causal_attention(q, k, v, q_positions=q_pos,
                                 kv_positions=kv_pos, mesh=mesh)


def _named(x, name: str):
    """``x`` [B, S, H, D] under a `checkpoint_name`, its heads side by
    side ([B, S, H·D]): what a policy keeps of it is then not padded to
    the chip's 128-lane tiles at head size 64."""
    return checkpoint_name(x.reshape(*x.shape[:2], -1), name).reshape(x.shape)


def _rope_kernel(x, layer, cfg: LlamaConfig, mesh: Optional[Mesh]) -> bool:
    """Whether a whole-sequence, no-cache `_block` rotates q and k by
    `ops.fused_qk_rope`'s Pallas kernel (forward and backward, behind the
    tp ring too): on the TPU (or where ``cfg.interpret_kernels`` asks for
    the interpreter), with dense weights, wherever a device's share of q
    and k is whole lane tiles of heads: as `use_fused_kernel` chooses the
    flash kernels, read off the backend, the mesh and the shapes, never
    asked for. Elsewhere `apply_rope`, which a head of 64 lanes costs ten
    times its bytes' time (PERF.md section 6, PR 63)."""
    if not (cfg.interpret_kernels or jax.default_backend() == "tpu") or any(
            isinstance(layer[w], QuantTensor) for w in ("wq", "wk", "wv")):
        return False
    return qk_rope_on_mesh_fits(*x.shape[:2], layer["wq"].shape[-2],
                                layer["wk"].shape[-2], cfg.head_dim, mesh)


def _tp_ring(seq_len: int, blocks, cfg: LlamaConfig, cache_kv=None) -> bool:
    """Whether `_block`'s four matmul groups run as collective matmuls
    (`parallel/collective_matmul.py`): no cache, dense weights, plain XLA
    around them (``cfg.fused_ops``' Pallas calls cannot be traced inside
    the ring's `shard_map`, so that option keeps the program it had;
    rope's kernel runs behind the ring, in a `shard_map` of its own), and
    a mesh and shapes that `collective_matmul.ring_size` accepts.
    ``blocks`` is one layer's weights or the stacked ones (sizes are
    read from the right)."""
    if cache_kv is not None or cfg.fused_ops or any(
            isinstance(w, QuantTensor) for w in blocks.values()):
        return False
    return collective_matmul.ring_size(
        seq_len, blocks["wq"].shape[-2], blocks["wk"].shape[-2],
        blocks["w_gate"].shape[-1]) > 1


def _write_each(cache, rows, starts):
    """rows [n, T, KH, D] -> cache[b, :, starts[b]:starts[b]+T] of an
    [n, KH, S, D] layer of n slots' rows, one write a row (n is a
    pair's 2). ``starts`` is bounded by `_block`'s contract, row by
    row."""
    rows = rows.swapaxes(1, 2).astype(cache.dtype)
    for b in range(rows.shape[0]):
        cache = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
            cache, rows[b:b + 1], (b, 0, starts[b], 0))
    return cache


def _block(x, layer, positions, cfg: LlamaConfig, mesh: Optional[Mesh],
           cache_kv=None, cache_index=None, standard_positions: bool = False):
    """One transformer block. Returns (x, new_kv | None).

    A whole sequence with no cache, traced under a mesh whose ``tp`` the
    shapes divide by (`_tp_ring`: read off the mesh and the shapes, never
    asked for), arrives and leaves sequence-sharded over tp
    (``res_seq``): the norms and the residual additions run on a shard,
    the four matmul groups move the other shards themselves under their
    products, SwiGLU is applied to each shard's products as they are
    made, and so is rotary embedding where it is plain XLA. Every other
    call multiplies with `_wdot` and leaves its collectives to the
    partitioner. With no cache, and where `_rope_kernel` finds a
    device's share of q and k dense, q, k and v are multiplied with a
    row's heads side by side and ONE Pallas call rotates q and k behind
    the products (behind the ring's assembly, where it runs)."""
    ring = _tp_ring(x.shape[1], layer, cfg, cache_kv)
    stream = ("batch", "res_seq" if ring else "seq", None)
    fused = bool(cfg.fused_ops)
    interp = cfg.fused_ops == "interpret"
    dense = cache_kv is None and _rope_kernel(x, layer, cfg, mesh)

    def rope(qkv, pos):
        q, k, v = qkv
        if cache_kv is not None and fused:
            return (*fused_qk_rope(q, k, pos, cfg.rope_theta,
                                   interpret=interp), v)
        return (apply_rope(q, pos, cfg.rope_theta),
                apply_rope(k, pos, cfg.rope_theta), v)

    def swiglu(gate_up):
        gate, up = gate_up
        return (fused_swiglu(gate, up, interpret=interp) if fused
                else jax.nn.silu(gate) * up,)

    h = _norm(x, layer["ln_attn"], cfg)
    wqkv = (layer["wq"], layer["wk"], layer["wv"])
    eqn, heads = "bsd,dhk->bshk", (None,)
    if dense:
        # The products are written with a row's heads side by side, as
        # the kernel and the policy's kept arrays want them: [D, H, hd]
        # -> [D, H·hd] is a view of a weight, the same of a product is a
        # pass over it where hd is half a lane tile. v's with them: a
        # product written [.., H, 64] takes twice the time of the flat
        # one (17.5 ms against 8.7 a step's 24 on a v5e, PR 63).
        wqkv = tuple(w.reshape(w.shape[0], -1) for w in wqkv)
        eqn, heads = "bsd,dn->bsn", ()
    if ring:
        q, k, v = collective_matmul.gather_matmul(
            eqn, h, wqkv, rowwise=None if dense else rope,
            row_args=() if dense else (positions,))
    else:
        q, k, v = (_wdot(eqn, h, w) for w in wqkv)
    q = constrain(q, ("batch", "seq", "heads", *heads))
    k = constrain(k, ("batch", "seq", "kv_heads", *heads))
    if dense:
        q, k = fused_qk_rope(q, k, positions, cfg.rope_theta,
                             head_dim=cfg.head_dim,
                             interpret=cfg.interpret_kernels, mesh=mesh)
        q, k, v = (a.reshape(*a.shape[:2], -1, cfg.head_dim) for a in (
            checkpoint_name(q, _Q_ROPE), checkpoint_name(k, _K_ROPE), v))
    else:
        if not ring:
            q, k, v = rope((q, k, v), positions)
        q, k = _named(q, _Q_ROPE), _named(k, _K_ROPE)

    new_kv = None
    if cache_kv is not None:
        ck, cv = cache_kv  # [B, KH, S, D] (engine-native, see init_kv_cache)
        # cache_index is bounded BY CONTRACT, not by a clamp: the engine
        # admits only prompt+new <= max_len (core._make_request) and
        # parks done-slot writes on a sacrificial row / the scratch
        # strip, so index+T never exceeds the cache extent. XLA would
        # clamp an overrun backwards over resident rows — callers
        # adding a new write path must re-establish the bound.
        if jnp.ndim(cache_index):
            # One index a ROW (`forward_last_rows_with_cache`): each
            # row's bucket written at its own, under the same contract.
            ck, cv = (_write_each(c, r, cache_index)
                      for c, r in ((ck, k), (cv, v)))
            cache_index = cache_index[:, None]
        else:
            ck = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
                ck, k.swapaxes(1, 2).astype(ck.dtype), (0, 0, cache_index, 0))
            cv = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
                cv, v.swapaxes(1, 2).astype(cv.dtype), (0, 0, cache_index, 0))
        new_kv = (ck, cv)
        kv_len = ck.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(kv_len), (x.shape[0], kv_len))
        kv_mask = kv_pos < (cache_index + k.shape[1])
        attn = causal_attention(q, ck.swapaxes(1, 2), cv.swapaxes(1, 2),
                                q_positions=positions,
                                kv_positions=kv_pos, kv_mask=kv_mask)
    else:
        attn = _attention_dispatch(q, k, v, positions, positions, cfg, mesh,
                                   standard_positions=standard_positions)
    attn = constrain(attn, ("batch", "seq", "heads", None))
    if ring:
        attn_out = collective_matmul.matmul_scatter(
            "bshk,hkd->bsd", attn, layer["wo"])
    else:
        attn_out = _wdot("bshk,hkd->bsd", attn, layer["wo"])
    attn_out = attn_out.astype(x.dtype)
    if fused:
        # Residual add folded into the next norm: one pass emits both
        # the normed MLP input and the updated residual stream.
        h, x = fused_rms_norm_residual(attn_out, x, layer["ln_mlp"],
                                       cfg.norm_eps, interpret=interp)
    else:
        x = x + attn_out
        h = rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    x = constrain(x, stream)
    w_mlp = (layer["w_gate"], layer["w_up"])
    if ring:
        # SwiGLU's rows stay in ring order: `w_down`'s ring takes them so.
        (ff,) = collective_matmul.gather_matmul(
            "bsd,df->bsf", h, w_mlp, rowwise=swiglu, in_sequence=False)
        down = collective_matmul.matmul_scatter(
            "bsf,fd->bsd", ff, layer["w_down"])
    else:
        (ff,) = swiglu(tuple(_wdot("bsd,df->bsf", h, w) for w in w_mlp))
        ff = constrain(ff, ("batch", "seq", "mlp"))
        down = _wdot("bsf,fd->bsd", ff, layer["w_down"])
    x = x + down.astype(x.dtype)
    return constrain(x, stream), new_kv


def forward(params: Params, tokens: jnp.ndarray, cfg: LlamaConfig,
            *, mesh: Optional[Mesh] = None,
            positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence forward: tokens [B,S] -> logits [B,S,V]."""
    x = forward_hidden(params, tokens, cfg, mesh=mesh, positions=positions)
    logits = _head_matmul(x, params, cfg)
    return constrain(logits, ("batch", "seq", "vocab"))


def forward_hidden(params: Params, tokens: jnp.ndarray, cfg: LlamaConfig,
                   *, mesh: Optional[Mesh] = None,
                   positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Tokens [B,S] -> final normed hidden states [B,S,D] (no LM head)."""
    b, s = tokens.shape
    standard = positions is None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    # Lookup against a d-unsharded view of the table: the table is stored
    # [vocab->tp, embed->fsdp], and a gather whose output is d-sharded cannot
    # be resharded to batch/seq-sharded activations without XLA's
    # "involuntary full rematerialization" (replicate-then-partition) on
    # every step. Gathering the embed dim first (the same per-use all-gather
    # ZeRO-3 applies to every weight) keeps the vocab-sharded gather
    # efficient (mask + psum over tp) and makes the activation reshard a
    # free local slice.
    table = constrain(params["embed"], ("vocab", None))
    x = jnp.take(table, tokens, axis=0).astype(cfg.dtype)
    x = constrain(x, ("batch", "seq", None))
    # Where the blocks run the tp ring (`_tp_ring`), the stream
    # between them is a sequence shard of tp: a local slice of the rows
    # just looked up (constrained to it directly, the partitioner gathers
    # the whole table instead), and ONE gather of the final hidden states
    # for the head, whose vocab split needs every row.
    ring = _tp_ring(s, params["blocks"], cfg)
    if ring:
        x = constrain(x, ("batch", "res_seq", None))

    def body(x, layer):
        y, _ = _block(x, layer, positions, cfg, mesh,
                      standard_positions=standard)
        return y, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    x, _ = lax.scan(body, x, params["blocks"])
    x = _norm(x, params["ln_out"], cfg)
    return constrain(x, ("batch", "seq", None)) if ring else x


def loss_fn(params: Params, tokens: jnp.ndarray, cfg: LlamaConfig,
            *, mesh: Optional[Mesh] = None,
            loss_mask: Optional[jnp.ndarray] = None,
            logits_chunk: int = 512) -> Tuple[jnp.ndarray, Dict]:
    """Next-token cross entropy over tokens [B, S].

    Targets are the left-shifted tokens with the final position masked out —
    shapes stay [B, S] (no :-1 slicing) so the sequence length remains evenly
    divisible by the ``sp`` mesh axis under sequence parallelism.

    The [B,S,V] logits are never materialized: cross-entropy runs in sequence
    chunks of ``logits_chunk`` under `jax.checkpoint`, so peak HBM holds one
    [B,C,V] chunk (fwd AND bwd — the chunk logits are recomputed from the
    hidden states in the backward pass). At V=128k this is the difference
    between fitting on a chip and an OOM.
    """
    x = forward_hidden(params, tokens, cfg, mesh=mesh)
    return loss_from_hidden(params, x, tokens, cfg, loss_mask=loss_mask,
                            logits_chunk=logits_chunk)


def loss_from_hidden(params: Params, x: jnp.ndarray, tokens: jnp.ndarray,
                     cfg: LlamaConfig, *,
                     loss_mask: Optional[jnp.ndarray] = None,
                     logits_chunk: int = 512) -> Tuple[jnp.ndarray, Dict]:
    """Chunked next-token CE given final hidden states [B,S,D] (shared by
    the dense and pipeline forwards)."""
    b, s = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    # [B, S], not [1, S]: the mean below divides by the number of
    # valid TOKENS (a broadcast row would count one sequence's worth
    # and report B times the loss).
    valid = jnp.broadcast_to(
        (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :], (b, s))
    if loss_mask is not None:
        valid = valid * jnp.roll(loss_mask, -1, axis=1).astype(jnp.float32)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

    def chunk_nll(args):
        xc, tc = args  # [B,C,D], [B,C]
        logits = _wdot("bcd,dv->bcv", xc, head).astype(jnp.float32)
        logits = constrain(logits, ("batch", "seq", "vocab"))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return logz - gold  # [B,C]

    if s > logits_chunk:
        # Pad the ragged tail (padded positions are already invalid in
        # `valid`, so they contribute nothing) — NEVER fall back to the
        # full [B,S,V] materialization the chunking exists to avoid.
        pad = (-s) % logits_chunk
        xs_p = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        ts_p = jnp.pad(targets, ((0, 0), (0, pad))) if pad else targets
        n = (s + pad) // logits_chunk
        xs = xs_p.reshape(b, n, logits_chunk, -1).swapaxes(0, 1)
        ts = ts_p.reshape(b, n, logits_chunk).swapaxes(0, 1)
        nll = lax.map(jax.checkpoint(chunk_nll), (xs, ts))
        nll = nll.swapaxes(0, 1).reshape(b, s + pad)[:, :s]
    else:
        nll = chunk_nll((x, targets))
    nll = nll * valid
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)
    return loss, {"loss": loss, "ppl_log": loss}


# KV-cache decode (serving path) ------------------------------------------

def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """KV cache in the ENGINE-NATIVE [layers, B, KH, S, D] layout: the
    Pallas decode kernel streams [B, KH, S, D] directly (storing [B, S,
    KH, D] cost two full-cache transposes per decoded token — measured
    on v5e). Activations transpose per step instead: new k/v are [B, T,
    KH, D] with tiny T, and the read-side swap feeding the generic
    attention path folds into the dot's dimension numbers."""
    dt = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _hidden_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: LlamaConfig):
    """tokens [B, T] written at [cache_index, cache_index+T) -> (x
    [B, T, d] after the final norm, the updated cache): the one body of
    ``forward_with_cache`` and ``forward_last_with_cache``, which differ
    in the rows they give the head."""
    b, t = tokens.shape
    start = cache_index[:, None] if jnp.ndim(cache_index) else cache_index
    positions = start + jnp.broadcast_to(jnp.arange(t), (b, t))
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    def body(x, layer_and_kv):
        layer, ck, cv = layer_and_kv
        y, new_kv = _block(x, layer, positions, cfg, None,
                           cache_kv=(ck, cv), cache_index=cache_index)
        return y, new_kv

    x, (new_k, new_v) = lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
    return _norm(x, params["ln_out"], cfg), {"k": new_k, "v": new_v}


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: LlamaConfig) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prefill-chunk or decode-step forward against a KV cache.

    tokens [B, T] written at [cache_index, cache_index+T); returns logits for
    those T positions plus the updated cache. ``cache_index`` may be traced.
    """
    x, cache = _hidden_with_cache(params, tokens, cache, cache_index, cfg)
    return _head_matmul(x, params, cfg), cache


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: LlamaConfig
                            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The tick's prefill: the same layers, the head for row ``last``
    alone (the prompt's last real token; ``last`` may be traced) ->
    (logits [B, V], cache). Rows past ``last`` are bucket padding:
    causal, so they move nothing the head reads, and their cache rows
    lie past the slot's length."""
    x, cache = _hidden_with_cache(params, tokens, cache, cache_index, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1)             # [B, 1, d]
    return _head_matmul(row, params, cfg)[:, 0], cache


def forward_last_rows_with_cache(params: Params, tokens: jnp.ndarray,
                                 cache: Dict[str, jnp.ndarray], cache_index,
                                 last, cfg: LlamaConfig
                                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """`forward_last_with_cache` for rows that are prompts APART: tokens
    [n, T], ``cache`` the n slots' rows, ``cache_index`` [n] and
    ``last`` [n] a row's own (a prefix hit starts past 0; a shorter
    prompt in a longer partner's bucket ends earlier) -> (logits [n, V],
    cache). Row b's K and V are written at ``cache_index[b]`` and its
    queries see keys under ``cache_index[b] + T``, its own alone: the
    weights are read once for all of them. What a module offers this
    function for, the engine's tick may prefill two waiting prompts in
    one program (`serve/engine/core.py` ``_partner``)."""
    x, cache = _hidden_with_cache(params, tokens, cache, cache_index, cfg)
    row = jnp.take_along_axis(x, last[:, None, None], axis=1)   # [n, 1, d]
    return _head_matmul(row, params, cfg)[:, 0], cache


def _decode_block(x, layer, layer_idx, cache_k, cache_v, lengths, seen,
                  cfg: LlamaConfig):
    """One transformer block of the decode step: x [B,1,D], one token a
    slot, slot b's at position ``lengths[b]``. The whole [L,B,KH,S,D]
    cache comes in and goes out: slot b's new K and V row is written at
    ``[layer_idx, b, :, lengths[b]]`` and nothing else of it is touched,
    so under a loop that carries the cache the update happens in place.
    Attention then reads slot b's first ``seen[b]`` rows (``lengths + 1``
    of a live slot; 0 of one that is not, whose write still lands).
    ``_block`` is the same arithmetic for [B,T] tokens at ONE
    ``cache_index`` with a functional cache (prefill, training); the two
    share the helpers and no cache logic."""
    fused = bool(cfg.fused_ops)
    interp = cfg.fused_ops == "interpret"
    positions = lengths[:, None]
    h = _norm(x, layer["ln_attn"], cfg)
    q = _wdot("bsd,dhk->bshk", h, layer["wq"])
    k = _wdot("bsd,dhk->bshk", h, layer["wk"])
    v = _wdot("bsd,dhk->bshk", h, layer["wv"])
    if fused:
        q, k = fused_qk_rope(q, k, positions, cfg.rope_theta,
                             interpret=interp)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    cache_k = _write_rows(cache_k, layer_idx, lengths, k[:, 0])
    cache_v = _write_rows(cache_v, layer_idx, lengths, v[:, 0])
    # ONE kernel call for all slots, each masked at its own length; the
    # kernel finds the layer's blocks in the whole cache, and
    # ops/decode_attention.py decides kernel or jnp twin from the platform.
    attn = decode_attention(
        q[:, 0], cache_k, cache_v, seen, layer=layer_idx, layout="bksd",
        interpret=cfg.interpret_kernels)
    attn_out = _wdot("bshk,hkd->bsd", attn[:, None],
                     layer["wo"]).astype(x.dtype)
    if fused:
        h, x = fused_rms_norm_residual(attn_out, x, layer["ln_mlp"],
                                       cfg.norm_eps, interpret=interp)
    else:
        x = x + attn_out
        h = rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    gate = _wdot("bsd,df->bsf", h, layer["w_gate"])
    up = _wdot("bsd,df->bsf", h, layer["w_up"])
    ff = fused_swiglu(gate, up, interpret=interp) if fused \
        else jax.nn.silu(gate) * up
    x = x + _wdot("bsf,fd->bsd", ff, layer["w_down"]).astype(x.dtype)
    return x, cache_k, cache_v


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: LlamaConfig,
                           live=None):
    """One decode step for every slot of the engine's cache at once.

    tokens [B, 1] (slot b's last token, at position ``lengths[b]``),
    lengths [B], ``live`` [B] bool (None: all) -> (logits [B, V] for the
    next token, the cache with one new row a layer a slot, counters).
    The layers are a loop that CARRIES the cache
    (``forward_with_cache`` scans it in and stacks it out, which
    builds a new array): jitted with the cache donated, the step
    rewrites ``2 * L * B`` rows of it and copies none. A slot that is
    not live (idle or frozen: the engine parks its write on a row
    nothing reads) attends to no row, so the kernel streams none of it
    and its logits are nobody's. The counters are one layer's:
    ``decode_attn_rows`` the rows attention must read,
    ``decode_attn_rows_streamed`` those of the blocks the kernel
    fetches for them."""

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    seen, counters = decode_step_rows(lengths, live, cache["k"])

    def body(carry, layer_and_idx):
        x, ck, cv = carry
        layer, layer_idx = layer_and_idx
        return _decode_block(x, layer, layer_idx, ck, cv, lengths, seen,
                             cfg), None

    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = _norm(x, params["ln_out"], cfg)
    return (_head_matmul(x, params, cfg)[:, 0], {"k": ck, "v": cv},
            counters)
