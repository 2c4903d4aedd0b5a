"""dots3-note-class decoder (``dots3_note``) as ONE CHIP'S SHARE of an
expert-parallel replica, served through the engine's model seam
(``serve/engine/README.md``).

Pre-norm residual blocks, RMSNorm, a final norm, an untied head. Every
layer's attention is multi-head latent attention (MLA, as
``models/glm_moe_lite.py`` runs it: expanded in prefill, absorbed in
decode) with a per-head sigmoid gate on its output, at one of TWO
geometries that ``layer_types`` orders:

- *full* layers (128 heads over a 512-value latent): a query past
  ``index_topk`` rows CHOOSES the rows it attends to. An indexer (64
  heads of 128, weights and a key cache of its own) scores every
  visible row, ``I[t,s] = sum_i w[t,i] relu(q_i[t] . k[s])`` in
  float32, and all the attention heads read the ``index_topk`` rows of
  largest score (``ops/row_select.py``: exact, no sort, ties to the
  lower row). By query position, so one pass, a prefill in chunks and a
  decode step compute one function.
- *sliding* layers (64 heads over a second, 1,024-value latent): a
  query reads the last ``window`` rows, its own among them, and no
  other row is ever read again.

The low-rank latents are rescaled by constants (``rho_q = sqrt(d /
q_lora_rank)``, ``rho_kv = sqrt(d / kv_lora_rank)``,
``apply_mla_qkv_lora_rescale``). Layer 0 carries a dense SwiGLU, the
rest a routed expert layer with one shared expert: the router
(`common.route`, shared) ranks all ``n_experts``, the gates are
normalised over all the chosen, and this chip multiplies the pairs that
fall on the experts it HOLDS (``held_experts``; ``ops/grouped_experts``
is told which): what the absent experts would add is left out, and that
partial sum goes on to the next layer. The vocabulary may be a slice.

**The cache: three kinds of entry in one slot**, slot axis second:

- ``kv``  ``[full layers, slots, rows, 640]``: the latent row of a full
  layer, ``c_kv`` (512) ++ the shared rotary key (64), padded to whole
  128-lane tiles (`glm_moe_lite`'s reason, PR 29);
- ``ik``  ``[full layers, slots, rows, 128]``: the indexer's key of the
  same token, at the same extent;
- ``win`` ``[sliding layers, slots, R, 1152]``: a RING of the last ``R``
  rows of the sliding layers' wider latent (1,024 + 64, padded), the
  row of position ``p`` at ``p mod R``. ``R`` is ``window`` rounded up
  to whole 128-row tiles (640 for 513): the kernel reads whole blocks,
  and a row a block of the ring keeps past the window is masked, not
  read into the sum. Which position a ring row holds follows from the
  query's position ALONE (``p_j = t - (t - j) mod R``), never from what
  is there: a slot that changes owner starts clean, nothing is zeroed.
  The entry is per-slot and overwritten, so it is named in
  `SLOT_STATE_KEYS`: the engine reuses no prefix for this family, and
  a slot that is not live (idle, frozen inside a chunk, between two
  chunks of its prefill) writes no ring row.

Decode reads the chosen rows WHERE THEY LIE, whole under a mask: the
latent kernel (``ops/mla_decode.py``) takes ``keep`` [slots, rows], the
selection for a full layer and the ring's validity for a sliding one,
and is called under two names (``rtpu_dsa_decode_attention``,
``rtpu_swa_decode_attention``) so that a trace tells them apart; the
mask of a full layer is made by one kernel of its own
(``rtpu_dsa_select``: scores and the exact top rows).
PERF.md (PR 42) has the reasons and what a gather or a row-list kernel
would change. A prefill chunk's full layers attend under their mask in
a third kernel (``rtpu_dsa_prefill_attention``, ``ops/dsa_prefill.py``:
the slot's latent rows expanded a tile at a time, a block of scores in
fast memory; PR 43); the selection that makes the mask is ``jnp``
(`row_select.index_scores`, `select_rows`). Its sliding layers read the
window in a fourth (``rtpu_swa_prefill_attention``,
``ops/swa_prefill.py``; PR 60): the chunk's rows and the ``window - 1``
ring rows before them expanded once, a block of queries over its own
rows and the reach before it with the mask made from positions inside
the kernel, which also counts what each query read (``window_rows``,
``window_first``). At a window or widths that are not whole tiles (the
toy geometries) that module's ``jnp`` twin runs.

What the engine's seam asks: `init_params`, `init_kv_cache`,
`forward_with_cache`, `forward_last_with_cache` (the tick's prefill:
one row of logits), `decode_step_with_cache`; each returns ``(logits,
cache, counters, seen)``: ``counters`` ride the fetch the tick makes
anyway, ``seen`` (each token's chosen experts; the mask over rows each
full layer's attention ran under) is what a check against a reference
reads, returned by the functional programs only.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, Tuple

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.models.common import route
from ray_tpu.ops import apply_rope, mla_decode_attention, rms_norm
from ray_tpu.ops import row_select
from ray_tpu.ops.dsa_prefill import dsa_prefill_attention
from ray_tpu.ops.grouped_experts import (
    gated_sum,
    grouped_swiglu,
    split_expert_stacks,
)
from ray_tpu.ops.swa_prefill import NO_ROW, swa_prefill_attention

Params = Dict[str, Any]
F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"

# Cache entries that hold per-slot contents of fixed size and no rows a
# token: the sliding layers' ring, overwritten as the slot advances.
SLOT_STATE_KEYS = ("win",)
# Fetched counter -> the attribute under which the request's span
# (``engine.prefill``, ``engine.decode_chunk``) carries it.
SPAN_ATTRS = {"dsa_queries_selected": "queries_selected",
              "moe_pairs_held": "expert_pairs_held",
              "moe_expert_hits": "experts_touched",
              "dsa_rows_attended": "rows_attended",
              "window_rows_read": "window_rows_read"}


@dataclasses.dataclass(frozen=True)
class LatentGeometry:
    """One kind of layer's MLA sizes."""
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_values(self) -> int:
        """What a token's cache row means: latent + shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_dim(self) -> int:
        """What it occupies: padded to whole 128-lane tiles."""
        return -(-self.row_values // 128) * 128

    @property
    def scale(self) -> float:
        return self.qk_head_dim ** -0.5


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    d_model: int = 5120
    layer_types: Tuple[str, ...] = (FULL, FULL) + (SLIDING,) * 3
    n_dense_layers: int = 1              # ``first_k_dense_replace``
    full: LatentGeometry = LatentGeometry(128, 1024, 512, 128, 64, 128, 8e7)
    sliding: LatentGeometry = LatentGeometry(64, 1024, 1024, 192, 64, 128,
                                             5e4)
    index_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    window: int = 513                    # rows a sliding query reads, its own
    lora_rescale: bool = True            # ``apply_mla_qkv_lora_rescale``
    d_ff: int = 13824                    # the dense layers' SwiGLU
    moe_d_ff: int = 1536                 # one expert's (and the shared one's)
    n_experts: int = 256                 # the router's width, published
    held_experts: Tuple[int, int] = (0, 256)   # (first, count) held HERE
    n_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    max_seq_len: int = 524288
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Run the decode kernel under the Pallas interpreter off the TPU
    # (tests); otherwise the kernel on the TPU, its jnp reference off it.
    interpret_kernels: bool = False

    def __post_init__(self):
        if any(k not in (FULL, SLIDING) for k in self.layer_types):
            raise ValueError(f"layer_types {self.layer_types}")
        if not 0 < self.n_dense_layers < self.n_layers:
            raise ValueError("need at least one dense and one expert layer")
        first, count = self.held_experts
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} of "
                             f"{self.n_experts}")
        if self.full.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("the indexer rotates its first qk_rope_head_dim "
                             "columns")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step."""
        return sys.modules[__name__]

    @property
    def ring_rows(self) -> int:
        """Rows of a sliding layer a slot keeps: the window in whole
        128-row tiles (the kernel reads whole blocks)."""
        return -(-self.window // 128) * 128

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_full_layers(self) -> int:
        return sum(k == FULL for k in self.layer_types)

    @property
    def n_sliding_layers(self) -> int:
        return self.n_layers - self.n_full_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def geometry(self, kind: str) -> LatentGeometry:
        return self.full if kind == FULL else self.sliding

    @property
    def segments(self):
        """Runs of consecutive layers of one attention kind and one
        feed-forward kind: (kind, dense?, first of its attention stack,
        first of its feed-forward stack, layers)."""
        out, seen = [], {FULL: 0, SLIDING: 0, True: 0, False: 0}
        for i, kind in enumerate(self.layer_types):
            dense = i < self.n_dense_layers
            if out and out[-1][0] == kind and out[-1][1] == dense:
                out[-1][4] += 1
            else:
                out.append([kind, dense, seen[kind], seen[dense], 1])
            seen[kind] += 1
            seen[dense] += 1
        return tuple(tuple(s) for s in out)


# Parameters ---------------------------------------------------------------

def init_params(cfg: Dots3NoteConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled (by the input's mean square: see
    `latent`), norm gains stored as offsets from one. Four stacks, each scanned a run at a time: the attention of
    the full and of the sliding layers, the dense and the expert
    feed-forward halves (with each layer's second norm). Storage
    conventions (the reference undoes them): projections split by head,
    ``kv_b_proj`` kept as its key half ``w_uk`` and its value half
    ``w_uv``, matrices input-major; the router, its bias, the indexer's
    head weights and its LayerNorm in float32."""
    d, dt = cfg.d_model, cfg.dtype
    e, f, fs = (cfg.held_experts[1], cfg.moe_d_ff,
                cfg.moe_d_ff * cfg.n_shared_experts)
    keys = iter(jax.random.split(key, 64))

    def norm(shape, fan_in, dtype=dt):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(dtype)

    def latent(rank):
        """The fan-in of a matrix that reads a RESCALED latent: its
        rank times the latent's mean square ``rho ** 2``, which is the
        hidden size: what the rescale is for (an up-projection is then
        scaled like every matrix that reads the stream, and attention's
        logits are of order one; scaled by the rank alone they would be
        ``rho_q rho_kv`` = 7 times as large and the softmax an
        argmax, which no bf16 program tracks a float32 one through)."""
        return rank * _rho(cfg, rank) ** 2

    def attn(n, g: LatentGeometry):
        h, rq, rkv = g.n_heads, g.q_lora_rank, g.kv_lora_rank
        return {
            "ln_attn": jnp.zeros((n, d), dt),
            "w_dq": norm((n, d, rq), d),
            "ln_q": jnp.zeros((n, rq), dt),
            "w_uq": norm((n, rq, h, g.qk_head_dim), latent(rq)),
            "w_dkv": norm((n, d, rkv + g.qk_rope_head_dim), d),
            "ln_kv": jnp.zeros((n, rkv), dt),
            "w_uk": norm((n, rkv, h, g.qk_nope_head_dim), latent(rkv)),
            "w_uv": norm((n, rkv, h, g.v_head_dim), latent(rkv)),
            "w_g": norm((n, d, h), d),
            "w_o": norm((n, h, g.v_head_dim, d), h * g.v_head_dim),
        }

    nf, ns = cfg.n_full_layers, cfg.n_sliding_layers
    nd, nm = cfg.n_dense_layers, cfg.n_moe_layers
    hi, di = cfg.index_heads, cfg.index_head_dim
    full = dict(attn(nf, cfg.full),
                w_iq=norm((nf, cfg.full.q_lora_rank, hi, di),
                          latent(cfg.full.q_lora_rank)),
                w_ik=norm((nf, d, di), d),
                ik_gain=jnp.zeros((nf, di), F32),
                ik_bias=jnp.zeros((nf, di), F32),
                w_iw=norm((nf, d, hi), d, F32))
    return {
        "embed": norm((cfg.vocab_size, d), d),
        "full": full,
        "sliding": attn(ns, cfg.sliding),
        "dense": {"ln_mlp": jnp.zeros((nd, d), dt),
                  "w_gate": norm((nd, d, cfg.d_ff), d),
                  "w_up": norm((nd, d, cfg.d_ff), d),
                  "w_down": norm((nd, cfg.d_ff, d), cfg.d_ff)},
        "moe": {"ln_mlp": jnp.zeros((nm, d), dt),
                "router": norm((nm, d, cfg.n_experts), d, F32),
                # `glm_moe_lite.init_params` has the reason for 0.02.
                "router_bias": 0.02 * jax.random.normal(
                    next(keys), (nm, cfg.n_experts), F32),
                "w_gate": norm((nm, e, d, f), d),
                "w_up": norm((nm, e, d, f), d),
                "w_down": norm((nm, e, f, d), f),
                "ws_gate": norm((nm, d, fs), d),
                "ws_up": norm((nm, d, fs), d),
                "ws_down": norm((nm, fs, d), fs)},
        "ln_out": jnp.zeros((d,), dt),
        "lm_head": norm((d, cfg.vocab_size), d),
    }


def _layer_of(stack, idx):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), stack)


# Feed-forward -------------------------------------------------------------

def _swiglu(x, w_gate, w_up, w_down):
    gate = jnp.einsum("td,df->tf", x, w_gate)
    up = jnp.einsum("td,df->tf", x, w_up)
    return jnp.einsum("tf,fd->td", jax.nn.silu(gate) * up, w_down)


def moe_ffn(x, layer, stacks, layer_idx, cfg: Dots3NoteConfig, valid=None):
    """x [T, d] -> (y [T, d], experts [T, k], load [held], pairs held,
    gates [T, k] float32, the router's input [T, d] float32): the router ranks ALL ``n_experts`` in
    float32 (`common.route`, its product at the chip's highest
    precision: the default would round the float32 router to bf16), the
    gates are normalised over all the chosen, and the pairs on this
    chip's experts are multiplied (dropless); a pair on an absent
    expert adds nothing here."""
    # The router reads the stream AT THE STREAM'S PRECISION, widened:
    # `reduce_precision` is an operation of its own, where a convert to
    # bf16 and back is what the chip's compiler, allowed excess
    # precision, takes out for one reader and not for another.
    router_in = lax.reduce_precision(
        x.astype(F32), exponent_bits=jnp.finfo(x.dtype).nexp,
        mantissa_bits=jnp.finfo(x.dtype).nmant)
    experts, gates = route(router_in, layer["router"], layer["router_bias"],
                           cfg, precision=lax.Precision.HIGHEST)
    y, load = grouped_swiglu(x, experts, stacks, layer_idx, cfg.n_experts,
                             valid, held=cfg.held_experts)
    y = gated_sum(y, gates).astype(x.dtype)
    shared = _swiglu(x, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    return y + shared, experts, load, jnp.sum(load), gates, router_in


def _ffn(x, ffn, moe, cfg, valid=None):
    """The block's second half on x [B, T, d] (the residual stream):
    -> (x, None | {experts [B,T,k], gates [B,T,k], router_in [B,T,d]
    (the normed stream the router read), load [held], held})."""
    b, t, d = x.shape
    flat = rms_norm(x, ffn["ln_mlp"], cfg.norm_eps).reshape(b * t, d)
    if moe is None:
        y = _swiglu(flat, ffn["w_gate"], ffn["w_up"], ffn["w_down"])
        return x + y.reshape(b, t, d).astype(x.dtype), None
    y, experts, load, held, gates, router_in = moe_ffn(
        flat, ffn, *moe, cfg, None if valid is None else valid.reshape(-1))
    return (x + y.reshape(b, t, d).astype(x.dtype),
            {"experts": experts.reshape(b, t, -1),
             "gates": gates.reshape(b, t, -1),
             "router_in": router_in.reshape(b, t, d), "load": load,
             "held": held})


# Attention, both geometries -----------------------------------------------

def _rho(cfg, rank):
    return (cfg.d_model / rank) ** 0.5 if cfg.lora_rescale else 1.0


def _queries_and_row(h, layer, positions, g: LatentGeometry, cfg):
    """h [B,T,d] (normed) -> c_q [B,T,rq] (rescaled), q_nope
    [B,T,H,nope], q_rope [B,T,H,rope] (rotated), row [B,T,W]: the
    token's cache row ``c_kv ++ k_rope ++ 0``, c_kv rescaled."""
    c_q = rms_norm(jnp.einsum("btd,dr->btr", h, layer["w_dq"]),
                   layer["ln_q"], cfg.norm_eps)
    c_q = (c_q.astype(F32) * _rho(cfg, g.q_lora_rank)).astype(h.dtype)
    q = jnp.einsum("btr,rhk->bthk", c_q, layer["w_uq"])
    q_nope = q[..., :g.qk_nope_head_dim]
    q_rope = apply_rope(q[..., g.qk_nope_head_dim:], positions, g.rope_theta)
    ckr = jnp.einsum("btd,dr->btr", h, layer["w_dkv"])
    c_kv = rms_norm(ckr[..., :g.kv_lora_rank], layer["ln_kv"], cfg.norm_eps)
    c_kv = (c_kv.astype(F32) * _rho(cfg, g.kv_lora_rank)).astype(h.dtype)
    k_rope = apply_rope(ckr[..., None, g.kv_lora_rank:], positions,
                        g.rope_theta)[..., 0, :]
    pad = jnp.zeros(ckr.shape[:-1] + (g.row_dim - g.row_values,), ckr.dtype)
    return c_q, q_nope, q_rope, jnp.concatenate([c_kv, k_rope, pad], -1)


def _expand(rows, layer, g: LatentGeometry):
    """Cache rows [B,S,W] -> per-head keys [B,H,S,qk] and values
    [B,H,S,v], head-major: a head's rows are whole (rows, lanes) tiles,
    which is how `swa_prefill_attention` reads them."""
    c_kv = rows[..., :g.kv_lora_rank]
    k_rope = rows[..., g.kv_lora_rank:g.row_values]
    k_nope = jnp.einsum("bsr,rhk->bhsk", c_kv, layer["w_uk"])
    v = jnp.einsum("bsr,rhv->bhsv", c_kv, layer["w_uv"])
    k_rope = jnp.broadcast_to(k_rope[:, None],
                              k_nope.shape[:3] + (g.qk_rope_head_dim,))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _gate_and_out(x, h, attn, layer):
    """attn [.., H, v] -> x + (sigmoid(h W_g) per head * attn) W_o."""
    gate = jax.nn.sigmoid(jnp.einsum("...d,dh->...h", h, layer["w_g"],
                                     preferred_element_type=F32))
    attn = (attn.astype(F32) * gate[..., None]).astype(x.dtype)
    return x + jnp.einsum("...hv,hvd->...d", attn, layer["w_o"]).astype(
        x.dtype)


def _index_parts(h, c_q, layer, positions, cfg: Dots3NoteConfig):
    """-> the indexer's queries [B,T,Hi,Di], its key of each token
    [B,T,Di] (LayerNorm, rotated, the cache's type) and the heads'
    weights [B,T,Hi] float32 with the constant folded in."""
    r, g = cfg.full.qk_rope_head_dim, cfg.full

    def rotate(x):      # [B,T,n,Di]: the first r columns turn
        return jnp.concatenate(
            [apply_rope(x[..., :r], positions, g.rope_theta), x[..., r:]], -1)

    q = rotate(jnp.einsum("btr,rhk->bthk", c_q, layer["w_iq"]))
    k = jnp.einsum("btd,dk->btk", h, layer["w_ik"]).astype(F32)
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(jnp.square(k), -1, keepdims=True) + 1e-5)
    k = (k * (1.0 + layer["ik_gain"]) + layer["ik_bias"]).astype(h.dtype)
    k = rotate(k[..., None, :])[..., 0, :]
    w = jnp.einsum("btd,dh->bth", h, layer["w_iw"],
                   preferred_element_type=F32)
    return q, k, w * (cfg.index_heads * cfg.index_head_dim) ** -0.5


def select_rows(scores, positions, cfg: Dots3NoteConfig, rows_seen):
    """scores [T,S] float32 of a chunk's queries at ``positions`` [T],
    no row at or past ``rows_seen`` visible -> [T,S] bool: every row up
    to the query's own while no more than ``index_topk`` are visible,
    else the ``index_topk`` best."""
    s = scores.shape[-1]
    visible = jnp.arange(s)[None, :] <= positions[:, None]
    best = row_select.top_rows_within(scores, visible, cfg.index_topk,
                                      rows_seen)
    return jnp.where((positions + 1 <= cfg.index_topk)[:, None], visible,
                     best)


def _full_prefill_block(x, layer, kv_l, ik_l, cache_index, positions,
                        cfg: Dots3NoteConfig):
    """x [B,T,d], kv_l [B,S,W], ik_l [B,S,Di]: this layer's rows of the
    slot(s) -> (x after attention, kv_l, ik_l, keep [B,T,S])."""
    g = cfg.full
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    c_q, q_nope, q_rope, rows = _queries_and_row(h, layer, positions, g, cfg)
    q_i, k_i, w_i = _index_parts(h, c_q, layer, positions, cfg)
    # cache_index + T is bounded by the engine's contract: the scheduler
    # admits only what fits a slot's rows.
    kv_l = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        kv_l, rows.astype(kv_l.dtype), (0, cache_index, 0))
    ik_l = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        ik_l, k_i.astype(ik_l.dtype), (0, cache_index, 0))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    rows_seen = cache_index + x.shape[1]

    def choose(q_i, w_i, pos, ik_s):
        scores = row_select.index_scores(q_i, w_i, ik_s, rows_seen)
        return select_rows(scores, pos, cfg, rows_seen)

    keep = jax.vmap(choose)(q_i, w_i, positions, ik_l)
    attn = dsa_prefill_attention(
        q, kv_l, keep, layer["w_uk"], layer["w_uv"], rows_seen,
        scale=g.scale, interpret=cfg.interpret_kernels)
    return _gate_and_out(x, h, attn, layer), kv_l, ik_l, keep


def _is_a_row(positions):
    """Whether a ring row's position (`_ring_positions`) is one the
    slot's owner has written: a slot that changes owner starts clean by
    THIS, not by zeroing."""
    return positions >= 0


def _window_read(mask, positions):
    """The mask a sliding layer's attention ran under [.., rows] and the
    position each of those rows holds -> {"rows": how many rows the
    query attended to, "first": the lowest position among them}: what a
    check holds to the published window."""
    return {"rows": jnp.sum(mask, -1, dtype=jnp.int32),
            "first": jnp.min(jnp.where(mask, positions, NO_ROW), -1)}


def _ring_positions(t, ring: int):
    """t [..] (a query's position) -> [.., ring]: the position whose
    row each ring row holds for it (negative: none yet)."""
    j = jnp.arange(ring, dtype=jnp.int32)
    return t[..., None] - jnp.mod(t[..., None] - j, ring)


def _sliding_prefill_block(x, layer, win_l, cache_index, positions, last,
                           cfg: Dots3NoteConfig):
    """x [B,T,d], win_l [B,R,W]: this layer's ring of the slot(s), row
    ``last`` the chunk's last real one -> (x after attention, win_l,
    what each query read: `_window_read`'s two). A query reads the rows
    of its chunk and, from the ring, the ``window - 1`` rows before the
    chunk (`swa_prefill_attention`: one kernel at the published sizes,
    which also counts what each query read from the mask it ran under;
    a ring row that `_is_a_row` disowns is handed over as no row); the
    ring then takes the chunk's last real rows."""
    g, ring, reach = cfg.sliding, cfg.ring_rows, cfg.window - 1
    b, t = x.shape[:2]
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    _, q_nope, q_rope, rows = _queries_and_row(h, layer, positions, g, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    before = cache_index - reach + jnp.arange(reach, dtype=jnp.int32)
    old = jnp.take(win_l, jnp.mod(before, ring), axis=1)         # [B,reach,W]
    # Expanded ONCE for the chunk: a block of queries reads two blocks
    # of rows, so a kernel that expanded would expand every row twice.
    k, v = _expand(jnp.concatenate([old.astype(rows.dtype), rows], 1),
                   layer, g)                                 # [B,H,reach+T,..]
    k_pos = jnp.concatenate([jnp.broadcast_to(before, (b, reach)),
                             positions], 1)
    attn, n_rows, first = swa_prefill_attention(
        q, k, v, positions, jnp.where(_is_a_row(k_pos), k_pos, NO_ROW),
        reach=reach, scale=g.scale, interpret=cfg.interpret_kernels)
    # The ring after the chunk: row j holds the newest real position
    # congruent to j, the chunk's where it has one.
    end = cache_index + (t - 1 if last is None else last)
    held = _ring_positions(jnp.asarray(end, jnp.int32), ring)
    new = jnp.take(rows, jnp.clip(held - cache_index, 0, t - 1), axis=1)
    win_l = jnp.where((held >= cache_index)[:, None], new.astype(win_l.dtype),
                      win_l)
    return (_gate_and_out(x, h, attn, layer), win_l,
            {"rows": n_rows, "first": first})


def _write_rows(cache, layer_idx, at, rows):
    """rows [B,W] -> cache[layer_idx, b, at[b]] of an [L,B,S,W] entry:
    a scatter into the free view [L*B, S, W], the form the chip's
    compiler updates in place (common._write_rows, PR 26). ``at`` is
    bounded by the engine's contract (or by the ring's size)."""
    n_layers, b, s, w = cache.shape
    slots = layer_idx * b + jnp.arange(b, dtype=jnp.int32)
    flat = cache.reshape(n_layers * b, s, w)
    flat = flat.at[slots, at.astype(jnp.int32)].set(
        rows.astype(cache.dtype), unique_indices=True,
        indices_are_sorted=True)
    return flat.reshape(cache.shape)


def _absorbed_queries(q_nope, q_rope, layer, g: LatentGeometry):
    """q_nope [B,H,nope], q_rope [B,H,rope] -> [B,H,W]: the key
    up-projection absorbed, the row's padding matched with zeros."""
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, layer["w_uk"])
    pad = jnp.zeros(q_lat.shape[:2] + (g.row_dim - g.row_values,),
                    q_lat.dtype)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def _full_decode_block(x, layer, idx, kv, ik, lengths, live,
                       cfg: Dots3NoteConfig):
    """x [B,1,d], the whole ``kv`` and ``ik`` entries carried -> (x
    after attention, kv, ik, {rows: keep [B,S], index_q [B,Hi,Di],
    index_w [B,Hi]: the indexer's operands for this token}). Scores over the slot's index
    keys and the exact top rows as a mask in one kernel
    (`row_select.select_decode_rows`; a slot that is not live chooses
    none), absorbed MLA over the latent rows where they lie, whole
    under that mask."""
    g = cfg.full
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    c_q, q_nope, q_rope, rows = _queries_and_row(h, layer, lengths[:, None],
                                                 g, cfg)
    q_i, k_i, w_i = _index_parts(h, c_q, layer, lengths[:, None], cfg)
    kv = _write_rows(kv, idx, lengths, rows[:, 0])
    ik = _write_rows(ik, idx, lengths, k_i[:, 0])
    keep = row_select.select_decode_rows(
        q_i[:, 0], w_i[:, 0], ik,
        jnp.where(live, lengths, -1).astype(jnp.int32), layer=idx,
        k=cfg.index_topk, interpret=cfg.interpret_kernels)
    o_lat = mla_decode_attention(
        _absorbed_queries(q_nope[:, 0], q_rope[:, 0], layer, g), kv,
        jnp.where(live, lengths + 1, 0).astype(jnp.int32), layer=idx,
        v_dim=g.kv_lora_rank, scale=g.scale, keep=keep,
        interpret=cfg.interpret_kernels,
        name="rtpu_dsa_decode_attention")
    o = jnp.einsum("bhr,rhv->bhv", o_lat, layer["w_uv"])
    return (_gate_and_out(x, h, o[:, None], layer), kv, ik,
            {"rows": keep > 0, "index_q": q_i[:, 0], "index_w": w_i[:, 0]})


def _sliding_decode_block(x, layer, idx, win, lengths, live,
                          cfg: Dots3NoteConfig):
    """x [B,1,d], the whole ``win`` entry carried -> (x after attention,
    win, what each query read: `_window_read`). A live slot's row goes to ``t mod R``; the kernel reads the
    ring whole under the mask of the rows that hold one of the last
    ``window`` positions."""
    g, ring = cfg.sliding, cfg.ring_rows
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    _, q_nope, q_rope, rows = _queries_and_row(h, layer, lengths[:, None], g,
                                               cfg)
    t = lengths.astype(jnp.int32)
    at = jnp.mod(t, ring)
    b = x.shape[0]
    old = lax.dynamic_index_in_dim(win, idx, 0, keepdims=False)[
        jnp.arange(b), at]
    win = _write_rows(win, idx, at,
                      jnp.where(live[:, None], rows[:, 0].astype(win.dtype),
                                old))
    held = _ring_positions(t, ring)                              # [B,R]
    keep = _is_a_row(held) & (held > t[:, None] - cfg.window)
    o_lat = mla_decode_attention(
        _absorbed_queries(q_nope[:, 0], q_rope[:, 0], layer, g), win,
        jnp.where(live, ring, 0).astype(jnp.int32), layer=idx,
        v_dim=g.kv_lora_rank, scale=g.scale, keep=keep, block_s=ring,
        interpret=cfg.interpret_kernels,
        name="rtpu_swa_decode_attention")
    o = jnp.einsum("bhr,rhv->bhv", o_lat, layer["w_uv"])
    return (_gate_and_out(x, h, o[:, None], layer), win,
            _window_read(keep, held))


# The engine's seam --------------------------------------------------------

def init_kv_cache(cfg: Dots3NoteConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """Latent rows, index keys and the sliding layers' ring in one cache
    (this module's header). ``max_len`` past 512 is rounded up to whole
    tiles of 512 rows."""
    dt = dtype or cfg.dtype
    if max_len > 512:
        max_len = -(-max_len // 512) * 512
    return {
        "kv": jnp.zeros((cfg.n_full_layers, batch, max_len,
                         cfg.full.row_dim), dt),
        "ik": jnp.zeros((cfg.n_full_layers, batch, max_len,
                         cfg.index_head_dim), dt),
        "win": jnp.zeros((cfg.n_sliding_layers, batch, cfg.ring_rows,
                          cfg.sliding.row_dim), dt)}


def _ffn_args(params, dense: bool, idx, stacks, scanned):
    if dense:
        return _layer_of(params["dense"], idx), None
    return _layer_of(scanned, idx), (stacks, idx)


def _scan_layers(params, carry, block, cfg: Dots3NoteConfig):
    """The layers in published order, a scan a run of one kind
    (`segments`): ``block(kind, dense)`` makes the run's body, whose
    outputs are (what the attention reports, what the expert layer
    reports | None). -> (carry, full layers' reports, sliding layers',
    expert layers'), each stacked over its layers."""
    full, sliding, moe = [], [], []
    for kind, dense, a0, f0, n in cfg.segments:
        ids = jnp.arange(n, dtype=jnp.int32)
        carry, (attn, ffn) = lax.scan(block(kind, dense), carry,
                                      (a0 + ids, f0 + ids))
        (full if kind == FULL else sliding).append(attn)
        if not dense:
            moe.append(ffn)
    join = lambda parts: jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
    return carry, join(full), join(sliding), join(moe)


def _prefill(params, tokens, cache, cache_index, last, cfg: Dots3NoteConfig):
    """-> (x [B,T,d] after the final norm, cache, counters, seen): a
    scan over the layers of each run of one kind, the cache's arrays
    carried and each layer's slice rewritten. ``cache`` holds ONE
    slot's entries (or, off the engine, a batch's)."""
    b, t = tokens.shape
    cache_index = jnp.asarray(cache_index, jnp.int32)
    positions = cache_index + jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32), (b, t))
    valid = (None if last is None
             else jnp.broadcast_to(jnp.arange(t) <= last, (b, t)))
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    stacks, scanned = split_expert_stacks(params["moe"])
    at = lambda a, i: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    put = lambda a, row, i: lax.dynamic_update_index_in_dim(a, row, i, 0)

    def block(kind, dense):
        def body(carry, xs):
            x, kv, ik, win = carry
            a_idx, f_idx = xs
            if kind == FULL:
                x, kv_l, ik_l, keep = _full_prefill_block(
                    x, _layer_of(params["full"], a_idx), at(kv, a_idx),
                    at(ik, a_idx), cache_index, positions, cfg)
                kv, ik = put(kv, kv_l, a_idx), put(ik, ik_l, a_idx)
                attn = {"rows": keep}
            else:
                x, win_l, attn = _sliding_prefill_block(
                    x, _layer_of(params["sliding"], a_idx), at(win, a_idx),
                    cache_index, positions, last, cfg)
                win = put(win, win_l, a_idx)
            ffn, moe = _ffn_args(params, dense, f_idx, stacks, scanned)
            x, about = _ffn(x, ffn, moe, cfg, valid)
            if about is not None:       # a chunk's router inputs stay behind
                about = {k: about[k] for k in ("experts", "held", "load")}
            return (x, kv, ik, win), (attn, about)
        return body

    (x, kv, ik, win), full, sliding, moe = _scan_layers(
        params, (x, cache["kv"], cache["ik"], cache["win"]), block, cfg)
    n_real = b * (t if last is None else jnp.asarray(last, jnp.int32) + 1)
    real = positions if valid is None else jnp.where(valid, positions, -1)
    counters = {
        "prefill_chunks": jnp.asarray(b, jnp.int32),
        "dsa_queries_selected": cfg.n_full_layers * jnp.sum(
            real + 1 > cfg.index_topk, dtype=jnp.int32),
        "dsa_prefill_rows_attended": cfg.n_full_layers * jnp.sum(
            jnp.minimum(real + 1, cfg.index_topk), dtype=jnp.int32),
        "moe_pairs_routed": (cfg.n_moe_layers * cfg.n_experts_per_tok
                             * jnp.asarray(n_real, jnp.int32)),
        "moe_pairs_held": jnp.sum(moe["held"]).astype(jnp.int32),
        "moe_prefill_expert_hits": jnp.sum(moe["load"] > 0, dtype=jnp.int32)}
    seen = {"experts": moe["experts"],                     # [Lm,B,T,k]
            "rows": full["rows"],                          # [Lf,B,T,S]
            "window_rows": sliding["rows"],                # [Ls,B,T]
            "window_first": sliding["first"]}
    return (rms_norm(x, params["ln_out"], cfg.norm_eps),
            {"kv": kv, "ik": ik, "win": win}, counters, seen)


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: Dots3NoteConfig):
    """tokens [B,T], all real, written at rows [cache_index,
    cache_index+T) -> (logits [B,T,V], cache, counters, seen): the
    functional prefill, whole-bucket logits. ``seen``: ``experts``
    [expert layers, B, T, k]; ``rows`` [full layers, B, T, S] bool, the
    mask each query's attention ran under; ``window_rows`` and
    ``window_first`` [sliding layers, B, T]: how many rows each query
    of a sliding layer attended to and the lowest position among them,
    counted from the mask its attention ran under."""
    x, cache, counters, seen = _prefill(params, tokens, cache, cache_index,
                                        None, cfg)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
    return logits, cache, counters, seen


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: Dots3NoteConfig):
    """The tick's prefill: ``tokens[:, :last+1]`` are real, the rest
    bucket padding (given to no expert, and kept out of the ring) ->
    (logits [B,V] of row ``last``, cache, counters, seen)."""
    x, cache, counters, seen = _prefill(params, tokens, cache, cache_index,
                                        last, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    logits = jnp.einsum("bd,dv->bv", row, params["lm_head"])
    return logits, cache, counters, seen


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: Dots3NoteConfig,
                           live=None):
    """One decode step for every slot: tokens [B,1], lengths [B],
    ``live`` [B] bool (None: all) -> (logits [B,V], cache, counters,
    seen). The scans CARRY the three cache entries: donated, the step
    rewrites a latent row and an index key a full layer a slot and a
    ring row a sliding layer a LIVE slot, and copies none. A slot that
    is not live keeps its ring; its latent row and index key land where
    the engine parked it and its attention reads no row.

    Counters, over live slots: ``dsa_rows_visible`` (rows the full
    layers' queries could read), ``dsa_rows_selected`` (rows in their
    chosen sets), ``dsa_rows_attended`` (rows their attention READ:
    whole under the mask, so the visible ones), ``dsa_queries_selected``
    (queries past ``index_topk`` rows), ``window_rows_read``; and over
    every slot's token (a frozen slot's is routed like any other:
    static shapes) ``moe_pairs_routed``, ``moe_pairs_held``,
    ``moe_expert_hits`` (held experts that a pair fell on, summed over
    the expert layers) and ``moe_layer_steps``.

    ``seen``, beside `forward_with_cache`'s: ``gates`` [expert layers,
    B, 1, k] and ``router_in`` [expert layers, B, 1, d] (each router's
    weights of the chosen and the normed stream it read), ``index_q``
    [full layers, B, Hi, Di] and ``index_w`` [full layers, B, Hi] (the
    indexer's rotated queries and head weights of this token): what a
    check recomputes the router and the selection from in float32."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    b = x.shape[0]
    live = (jnp.ones(lengths.shape, bool) if live is None
            else live.astype(bool))
    stacks, scanned = split_expert_stacks(params["moe"])

    def block(kind, dense):
        def body(carry, xs):
            x, kv, ik, win = carry
            a_idx, f_idx = xs
            if kind == FULL:
                x, kv, ik, attn = _full_decode_block(
                    x, _layer_of(params["full"], a_idx), a_idx, kv, ik,
                    lengths, live, cfg)
            else:
                x, win, attn = _sliding_decode_block(
                    x, _layer_of(params["sliding"], a_idx), a_idx, win,
                    lengths, live, cfg)
            ffn, moe = _ffn_args(params, dense, f_idx, stacks, scanned)
            x, about = _ffn(x, ffn, moe, cfg)
            return (x, kv, ik, win), (attn, about)
        return body

    (x, kv, ik, win), full, sliding, moe = _scan_layers(
        params, (x, cache["kv"], cache["ik"], cache["win"]), block, cfg)
    x = rms_norm(x, params["ln_out"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0], params["lm_head"])
    rows = jnp.where(live, lengths.astype(jnp.int32) + 1, 0)
    nf, ns = cfg.n_full_layers, cfg.n_sliding_layers
    counters = {
        "dsa_rows_visible": nf * jnp.sum(rows),
        "dsa_rows_selected": nf * jnp.sum(jnp.minimum(rows, cfg.index_topk)),
        "dsa_rows_attended": nf * jnp.sum(rows),
        "dsa_queries_selected": nf * jnp.sum(rows > cfg.index_topk,
                                             dtype=jnp.int32),
        "window_rows_read": ns * jnp.sum(jnp.minimum(rows, cfg.window)),
        "moe_layer_steps": jnp.int32(cfg.n_moe_layers),
        "moe_expert_hits": jnp.sum(moe["load"] > 0, dtype=jnp.int32),
        "moe_pairs_routed": jnp.int32(cfg.n_moe_layers * b
                                      * cfg.n_experts_per_tok),
        "moe_pairs_held": jnp.sum(moe["held"]).astype(jnp.int32)}
    seen = {"experts": moe["experts"],                     # [Lm,B,1,k]
            "gates": moe["gates"],                         # [Lm,B,1,k]
            "router_in": moe["router_in"],                 # [Lm,B,1,d]
            "rows": full["rows"][:, :, None],              # [Lf,B,1,S]
            "index_q": full["index_q"],                    # [Lf,B,Hi,Di]
            "index_w": full["index_w"],                    # [Lf,B,Hi]
            "window_rows": sliding["rows"][:, :, None],    # [Ls,B,1]
            "window_first": sliding["first"][:, :, None]}
    return logits, {"kv": kv, "ik": ik, "win": win}, counters, seen


def forward(params: Params, tokens: jnp.ndarray,
            cfg: Dots3NoteConfig) -> jnp.ndarray:
    """Full causal forward, no cache kept: tokens [B,T] -> logits."""
    b, t = tokens.shape
    return forward_with_cache(params, tokens, init_kv_cache(cfg, b, t), 0,
                              cfg)[0]
