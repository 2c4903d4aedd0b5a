"""MiniCPM-SALA-class decoder (``minicpm_sala``): layers of block-sparse
attention that chooses its own rows (``minicpm4``) among layers of
lightning linear attention (``lightning-attn``), in the IRREGULAR order
``mixer_types`` gives, served through the engine's model seam
(``serve/engine/README.md``).

Pre-norm residual blocks with muP scalars, a final RMSNorm, an untied
head. ``x`` a block's input, ``a = scale_depth / sqrt(mup_denominator)``
(the PUBLISHED depth, 32, whatever number of layers runs):

    h   = x + a Mixer(RMSNorm(x))
    out = h + a W_down (silu(W_gate n) * (W_up n)),   n = RMSNorm(h)
    x_0 = scale_emb * embedding row
    logits = W_head (RMSNorm(x_L) / (hidden_size / dim_model_base))

*Lightning layer* (H heads of 128; ``ops/lightning.py``): ``q, k, v =
x W_q, x W_k, x W_v``; per head ``q <- RMSNorm_128(q)``,
``k <- RMSNorm_128(k)``, rotate-half RoPE on q and k (theta
``rope_theta``). The state ``S`` in R^{128 x 128} a head, float32, zero
before the sequence:

    S_t = lambda_h S_{t-1} + k_t v_t^T          lambda_h = exp(-2^(-8 (h+1) / H))
    o_t = S_t^T q_t / sqrt(128)
    y_t = RMSNorm_128(o_t) * sigmoid(x W_g)     (per head; the gate 4096 wide)
    Mixer = concat_h(y) W_o

``lambda_h`` is lightning attention's slope table: a constant a head,
no parameter, no per-layer factor. No activation on q, k beyond the
norm and the rotation.

*Sparse layer* (32 query heads, 2 KV heads of 128, G = 16;
``ops/sparse_attention.py`` has the selection's equations): ``q, k, v``
projected, per-head RMSNorm on q and k, NO rotary. The query at
position t (t + 1 rows visible) attends causally over every row if
``t + 1 <= dense_len``, else over the rows of ``topk`` blocks of 64
chosen a KV head at a time from the compressed keys ``kc_j =
mean(k[16 j : 16 j + 32])`` (the forced blocks counted inside the 64).
Then ``o <- o * sigmoid(x W_g)``, ``W_o``. One selection serves the 16
heads of a group.

**Assumed** (the published config has no key for them; the benchmark's
configuration file lists each under ``assumed``): the selection's sizes
(MiniCPM4's ``sparse_config``); the dense / sparse rule taken BY QUERY
POSITION, which is what decoding through a cache does; the published
prefill takes it by the PROMPT's length (a prompt past ``dense_len`` is
selected for from its first row), so a prompt's rows under
``dense_len`` differ from it here, and in exchange a prefill in chunks,
a decode step and one pass over the sequence compute one function;
pre-norm blocks with the depth scalar on both branches; sigmoid gates
of full width; a float32 state and a float32 residual stream whose
products take their operands in the weights' type (as ``olmo_hybrid``).

**The cache** has entries of THREE kinds, slot axis second:

    k, v   [sparse layers, B, 2, rows, 128]        a row a token
    kc     [sparse layers, B, 2, rows / 16, 128]   a row a WINDOW: at a
                                                   sixteenth of the extent
    state  [lightning layers, B, 32, 128, 128]     float32, no rows

``kc[j]`` is written when its window completes. A prefill chunk at
``cache_index`` (a multiple of 16: prompt buckets are) rewrites the
windows that begin at ``cache_index - 16`` and after, the one that
straddles the chunks' boundary among them; a decode step that writes
row r writes window ``(r + 1 - 32) / 16`` where that is whole. A window
that holds a bucket's padding or another request's rows stands in the
array but no query counts it: completeness is judged from the query's
position (``16 j + 32 <= t + 1``), never from what the array holds.
Prefill runs `lightning.chunk_scan` from the slot's state, or from ZERO
where ``cache_index`` is 0 (an admission resets its slot inside the
tick's prefill program); a bucket's padding steps no state. Decode
steps every LIVE slot's state where it lies (`lightning_decode`) and
reads each live slot's selected blocks where they lie
(`sparse_decode_attention`).

`SLOT_STATE_KEYS`, `SPAN_ATTRS`: the engine's
contract for a family with per-slot state (``models/olmo_hybrid.py``).
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, Tuple

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.models.common import (_layer_of, _mm, _real, _starts_fresh,
                                   _write_rows)
# The other state family's: a product with bf16 operands and a float32
# sum, a layer of a stack sliced where its products read it, whether a
# prefill starts a request (its slot's state is then not read) and which
# tokens of a bucket are real.
from ray_tpu.ops import apply_rope, lightning, rms_norm, sparse_attention
from ray_tpu.ops.sparse_attention import Selection

Params = Dict[str, Any]
F32 = jnp.float32
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

SLOT_STATE_KEYS = ("state",)
# Fetched counter -> the attribute the request's span carries it under.
SPAN_ATTRS = {"state_resets": "state_reset",
              "prefill_chunks": "prefill_chunks"}


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    d_model: int = 4096
    mixer_types: Tuple[str, ...] = (SPARSE,) + (LIGHTNING,) * 3
    n_heads: int = 32                # sparse layers: query heads
    n_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    d_ff: int = 16384
    max_seq_len: int = 524288
    rope_theta: float = 10000.0
    sparse_rope: bool = False        # the published ``attn_use_rope``
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32        # the published depth in the scalar
    dim_model_base: int = 256
    norm_eps: float = 1e-6
    selection: Selection = Selection()
    dtype: Any = jnp.bfloat16
    # Run the decode kernels under the Pallas interpreter off the TPU
    # (tests); otherwise the kernels on the TPU, their jnp twins off it.
    interpret_kernels: bool = False

    def __post_init__(self):
        unknown = set(self.mixer_types) - {SPARSE, LIGHTNING}
        if unknown:
            raise ValueError(f"mixer types {sorted(unknown)} are not in "
                             "models/minicpm_sala.py")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step."""
        return sys.modules[__name__]

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def n_sparse_layers(self) -> int:
        return self.mixer_types.count(SPARSE)

    @property
    def n_lightning_layers(self) -> int:
        return self.mixer_types.count(LIGHTNING)

    @property
    def branch_scale(self) -> float:
        return self.scale_depth / self.mup_denominator ** 0.5

    @property
    def segments(self):
        """Runs of one kind in ``mixer_types``: (kind, the run's first
        layer in its kind's stack, layers). The irregular order is
        walked a run at a time, each a scan over its layers."""
        out, seen = [], {SPARSE: 0, LIGHTNING: 0}
        for kind in self.mixer_types:
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return [tuple(run) for run in out]


# Parameters ---------------------------------------------------------------

def init_params(cfg: MiniCPMSalaConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled, norm gains stored as offsets from
    one. Two stacks, ``sparse`` [sparse layers, ..] and ``lightning``
    [lightning layers, ..], layer i of ``mixer_types`` being the next of
    its kind; matrices input-major but the q, k, v and gate projections,
    which are output-major [H*D, d] (`_proj`)."""
    d, dt, f = cfg.d_model, cfg.dtype, cfg.d_ff
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lh, ld = cfg.lightning_heads, cfg.lightning_head_dim
    keys = iter(jax.random.split(key, 32))

    def norm(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(dt)

    def mlp(lead):
        return {"ln_in": jnp.zeros(lead + (d,), dt),
                "ln_mlp": jnp.zeros(lead + (d,), dt),
                "w_gate": norm(lead + (d, f), d),
                "w_up": norm(lead + (d, f), d),
                "w_down": norm(lead + (f, d), f)}

    sp, li = (cfg.n_sparse_layers,), (cfg.n_lightning_layers,)
    return {
        "embed": norm((cfg.vocab_size, d), d),
        "sparse": dict(
            mlp(sp),
            wq=norm(sp + (h * hd, d), d), wk=norm(sp + (kh * hd, d), d),
            wv=norm(sp + (kh * hd, d), d),
            ln_q=jnp.zeros(sp + (hd,), dt), ln_k=jnp.zeros(sp + (hd,), dt),
            w_g=norm(sp + (h * hd, d), d),
            wo=norm(sp + (h * hd, d), h * hd)),
        "lightning": dict(
            mlp(li),
            w_q=norm(li + (lh * ld, d), d), w_k=norm(li + (lh * ld, d), d),
            w_v=norm(li + (lh * ld, d), d),
            ln_q=jnp.zeros(li + (ld,), dt), ln_k=jnp.zeros(li + (ld,), dt),
            w_g=norm(li + (lh * ld, d), d),
            ln_o=jnp.zeros(li + (ld,), dt),
            w_o=norm(li + (lh * ld, d), lh * ld)),
        "ln_out": jnp.zeros((d,), dt),
        "lm_head": norm((d, cfg.vocab_size), d),
    }


# The halves of a block ----------------------------------------------------

def _proj(x, w, heads: int):
    """x [..,d] through a projection w [H*D, d] -> [..,H,D] float32.
    The q, k, v and gate projections are STORED output-major with their
    heads merged. The chip's compiler wants their contraction axis
    minor: stored [d, H*D] (and [d, H, D], whose last two axes it tiles)
    it laid the whole STACK of layers out again, once a program: 1.9 GB
    of temporaries and as much copied a decode chunk (the described
    chip, PR 35); stored so, 0.08 GB."""
    flat = _mm("...d,fd->...f", x, w)
    return flat.reshape(x.shape[:-1] + (heads, -1))


def _after(x, mixed, layer, cfg: MiniCPMSalaConfig):
    """``h = x + a mixed``, then the SwiGLU half on ``RMSNorm(h)``. The
    residual stream is float32."""
    h = x + cfg.branch_scale * mixed
    n = rms_norm(h, layer["ln_mlp"], cfg.norm_eps)
    ff = jax.nn.silu(_mm("...d,df->...f", n, layer["w_gate"])) * _mm(
        "...d,df->...f", n, layer["w_up"])
    return h + cfg.branch_scale * _mm("...f,fd->...d", ff, layer["w_down"])


def _lightning_qkv(x, layer, positions, cfg: MiniCPMSalaConfig):
    """n [..,T,d] (normed), positions [..,T] -> q (scaled), k, v
    [..,T,H,128] float32 and the gate [..,T,H,128]."""
    h = cfg.lightning_heads
    q = rms_norm(_proj(x, layer["w_q"], h), layer["ln_q"], cfg.norm_eps)
    k = rms_norm(_proj(x, layer["w_k"], h), layer["ln_k"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return (q * cfg.lightning_head_dim ** -0.5, k,
            _proj(x, layer["w_v"], h),
            jax.nn.sigmoid(_proj(x, layer["w_g"], h)))


def _lightning_out(o, gate, layer, cfg: MiniCPMSalaConfig):
    """o [..,H,128] float32 -> the mixer's output [..,d]."""
    y = rms_norm(o, layer["ln_o"], cfg.norm_eps) * gate
    return _mm("...f,fd->...d", y.reshape(y.shape[:-2] + (-1,)),
               layer["w_o"])


def _lightning_prefill_block(x, layer, state_l, cache_index, positions, last,
                             cfg: MiniCPMSalaConfig):
    """x [B,T,d]; state_l [B,H,128,128]: the slot's -> (x, state_l as
    it stands after the last REAL token)."""
    valid, _ = _real(x.shape[1], last)
    n = rms_norm(x, layer["ln_in"], cfg.norm_eps)
    q, k, v, gate = _lightning_qkv(n, layer, positions, cfg)
    g = jnp.broadcast_to(lightning.log_decays(cfg.lightning_heads),
                         q.shape[:3])
    if valid is not None:       # padding: no decay, nothing written
        g = jnp.where(valid[None, :, None], g, 0.0)
        k = jnp.where(valid[None, :, None, None], k, 0.0)
    state = jnp.where(_starts_fresh(cache_index), 0.0, state_l)
    o, state = lightning.chunk_scan(q, k, v, g, state)
    return _after(x, _lightning_out(o, gate, layer, cfg), layer, cfg), state


def _lightning_decode_block(x, layer, layer_idx, state, lengths, live,
                            cfg: MiniCPMSalaConfig):
    """x [B,d]; the whole ``state`` array carried; a slot that is not
    ``live`` keeps its state."""
    n = rms_norm(x, layer["ln_in"], cfg.norm_eps)
    q, k, v, gate = _lightning_qkv(n[:, None], layer, lengths[:, None], cfg)
    g = jnp.where(live[:, None],
                  lightning.log_decays(cfg.lightning_heads)[None], 0.0)
    o, state = lightning.lightning_decode(
        state, layer_idx, q[:, 0], jnp.where(live[:, None, None], k[:, 0], 0),
        v[:, 0], g, interpret=cfg.interpret_kernels)
    return (_after(x, _lightning_out(o, gate[:, 0], layer, cfg), layer, cfg),
            state)


def _sparse_qkv(n, layer, positions, cfg: MiniCPMSalaConfig):
    """n [..,T,d] (normed), positions [..,T] -> q [..,T,H,128], k, v
    [..,T,KH,128] in the type the cache holds and the kernels read (q
    and k normed per head; rotated only under ``sparse_rope``, which
    the published model switches off), and the gate [..,T,H,128]
    float32."""
    dt = cfg.dtype
    h, kh = cfg.n_heads, cfg.n_kv_heads
    q = rms_norm(_proj(n, layer["wq"], h), layer["ln_q"], cfg.norm_eps)
    k = rms_norm(_proj(n, layer["wk"], kh), layer["ln_k"], cfg.norm_eps)
    if cfg.sparse_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return (q.astype(dt), k.astype(dt),
            _proj(n, layer["wv"], kh).astype(dt),
            jax.nn.sigmoid(_proj(n, layer["w_g"], h)))


def _sparse_out(attn, gate, layer):
    """attn [..,H,128], gate [..,H,128] float32 -> the mixer's output."""
    y = attn.astype(F32) * gate
    return _mm("...f,fd->...d", y.reshape(y.shape[:-2] + (-1,)), layer["wo"])


def _chunk_windows(ck, cache_index, t: int, sel: Selection):
    """The compressed keys a prefill chunk of ``t`` rows at
    ``cache_index`` completes or completes again, from the slot's rows
    ck [B,KH,S,D] with the chunk written: -> (windows [B,KH,W,D], the
    first one's index). The window that straddles the boundary with the
    chunk before is among them; the one that straddles the chunk's end
    is written too and counts for no query until its rows are there."""
    ext = sel.stride if t + sel.stride <= ck.shape[2] else 0
    first = jnp.maximum(cache_index - ext, 0)
    rows = lax.dynamic_slice_in_dim(ck, first, t + ext, axis=2)
    return sparse_attention.window_means(rows, sel), first // sel.stride


def _sparse_prefill_block(x, layer, ck, cv, ckc, cache_index, positions,
                          cfg: MiniCPMSalaConfig):
    """x [B,T,d]; ck, cv [B,KH,S,D], ckc [B,KH,S/16,D]: the slot's rows
    of this layer -> (x, ck, cv, ckc, chosen [B,T,KH,S/64] bool: the
    mask over blocks each query attended under)."""
    sel = cfg.selection
    n = rms_norm(x, layer["ln_in"], cfg.norm_eps)
    q, k, v, gate = _sparse_qkv(n, layer, positions, cfg)
    # cache_index + T is bounded by the engine's contract, as in
    # llama._block: the scheduler admits only what fits a slot's rows.
    ck = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        ck, k.swapaxes(1, 2).astype(ck.dtype), (0, 0, cache_index, 0))
    cv = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        cv, v.swapaxes(1, 2).astype(cv.dtype), (0, 0, cache_index, 0))
    windows, first = _chunk_windows(ck, cache_index, x.shape[1], sel)
    ckc = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        ckc, windows.astype(ckc.dtype), (0, 0, first, 0))
    attn, chosen = jax.vmap(
        lambda q, k, v, kc, pos: sparse_attention.sparse_prefill_attention(
            q, k, v, kc, pos, sel))(q, ck, cv, ckc, positions)
    mixed = _sparse_out(attn, gate, layer)
    return _after(x, mixed, layer, cfg), ck, cv, ckc, chosen


def _completed_window(cache_k, layer_idx, lengths, live, sel: Selection):
    """After a decode step wrote row ``lengths[b]`` of layer
    ``layer_idx``: (the mean of the ``kernel`` rows that end there
    [B,KH,D], the window's index [B], or the array's LAST index, which
    no query counts, where the row completes no window or the slot is
    not live)."""
    n_layers, b, kh, s, d = cache_k.shape
    r = lengths.astype(jnp.int32)
    heads = layer_idx * (b * kh) + jnp.arange(b * kh, dtype=jnp.int32)
    rows = jnp.repeat(r, kh)[:, None] - jnp.arange(sel.kernel)[None, ::-1]
    window = cache_k.reshape(n_layers * b * kh, s, d)[
        heads[:, None], jnp.maximum(rows, 0)]           # [B*KH,kernel,D]
    whole = live & ((r + 1) % sel.stride == 0) & (r + 1 >= sel.kernel)
    index = jnp.where(whole, (r + 1 - sel.kernel) // sel.stride,
                      s // sel.stride - 1)
    return jnp.mean(window.astype(F32), axis=1).reshape(b, kh, d), index


def _sparse_decode_block(x, layer, layer_idx, cache_k, cache_v, cache_kc,
                         lengths, seen, live, cfg: MiniCPMSalaConfig):
    """x [B,d]; the whole K, V and compressed-key arrays carried: slot
    b's new row is written at ``lengths[b]`` (llama's scatter), the
    window it completes (if any) beside it, then ONE selection and ONE
    kernel call for all slots. -> (x, k, v, kc, ids [B,KH,N], count
    [B])."""
    sel = cfg.selection
    n = rms_norm(x, layer["ln_in"], cfg.norm_eps)
    q, k, v, gate = (a[:, 0] for a in _sparse_qkv(
        n[:, None], layer, lengths[:, None], cfg))
    cache_k = _write_rows(cache_k, layer_idx, lengths, k)
    cache_v = _write_rows(cache_v, layer_idx, lengths, v)
    window, index = _completed_window(cache_k, layer_idx, lengths, live, sel)
    cache_kc = _write_rows(cache_kc, layer_idx, index, window)
    ids, count = sparse_attention.select_blocks(
        q, lax.dynamic_index_in_dim(cache_kc, layer_idx, 0, keepdims=False),
        seen, sel)
    attn = sparse_attention.sparse_decode_attention(
        q, cache_k, cache_v, ids, count, seen, layer=layer_idx,
        block=sel.block, interpret=cfg.interpret_kernels)
    return (_after(x, _sparse_out(attn, gate, layer), layer, cfg), cache_k,
            cache_v, cache_kc, ids, count)


# The engine's seam --------------------------------------------------------

def init_kv_cache(cfg: MiniCPMSalaConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """Rows, windows and state in one cache (this module's header).
    ``max_len`` is rounded up to whole blocks."""
    dt = dtype or cfg.dtype
    sel = cfg.selection
    max_len = -(-max_len // sel.block) * sel.block
    rows = (cfg.n_sparse_layers, batch, cfg.n_kv_heads, max_len,
            cfg.head_dim)
    return {
        "k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
        "kc": jnp.zeros(rows[:3] + (max_len // sel.stride, cfg.head_dim), dt),
        "state": jnp.zeros(
            (cfg.n_lightning_layers, batch, cfg.lightning_heads,
             cfg.lightning_head_dim, cfg.lightning_head_dim), F32)}


def _embed(params, tokens, cfg: MiniCPMSalaConfig):
    return jnp.take(params["embed"], tokens, axis=0).astype(F32) * cfg.scale_emb


def _head(x, params, eq: str, cfg: MiniCPMSalaConfig):
    x = rms_norm(x, params["ln_out"], cfg.norm_eps) * (
        cfg.dim_model_base / cfg.d_model)
    return _mm(eq, x, params["lm_head"])


def _prefill(params, tokens, cache, cache_index, last,
             cfg: MiniCPMSalaConfig):
    """-> (x [B,T,d] before the final norm, cache, counters, seen): a
    scan over the layers of each run of one kind, the cache's arrays
    carried and each layer's slice rewritten. ``cache`` holds ONE
    slot's rows and state."""
    b, t = tokens.shape
    positions = cache_index + jnp.broadcast_to(jnp.arange(t), (b, t))
    x = _embed(params, tokens, cfg)
    at = lambda a, i: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    put = lambda a, row, i: lax.dynamic_update_index_in_dim(a, row, i, 0)

    def sparse(carry, idx):
        x, k, v, kc, state = carry
        x, ck, cv, ckc, mask = _sparse_prefill_block(
            x, _layer_of(params["sparse"], idx), at(k, idx), at(v, idx),
            at(kc, idx), cache_index, positions, cfg)
        return (x, put(k, ck, idx), put(v, cv, idx), put(kc, ckc, idx),
                state), mask

    def linear(carry, idx):
        x, k, v, kc, state = carry
        x, state_l = _lightning_prefill_block(
            x, _layer_of(params["lightning"], idx), at(state, idx),
            cache_index, positions, last, cfg)
        return (x, k, v, kc, put(state, state_l, idx)), None

    carry = (x, cache["k"], cache["v"], cache["kc"], cache["state"])
    chosen = []
    for kind, first, n in cfg.segments:
        carry, mask = lax.scan(sparse if kind == SPARSE else linear, carry,
                               first + jnp.arange(n, dtype=jnp.int32))
        if kind == SPARSE:
            chosen.append(mask)
    x, k, v, kc, state = carry
    counters = {
        "prefill_chunks": jnp.asarray(b, jnp.int32),
        "state_resets": b * _starts_fresh(cache_index).astype(jnp.int32)}
    seen = {"block_mask": jnp.concatenate(chosen)} if chosen else {}
    return x, {"k": k, "v": v, "kc": kc, "state": state}, counters, seen


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: MiniCPMSalaConfig):
    """tokens [B,T], all real, written at rows [cache_index,
    cache_index+T) and scanned from the slot's state (zero at
    ``cache_index`` 0) -> (logits [B,T,V], cache, counters, seen:
    ``block_mask`` [sparse layers, B, T, KH, S/64] bool, the blocks each
    query attended over: the mask the attention itself read, every
    block where the query's context is dense)."""
    x, cache, counters, seen = _prefill(
        params, tokens, cache, jnp.asarray(cache_index, jnp.int32), None, cfg)
    logits = _head(x, params, "btd,dv->btv", cfg).astype(cfg.dtype)
    return logits, cache, counters, seen


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: MiniCPMSalaConfig):
    """The tick's prefill: ``tokens[:, :last+1]`` are real, the rest
    bucket padding that steps no state -> (logits [B,V] of row
    ``last``, cache, counters, seen)."""
    x, cache, counters, seen = _prefill(
        params, tokens, cache, jnp.asarray(cache_index, jnp.int32), last, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    return _head(row, params, "bd,dv->bv", cfg), cache, counters, seen


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: MiniCPMSalaConfig,
                           live=None):
    """One decode step for every slot: tokens [B,1], lengths [B],
    ``live`` [B] bool (None: all) -> (logits [B,V], cache, counters,
    seen). The scans CARRY the four cache arrays: donated, the step
    rewrites a row (and at most a window) a sparse layer a slot and a
    state a lightning layer a live slot, and copies none. A slot that
    is not live keeps its state and its windows; its K/V write lands
    where the engine parked it and its attention reads no block.
    Counters, summed over the sparse layers: ``sparse_rows_held`` (the
    rows live slots hold) and ``sparse_rows_selected`` (the rows of
    them that lie in the blocks read), ``sparse_select_steps`` (live
    slots past ``dense_len``); ``lightning_state_steps``: live slots x
    lightning layers."""
    sel = cfg.selection
    x = _embed(params, tokens, cfg)[:, 0]
    live = (jnp.ones(lengths.shape, bool) if live is None
            else live.astype(bool))
    seen = jnp.where(live, lengths + 1, 0).astype(jnp.int32)

    def sparse(carry, idx):
        x, k, v, kc, state = carry
        x, k, v, kc, ids, count = _sparse_decode_block(
            x, _layer_of(params["sparse"], idx), idx, k, v, kc, lengths,
            seen, live, cfg)
        return (x, k, v, kc, state), (ids, count)

    def linear(carry, idx):
        x, k, v, kc, state = carry
        x, state = _lightning_decode_block(
            x, _layer_of(params["lightning"], idx), idx, state, lengths,
            live, cfg)
        return (x, k, v, kc, state), None

    carry = (x, cache["k"], cache["v"], cache["kc"], cache["state"])
    chosen, counts = [], []
    for kind, first, n in cfg.segments:
        carry, ys = lax.scan(sparse if kind == SPARSE else linear, carry,
                             first + jnp.arange(n, dtype=jnp.int32))
        if kind == SPARSE:
            chosen.append(ys[0])
            counts.append(ys[1])
    x, k, v, kc, state = carry
    logits = _head(x, params, "bd,dv->bv", cfg)
    selects = (seen > sel.dense_len)
    counters = {
        "sparse_rows_held": cfg.n_sparse_layers * jnp.sum(seen),
        "sparse_select_steps":
            cfg.n_sparse_layers * jnp.sum(selects, dtype=jnp.int32),
        "lightning_state_steps":
            cfg.n_lightning_layers * jnp.sum(live, dtype=jnp.int32)}
    out = {}
    if chosen:
        ids, count = jnp.concatenate(chosen), jnp.concatenate(counts)
        # The block that holds a slot's newest row is always read: of
        # the listed blocks' rows, those past it are not there.
        counters["sparse_rows_selected"] = jnp.sum(
            jnp.where(count > 0, count * sel.block - (-seen) % sel.block, 0))
        out["blocks"] = jnp.where(
            selects[None, :, None, None], ids[..., :sel.topk],
            -1)[:, :, None]                             # [Ls,B,1,KH,topk]
    return logits, {"k": k, "v": v, "kc": kc, "state": state}, counters, out


def forward(params: Params, tokens: jnp.ndarray,
            cfg: MiniCPMSalaConfig) -> jnp.ndarray:
    """Full causal forward, no cache kept: tokens [B,T] -> logits."""
    b, t = tokens.shape
    return forward_with_cache(params, tokens, init_kv_cache(cfg, b, t), 0,
                              cfg)[0]
