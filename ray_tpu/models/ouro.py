"""A looped decoder (the Ouro family, arXiv:2510.25741): ONE stack of
sandwich-norm blocks that every token crosses ``n_loops`` times with the
same weights, each pass keeping K/V rows of its own, and an exit gate
read after every pass.

    block_l(x):  a = Attn_l(RMS(x; g1_l));      x = x + RMS(a; g2_l)
                 m = SwiGLU_l(RMS(x; g3_l));    x = x + RMS(m; g4_l)
    model:       h_0 = E[tokens]
                 for u = 1 .. T, THE SAME L blocks:
                     h_u = RMS(block_L( .. block_1(h_{u-1}) ..); g_out)
                     lambda_u = sigmoid(h_u . w_gate + b_gate)
                 logits = h_T W_head

Attention is plain multi-head (or grouped) attention with rotate-half
RoPE at the token's position, THE SAME position in every pass, no
projection bias. The two norms of a branch's OUTPUT (`_branch_norm`)
sit on the branch before the addition, so `fused_rms_norm_residual`,
which adds first, does not apply. The final norm is applied after EVERY
pass and the normed state is handed to the next (`_between_passes`).

**The exit gate** is computed after every pass, in the programs as in
the reference (``seen["gates"]``); at ``exit_threshold`` 1.0, the
published value, the first pass at which the accumulated exit
probability reaches the threshold is the last one for every token, so
every token runs all passes. A threshold under 1 (slots of one step at
different depths) is refused at construction: ROADMAP R14.

**The cache** is ``{"k", "v"}`` of ``[n_loops x n_layers, B, KH, S,
hd]``, slot axis second: entry `_entry` ``(u - 1) x n_layers + l``
holds pass u's rows of layer l, and pass u of layer l attends to ITS
OWN keys and values of the earlier positions. To `_write_rows`,
`decode_attention(.., layer=..)` and `decode_step_rows` an entry is what
a layer is to llama. A prefix's rows are a pure function of the prefix
(every pass is causal): no per-slot state, no ``SLOT_STATE_KEYS``, the
engine's prefix cache reuses rows.

**The programs' shape.** A prefill and a decode step cross the stack
``n_loops`` times: a Python loop over the passes, each pass ONE
`lax.scan` over ``params["blocks"]`` (llama's form, four times over).
The step's scans CARRY the whole cache, so that a donated program
rewrites the new rows where they lie; a prefill's leave their new rows
to be written once a pass; in both each matrix is read by its product
where it lies: a program reads the weights ``n_loops`` times and copies
them never (PERF.md section 5 has the chip's op table).

**Precision**: the residual stream, the norms, RoPE and the gate are
float32; every product takes bf16 operands (`_mm`: the activation
rounded to the weight's type on the way in) and accumulates in float32;
the cache holds ``cfg.dtype`` rows.

What the engine's seam asks: `init_params`, `init_kv_cache`,
`forward_with_cache`, `forward_last_with_cache`,
`decode_step_with_cache`; each returns ``(logits, cache, counters,
seen)``: ``counters`` ride the fetch the tick makes anyway, ``seen``
(each pass's gate; at the row the head reads, what every block
application read, added and handed on) is what a check reads, returned
by the check's programs only.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.models.common import _mm, _swiglu, _write_rows
from ray_tpu.ops import (apply_rope, causal_attention, decode_attention,
                         decode_step_rows, rms_norm)

Params = Dict[str, Any]
F32 = jnp.float32

# No optional mechanism of the engine yet (ROADMAP R1: these {k, v} rows
# are the form its four options were written for).
ENGINE_OFFERS = ()
# Fetched counter -> the attribute under which the request's span
# (``engine.prefill``, ``engine.decode_chunk``) carries it.
SPAN_ATTRS = {"loop_passes": "passes", "loop_prefill_passes": "passes"}


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    # Passes over the stack (``total_ut_steps``) and the accumulated
    # exit probability at which a token would leave it
    # (``early_exit_threshold``).
    n_loops: int = 4
    exit_threshold: float = 1.0
    max_seq_len: int = 65536
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # The tests' hook (``serve/engine/README.md``): the decode step's
    # attention kernel under the Pallas interpreter off the TPU.
    interpret_kernels: bool = False

    def __post_init__(self):
        if self.exit_threshold < 1.0:
            raise ValueError(
                f"exit_threshold {self.exit_threshold} < 1 lets a token "
                f"leave the stack after fewer passes than its neighbours: "
                f"slots of one step at different depths are ROADMAP R14, "
                f"not served yet (models/ouro.py runs every pass)")
        if self.n_loops < 1:
            raise ValueError("n_loops counts the passes: at least 1")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step (``serve/engine/README.md``)."""
        return sys.modules[__name__]

    @property
    def n_entries(self) -> int:
        """(pass, layer) cache entries a position."""
        return self.n_loops * self.n_layers

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        attn = 2 * d * (self.n_heads + self.n_kv_heads) * self.head_dim
        return (2 * v * d + self.n_layers * (attn + 3 * d * f + 4 * d)
                + d + d + 1)


def tiny_config(**kw) -> OuroConfig:
    base = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                n_kv_heads=4, head_dim=16, d_ff=128, n_loops=4,
                max_seq_len=128, dtype=F32)
    base.update(kw)
    return OuroConfig(**base)


def init_params(cfg: OuroConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled, in ``cfg.dtype``; the gate's
    weight fan-in scaled and its bias 0, float32. Norm gains (stored, as
    `ops.rms_norm` reads them, as an offset from one) are one, but for
    the two norms of a block's BRANCH OUTPUTS, drawn uniformly in [1/8,
    3/8] a channel: a residual branch that adds a whole unit vector to a
    stream re-normed to unit size every pass makes a seeded network of
    ``n_loops x n_layers`` blocks amplify a rounding a hundredfold (bf16
    products then move the logits by 10 to 20 % of their norm: the chip,
    PR 64), where a branch scaled down at its output, as deep residual
    networks are initialised, keeps it to a few per cent.

    ``wq``, ``wk`` and ``wv`` are stored as a checkpoint's
    ``q_proj.weight`` is, OUTPUT-major with a row's heads side by side
    ([H x hd, d]): stored [d, H, hd] or [d, H x hd], the chip's compiler
    re-lays the three stacks out to this form once a program (1.2 GB
    copied a decode chunk and a prefill, and as much held: the compile
    for a described v5e, PR 64). ``wo`` is [H x hd, d], input-major like
    the SwiGLU's three."""
    d, hd, h, kh, f, v, l = (cfg.d_model, cfg.head_dim, cfg.n_heads,
                             cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size,
                             cfg.n_layers)
    keys = jax.random.split(key, 12)
    dt = cfg.dtype

    def norm(key, shape, fan_in, dtype=dt):
        return (jax.random.normal(key, shape, F32)
                * (fan_in ** -0.5)).astype(dtype)

    ones = lambda: jnp.zeros((l, d), F32)  # noqa: E731
    branch = lambda key: jax.random.uniform(  # noqa: E731
        key, (l, d), F32, 0.125, 0.375) - 1.0
    return {
        "embed": norm(keys[0], (v, d), d),
        "blocks": {
            "ln_attn": ones(), "ln_attn_out": branch(keys[10]),
            "wq": norm(keys[1], (l, h * hd, d), d),
            "wk": norm(keys[2], (l, kh * hd, d), d),
            "wv": norm(keys[3], (l, kh * hd, d), d),
            "wo": norm(keys[4], (l, h * hd, d), h * hd),
            "ln_mlp": ones(), "ln_mlp_out": branch(keys[11]),
            "w_gate": norm(keys[5], (l, d, f), d),
            "w_up": norm(keys[6], (l, d, f), d),
            "w_down": norm(keys[7], (l, f, d), f),
        },
        "ln_out": jnp.zeros((d,), F32),
        "exit_gate": {"w": norm(keys[8], (d,), d, F32),
                      "b": jnp.zeros((), F32)},
        "head": norm(keys[9], (d, v), d),
    }


def init_kv_cache(cfg: OuroConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """``n_loops x n_layers`` entries of llama's engine-native [B, KH,
    S, hd] rows (this module's header)."""
    dt = dtype or cfg.dtype
    shape = (cfg.n_entries, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


# What the benchmark's controls replace (`benchmark/degraded_looped.py`)
# are these three names, `_after_attention` and ``cfg.n_loops``.

def _entry(u, layer_idx, cfg: OuroConfig):
    """The cache entry of pass ``u`` (from 0) of layer ``layer_idx``."""
    return u * cfg.n_layers + layer_idx


def _branch_norm(y, gain, cfg: OuroConfig):
    """The norm of a branch's output, before it is added."""
    return rms_norm(y, gain, cfg.norm_eps)


def _between_passes(x, h):
    """What a pass hands the next, of the stack's output ``x`` and its
    final norm ``h``: the NORMED state."""
    return h


def _gate(h, params):
    """h [.., d] float32 -> lambda [..]: one number a token."""
    gate = params["exit_gate"]
    return jax.nn.sigmoid(jnp.sum(h * gate["w"], axis=-1) + gate["b"])


def _qkv(x, layer, positions, cfg: OuroConfig):
    """x [B,T,d] float32 -> q [B,T,H,hd], k, v [B,T,KH,hd] in the type
    the cache holds, q and k rotated (float32 until then)."""
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)

    def heads(w):
        y = _mm("btd,nd->btn", h, w)
        return y.reshape(y.shape[:2] + (-1, cfg.head_dim))

    q = apply_rope(heads(layer["wq"]), positions, cfg.rope_theta)
    k = apply_rope(heads(layer["wk"]), positions, cfg.rope_theta)
    v = heads(layer["wv"])
    return q.astype(cfg.dtype), k.astype(cfg.dtype), v.astype(cfg.dtype)


def _attn_out(attn, layer):
    """attn [.., H, hd] -> the branch's output [.., d] float32."""
    return _mm("...n,nd->...d", attn.reshape(attn.shape[:-2] + (-1,)),
               layer["wo"])


def _after_attention(x, attn_out, layer, cfg: OuroConfig):
    """The rest of a block on x [.., d] once its attention branch's
    output ``attn_out`` [.., d] is known -> (x, what a check reads of
    this application: the two branches as added)."""
    a = _branch_norm(attn_out, layer["ln_attn_out"], cfg)
    x = x + a
    h = rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    flat = h.reshape(-1, h.shape[-1])
    m = _swiglu(flat, layer["w_gate"], layer["w_up"],
                layer["w_down"]).reshape(h.shape)
    m = _branch_norm(m, layer["ln_mlp_out"], cfg)
    return x + m, (a, m)


def _prefill_block(x, layer, entry, cache, cache_index, positions,
                   cfg: OuroConfig):
    """x [B,T,d]; ``cache`` the slot's rows as they were BEFORE the
    call, of which the queries see this (pass, layer) ``entry``'s under
    ``cache_index`` beside the call's own -> (x, k, v [B,KH,T,hd]: the
    rows to write at [cache_index, cache_index + T), branches). A
    prompt's first bucket reads no row of the cache."""
    q, k, v = _qkv(x, layer, positions, cfg)

    def fresh(_):
        return causal_attention(q, k, v, q_positions=positions,
                                kv_positions=positions)

    def through_the_cache(_):
        old_k, old_v = (lax.dynamic_index_in_dim(
            cache[key], entry, 0, keepdims=False).swapaxes(1, 2)
            for key in ("k", "v"))                          # [B,S,KH,hd]
        b, s = old_k.shape[:2]
        old_pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        return causal_attention(
            q, jnp.concatenate([old_k, k], axis=1),
            jnp.concatenate([old_v, v], axis=1), q_positions=positions,
            kv_positions=jnp.concatenate([old_pos, positions], axis=1),
            kv_mask=jnp.concatenate(
                [old_pos < cache_index, jnp.ones(positions.shape, bool)],
                axis=1))

    attn = lax.cond(cache_index == 0, fresh, through_the_cache, None)
    x, branches = _after_attention(x, _attn_out(attn, layer), layer, cfg)
    return x, k.swapaxes(1, 2), v.swapaxes(1, 2), branches


def _decode_block(x, layer, entry, cache_k, cache_v, lengths, seen_rows,
                  cfg: OuroConfig):
    """x [B,d], one token a slot at position ``lengths[b]``; the whole
    K and V arrays carried: slot b's new row is written at ``[entry, b,
    :, lengths[b]]`` (llama's scatter), then ONE kernel call for all
    slots over each one's first ``seen_rows[b]`` rows of the entry."""
    q, k, v = _qkv(x[:, None], layer, lengths[:, None], cfg)
    cache_k = _write_rows(cache_k, entry, lengths, k[:, 0])
    cache_v = _write_rows(cache_v, entry, lengths, v[:, 0])
    attn = decode_attention(
        q[:, 0], cache_k, cache_v, seen_rows, layer=entry, layout="bksd",
        interpret=cfg.interpret_kernels)
    x, branches = _after_attention(
        x, _attn_out(attn, layer), layer, cfg)
    return x, cache_k, cache_v, branches


def _passes(params, x, kv, block, read_row, cfg: OuroConfig):
    """``x`` through ``cfg.n_loops`` passes of the stack, each ONE scan
    over ``params["blocks"]`` that carries ``kv``. ``block(x, layer, u,
    idx, kv)`` -> (x, kv, rows, branches): ``rows`` is what the
    application leaves to be stacked (a prefill's new K and V rows; a
    step writes its own and leaves None); ``read_row(a)`` picks of [B,
    .., d] what a check reads. -> (h_T, kv, each pass's stacked rows,
    seen)."""
    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    gates, stacked, handed = [], [], []
    for u in range(cfg.n_loops):
        def body(carry, layer_and_idx, u=u):
            x, kv = carry
            layer, idx = layer_and_idx
            y, kv, rows, (a, m) = block(x, layer, u, idx, kv)
            return (y, kv), (rows, {
                "entered": read_row(x), "attn": read_row(a),
                "ffn": read_row(m), "handed": read_row(y)})

        (x, kv), (rows, about) = lax.scan(body, (x, kv),
                                          (params["blocks"], layers))
        # The final norm, after EVERY pass: what the gate, the next pass
        # and (after the last) the head read.
        h = rms_norm(x, params["ln_out"], cfg.norm_eps)
        gates.append(_gate(h, params))
        x = _between_passes(x, h)
        stacked.append(rows)
        handed.append(about)
    seen = {"gates": jnp.stack(gates),                      # [T, B, ..]
            "blocks": jax.tree.map(lambda *a: jnp.concatenate(a), *handed)}
    return h, kv, stacked, seen


def _head(x, params):
    return _mm("...d,dv->...v", x, params["head"])


def _step_counters(cfg: OuroConfig):
    return {"loop_passes": jnp.asarray(cfg.n_loops, jnp.int32),
            "loop_layer_steps": jnp.asarray(cfg.n_entries, jnp.int32)}


def _prefill_counters(cfg: OuroConfig):
    """A prefill's passes under a name of their own: ``loop_passes``
    over the decode steps fetched is the passes a decoded token ran."""
    return {"loop_prefill_passes": jnp.asarray(cfg.n_loops, jnp.int32)}


def _hidden_with_cache(params, tokens, cache, cache_index, last,
                       cfg: OuroConfig):
    """tokens [B,T] at [cache_index, cache_index + T) -> (h_T [B,T,d],
    cache, seen); a check reads row ``last`` (None: the bucket's last)
    of every block application. Functional in the cache: an application
    reads its entry's rows as they came in and leaves its new rows to
    the scan, and each pass's are written after it, ONE update a pass
    (its entries lie side by side from `_entry` of layer 0): a cache
    carried through the scans and read where it was just written has
    the chip's compiler re-lay the slot's rows out and back a call (0.8
    GB each way at 512 rows: the compile for a described v5e, PR
    64)."""
    b, t = tokens.shape
    positions = cache_index + jnp.broadcast_to(jnp.arange(t), (b, t))
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    at = t - 1 if last is None else last

    def block(x, layer, u, idx, kv):
        x, k, v, branches = _prefill_block(
            x, layer, _entry(u, idx, cfg), cache, cache_index, positions,
            cfg)
        return x, kv, (k, v), branches

    x, _, stacked, seen = _passes(
        params, x, None, block,
        lambda a: lax.dynamic_index_in_dim(a, at, 1, keepdims=False), cfg)
    ck, cv = cache["k"], cache["v"]
    for u, (k, v) in enumerate(stacked):        # [L,B,KH,T,hd] each
        # cache_index + T is bounded by the engine's contract, as in
        # llama._block: the scheduler admits only what fits a slot's rows.
        at_entry = (_entry(u, 0, cfg), 0, 0, cache_index, 0)
        ck = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
            ck, k.astype(ck.dtype), at_entry)
        cv = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
            cv, v.astype(cv.dtype), at_entry)
    return x, {"k": ck, "v": cv}, seen


def forward_with_cache(params: Params, tokens, cache, cache_index,
                       cfg: OuroConfig):
    """A prefill bucket with the bucket's logits [B,T,V]."""
    x, cache, seen = _hidden_with_cache(params, tokens, cache, cache_index,
                                        None, cfg)
    return _head(x, params), cache, _prefill_counters(cfg), seen


def forward_last_with_cache(params: Params, tokens, cache, cache_index,
                            last, cfg: OuroConfig):
    """The tick's prefill: the same passes, the head for row ``last``
    alone -> (logits [B,V], ..). Rows past ``last`` are bucket padding:
    every pass is causal, so they move nothing the head reads, and
    their cache rows lie past the slot's length."""
    x, cache, seen = _hidden_with_cache(params, tokens, cache, cache_index,
                                        last, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    return _head(row, params), cache, _prefill_counters(cfg), seen


def decode_step_with_cache(params: Params, tokens, cache, lengths,
                           cfg: OuroConfig, live=None):
    """One decode step for every slot of the engine's cache at once:
    tokens [B,1], lengths [B], ``live`` [B] bool (None: all) -> (logits
    [B,V], the cache with one new row an ENTRY a slot, counters, seen).
    A slot that is not live attends to no row (llama's rule). The
    counters: ``decode_attn_rows`` / ``decode_attn_rows_streamed`` of
    ONE entry, as llama counts one layer's; ``loop_passes`` the passes
    this step ran, ``loop_layer_steps`` its block applications."""
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(F32)
    seen_rows, counters = decode_step_rows(lengths, live, cache["k"])

    def block(x, layer, u, idx, kv):
        x, ck, cv, branches = _decode_block(
            x, layer, _entry(u, idx, cfg), *kv, lengths, seen_rows, cfg)
        return x, (ck, cv), None, branches

    x, (ck, cv), _, seen = _passes(params, x, (cache["k"], cache["v"]),
                                   block, lambda a: a, cfg)
    return (_head(x, params), {"k": ck, "v": cv},
            {**counters, **_step_counters(cfg)}, seen)
