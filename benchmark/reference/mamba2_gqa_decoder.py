"""The plain reference of the dense ``granitemoehybrid`` family
(Granite-4.0-H: Mamba-2 layers among a few grouped-query attention
layers): the next-token forward pass in straightforward float32
``jax.numpy``.

Written from the published description (the model's ``config.json``
and the layer equations as issue 46 sets them out, which are those of
the publisher's ``GraniteMoeHybridForCausalLM``:
``tests/test_granite_hybrid_published.py`` holds this file to that code
on seeded weights), independent of ``ray_tpu/models/granite_hybrid.py``
and ``ray_tpu/ops/mamba2.py``. ``layer_types`` says which layers are
``mamba`` and which ``attention``. ``x`` is a block's input, d the
hidden size; RMSNorm has a gain and ``rms_norm_eps``; no bias but the
convolution's:

    x_0    = embedding_multiplier E[token]
    x      <- x + residual_multiplier Mixer(RMSNorm(x))
    [a; b] = RMSNorm(x) W_in                      (2 x shared_intermediate_size)
    x      <- x + residual_multiplier (silu(a) * b) W_out
    logits = RMSNorm(last x) E^T / logits_scaling        (tied head)

*Mamba-2 layer* (H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, N =
``mamba_d_state``, one group, I = H P): ``[z (I); u (I + 2N); delta (H)]
= h W_in``. ``u'_t = silu(c_b + sum_{i<W} c_i u_{t-W+1+i})``, zeros before
the sequence, W = ``mamba_d_conv``; ``u' = [x (H x P); B (N); C (N)]``.
``dt = softplus(delta + dt_bias)`` (the published limits are (0, inf): no
clamp), ``A = -exp(A_log)``. The state S in R^{H x P x N}, ``S_0 = 0``:

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
    y_t[h] = S_t[h] C_t + D[h] x_t[h]

ONE TOKEN AT A TIME, exactly as written (never the chunked form the
system's prefill runs, nor the publisher's, whose naive path is chunked
too). ``Mixer(h)_t = RMSNorm_I(y_t * silu(z_t); w) W_out``: the gate
BEFORE the norm, the norm over all I values.

*Attention layer*: ``q = h W_q`` (``num_attention_heads`` of
``hidden_size / num_attention_heads``), ``k, v`` over
``num_key_value_heads``; no rotary embedding
(``position_embedding_type`` ``nope``), no q/k norm; causal
softmax(``attention_multiplier`` q k^T) v; ``W_o``.

No cache, no kernels, no chunks: a Python loop over layers, each matrix
widened from bf16 to float32 as it is used, every product under
``jax.default_matmul_precision("highest")``. The head is made a block
of vocabulary columns at a time, which changes no number.

**Assumed** (the configuration file's ``assumed`` says the same): the
state and its update in float32; ``A_log``, ``dt_bias`` and ``D`` are
read as the tree holds them (the system's builder draws the first two
as the Mamba-2 authors' initialisation does; the publisher's
``_init_weights`` sets ``A = 1 .. H`` and ``dt_bias = 1``, under which
the heads of large A forget within a token).

It reads the SYSTEM's parameter tree; what is the system's convention
and not the published one is undone here:

- norm gains are stored as an offset from one (``g = 1 + stored``);
- the layers are two stacks: ``params["mamba"]`` ``[mamba layers, ...]``
  and ``params["attention"]`` ``[attention layers, ...]``, layer i of
  ``layer_types`` being the next of its kind;
- matrices are input-major: mamba ``w_in [d, I + (I + 2N)]`` and ``w_dt
  [d, H]`` (the published ``in_proj`` transposed, its columns ``z ++ u``
  and ``delta`` as two matrices),
  ``conv_w [I + 2N, W]``, ``conv_b [I + 2N]``, ``a_log, dt_bias, d_skip
  [H]``, ``ln_gate [I]``, ``w_out [I, d]``; attention ``wq [d, heads,
  hd]``, ``wk, wv [d, kv heads, hd]``, ``wo [heads, hd, d]``; both
  ``ln_mix`` and ``ln_mlp`` (the two pre-norms), ``w_ff_in [d, 2 F]``
  (``a ++ b``), ``w_ff_out [F, d]``; ``embed [V, d]``, read again as the
  head.

``cfg`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_HEAD_COLUMNS = 16384   # the head: vocabulary columns a block


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


def _swiglu_half(x, mixed, w, eps, residual):
    f = lambda name: w[name].astype(F32)
    x = x + residual * mixed
    a, b = jnp.split(_rms_norm(x, w["ln_mlp"], eps) @ f("w_ff_in"), 2,
                     axis=-1)
    return x + residual * ((jax.nn.silu(a) * b) @ f("w_ff_out"))


@functools.partial(jax.jit, static_argnames=("eps", "residual", "operands"))
def _mamba_layer(x, w, *, eps, residual, operands=None):
    """x [T, d] float32 of ONE sequence; ``w`` one Mamba layer -> (the
    block's output [T, d], the state after the last token [H, P, N]).
    ``operands``: a type the input projection's left operand is rounded
    to first (`first_state` says when; None: never)."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        t = x.shape[0]
        n_heads = w["a_log"].shape[0]
        inner = w["w_out"].shape[0]
        n_state = (w["conv_w"].shape[0] - inner) // 2
        h = _rms_norm(x, w["ln_mix"], eps)
        if operands is not None:
            h = h.astype(operands).astype(F32)
        z, u = jnp.split(h @ f("w_in"), [inner], axis=-1)
        delta = h @ f("w_dt")
        taps = f("conv_w")                                    # [C, W]
        width = taps.shape[1]
        before = jnp.concatenate(
            [jnp.zeros((width - 1, u.shape[1]), F32), u])
        u = jax.nn.silu(f("conv_b") + sum(before[i:i + t] * taps[:, i]
                                          for i in range(width)))
        xs, bm, cm = jnp.split(u, [inner, inner + n_state], axis=-1)
        xs = xs.reshape(t, n_heads, -1)
        dt = jax.nn.softplus(delta + f("dt_bias"))            # [T, H]
        decay = jnp.exp(dt * -jnp.exp(f("a_log")))

        def token(s, at):                       # s [H, P, N]
            x_t, b_t, c_t, dt_t, decay_t = at
            s = (decay_t[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
            return s, jnp.einsum("hpn,n->hp", s, c_t)

        state, y = jax.lax.scan(
            token, jnp.zeros((n_heads, xs.shape[-1], n_state), F32),
            (xs, bm, cm, dt, decay))
        y = y + f("d_skip")[:, None] * xs
        y = y.reshape(t, inner) * jax.nn.silu(z)
        mixed = _rms_norm(y, w["ln_gate"], eps) @ f("w_out")
        return _swiglu_half(x, mixed, w, eps, residual), state


@functools.partial(jax.jit,
                   static_argnames=("eps", "residual", "attn_scale"))
def _attention_layer(x, w, *, eps, residual, attn_scale):
    """x [T, d] float32 of ONE sequence; ``w`` one attention layer."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        t, d = x.shape
        _, n_heads, hd = w["wq"].shape
        kv_heads = w["wk"].shape[1]
        h = _rms_norm(x, w["ln_mix"], eps)
        q = (h @ f("wq").reshape(d, -1)).reshape(t, kv_heads, -1, hd)
        k = (h @ f("wk").reshape(d, -1)).reshape(t, kv_heads, hd)
        v = (h @ f("wv").reshape(d, -1)).reshape(t, kv_heads, hd)
        scores = jnp.einsum("qkgd,skd->kgqs", q, k) * attn_scale
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1),
                          v)
        mixed = attn.reshape(t, n_heads * hd) @ f("wo").reshape(-1, d)
        return _swiglu_half(x, mixed, w, eps, residual)


def hidden(params, tokens, cfg):
    """tokens [T] of one sequence -> the last block's output [T, d],
    before the final norm."""
    x = (jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
         * cfg["embedding_multiplier"])
    eps, residual = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    n_mamba = n_attention = 0
    for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]:
        if kind == "mamba":
            w = jax.tree.map(lambda a: a[n_mamba], params["mamba"])
            x, _ = _mamba_layer(x, w, eps=eps, residual=residual)
            n_mamba += 1
        else:
            w = jax.tree.map(lambda a: a[n_attention], params["attention"])
            x = _attention_layer(x, w, eps=eps, residual=residual,
                                 attn_scale=cfg["attention_multiplier"])
            n_attention += 1
    return x


def first_state(params, tokens, cfg):
    """tokens [T] of one sequence -> the FIRST layer's state after the
    last token, [H, P, N] float32 (``layer_types`` begins with a Mamba
    layer). Its inputs are the embedding's rows, which no earlier
    layer's rounding has touched; the blocks are PRE-norm, so the one
    product before the state, ``RMSNorm(x) W_in``, takes a float32 left
    operand that a system serving bf16 weights rounds to bf16 (the
    configuration's stated operand type): the state then differs by
    2e-3 whatever its own precision (the chip, PR 46). So HERE, and
    only here, the normed input is rounded to the weights' type before
    that product, as the served precision states; every sum, the
    convolution, the decay and the state stay float32, and what a
    system's state differs by is the precision of the state itself.
    `logits_at` rounds nothing."""
    if cfg["layer_types"][0] != "mamba":
        raise ValueError("the first layer is not a Mamba layer")
    x = (jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
         * cfg["embedding_multiplier"])
    w = jax.tree.map(lambda a: a[0], params["mamba"])
    return _mamba_layer(x, w, eps=cfg["rms_norm_eps"],
                        residual=cfg["residual_multiplier"],
                        operands=params["embed"].dtype)[1]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_out, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, ln_out, eps) @ rows.astype(F32).T


def logits_at(params, tokens, rows, cfg):
    """Float32 logits [len(rows), V] at the (sequence, position) pairs
    ``rows`` from a full causal forward pass over tokens [B, T], each
    sequence read up to its last row."""
    tokens = np.asarray(tokens)
    ends = {s: max(p for q, p in rows if q == s) + 1 for s, _ in rows}
    xs = {s: hidden(params, tokens[s, :n], cfg) for s, n in ends.items()}
    picked = jnp.stack([xs[s][p] for s, p in rows])
    embed = params["embed"]
    return jnp.concatenate(
        [_head(picked, params["ln_out"], embed[c:c + _HEAD_COLUMNS],
               eps=cfg["rms_norm_eps"])
         for c in range(0, embed.shape[0], _HEAD_COLUMNS)],
        axis=-1) / cfg["logits_scaling"]
