"""The plain reference of the Olmo-Hybrid family (``olmo_hybrid``): the
next-token forward pass in straightforward float32 ``jax.numpy``.

Written from the published description (the model's ``config.json``;
the ``linear_*`` keys are those of the published gated delta-rule
layer, HF ``Qwen3NextGatedDeltaNet`` / FLA ``GatedDeltaNet``; the layer
equations as issue 33 sets them out), independent of
``ray_tpu/models/olmo_hybrid.py`` and ``ray_tpu/ops/gated_delta.py``.
``layer_types`` says which layers are linear and which full. ``x`` is a
block's input, d the hidden size:

    h   = x + RMSNorm(Mixer(x))                      (post-norm blocks)
    out = h + RMSNorm(W_down (silu(W_gate h) * (W_up h)))
    logits = RMSNorm(last out) W_head                (untied head)

*Linear layer*, per head h of H, keys of dk, values of dv:
``q~ = x W_q``, ``k~ = x W_k``, ``v~ = x W_v``. Each channel of the
three passes a causal depthwise convolution over time of width W
(``y_t = sum_{i<W} c_i u_{t-W+1+i}``, zeros before the sequence), then
SiLU. ``q_t = q^_t / ||q^_t||_2 * dk^-1/2``, ``k_t = k^_t / ||k^_t||_2`` (the
published layer's ``l2norm``: 1e-6 added under the root).
``beta_t = 2 sigmoid(x W_b)`` (the 2 where ``linear_allow_neg_eigval``),
``g_t = -exp(A_log) softplus(x W_a + dt_bias)``, ``alpha_t = exp(g_t)``.
The state S in R^{dv x dk}, ``S_0 = 0``:

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t

ONE TOKEN AT A TIME, exactly as written (never the chunked form the
system's prefill runs). ``y_t = RMSNorm_dv(o_t; w_o_norm) * silu(x W_g)``
per head, then ``concat_h(y_t) W_o``.

*Full layer*: ``q, k, v = x W_q, x W_k, x W_v``; ``q <- RMSNorm_d(q)``,
``k <- RMSNorm_d(k)`` over the whole projection before the split into
heads; no rotary embedding; causal softmax(q k^T / sqrt(head_dim)) v;
``W_o``.

No cache, no kernels, no chunks: a Python loop over layers, each matrix
widened from bf16 to float32 as it is used, every product under
``jax.default_matmul_precision("highest")``. So that 2,048 tokens of 16
layers at the published widths fit beside a serving engine, the
attention scores are made a block of query rows at a time and the head
a block of vocabulary columns at a time; neither changes a number.

It reads the SYSTEM's parameter tree; what is the system's convention
and not the published one is undone here:

- norm gains are stored as an offset from one (``g = 1 + stored``);
- the layers are two stacks: ``params["linear"]`` ``[linear layers,
  ...]`` and ``params["full"]`` ``[full layers, ...]``, layer i of
  ``layer_types`` being the next of its kind;
- matrices are input-major and split by head: linear ``w_q, w_k
  [d, H, dk]``, ``w_v, w_g [d, H, dv]``, ``w_a, w_b [d, H]``, ``w_o
  [H, dv, d]``, ``conv_w [H (2 dk + dv), W]`` over the channels
  ``q~ ++ k~ ++ v~``, ``ln_o [dv]`` (the published ``o_norm``); full
  ``wq, wk, wv [d, H, hd]``, ``wo [H, hd, d]``, ``ln_q, ln_k [d]``; both
  ``ln_mix`` and ``ln_mlp`` (the two post-norms), ``w_gate, w_up
  [d, F]``, ``w_down [F, d]``; ``embed [V, d]``, ``lm_head [d, V]``.

``cfg`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_QUERY_ROWS = 512       # attention scores: query rows a block
_HEAD_COLUMNS = 16384   # the head: vocabulary columns a block


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


def _unit(x):
    """x / ||x||_2 per head, with the published layer's guard: 1e-6
    under the root (``l2norm`` of the published implementation)."""
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-6)


def _swiglu_half(x, mixed, w, eps):
    """``h = x + RMSNorm(mixed)``, then ``h + RMSNorm(SwiGLU(h))``."""
    f = lambda name: w[name].astype(F32)
    h = x + _rms_norm(mixed, w["ln_mix"], eps)
    y = (jax.nn.silu(h @ f("w_gate")) * (h @ f("w_up"))) @ f("w_down")
    return h + _rms_norm(y, w["ln_mlp"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "neg_eigval"))
def _linear_layer(x, w, *, eps, neg_eigval):
    """x [T, d] float32 of ONE sequence; ``w`` one linear layer ->
    (the block's output [T, d], the state after the last token
    [H, dv, dk])."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        t, d = x.shape
        _, n_heads, dk = w["w_q"].shape
        dv = w["w_v"].shape[-1]
        u = jnp.concatenate([x @ f(name).reshape(d, -1)
                             for name in ("w_q", "w_k", "w_v")], axis=-1)
        taps = f("conv_w")                                    # [C, W]
        width = taps.shape[1]
        before = jnp.concatenate(
            [jnp.zeros((width - 1, u.shape[1]), F32), u])
        y = jax.nn.silu(sum(before[i:i + t] * taps[:, i]
                            for i in range(width)))
        q, k, v = jnp.split(y, [n_heads * dk, 2 * n_heads * dk], axis=-1)
        q = q.reshape(t, n_heads, dk)
        k = k.reshape(t, n_heads, dk)
        v = v.reshape(t, n_heads, dv)
        q = _unit(q) / jnp.sqrt(jnp.asarray(dk, F32))
        k = _unit(k)
        beta = jax.nn.sigmoid(x @ f("w_b")) * (2.0 if neg_eigval else 1.0)
        alpha = jnp.exp(-jnp.exp(f("a_log"))
                        * jax.nn.softplus(x @ f("w_a") + f("dt_bias")))

        def token(s, at):                       # s [H, dv, dk]
            q_t, k_t, v_t, a_t, b_t = at
            s = a_t[:, None, None] * s
            s = s + (b_t[:, None] * (v_t - jnp.einsum("hvk,hk->hv", s, k_t))
                     )[:, :, None] * k_t[:, None, :]
            return s, jnp.einsum("hvk,hk->hv", s, q_t)

        state, o = jax.lax.scan(token, jnp.zeros((n_heads, dv, dk), F32),
                                (q, k, v, alpha, beta))
        gate = jax.nn.silu(x @ f("w_g").reshape(d, -1)).reshape(o.shape)
        y = _rms_norm(o, w["ln_o"], eps) * gate
        mixed = y.reshape(t, -1) @ f("w_o").reshape(-1, d)
        return _swiglu_half(x, mixed, w, eps), state


@functools.partial(jax.jit, static_argnames=("eps",))
def _full_layer(x, w, *, eps):
    """x [T, d] float32 of ONE sequence; ``w`` one full layer."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        t, d = x.shape
        _, n_heads, hd = w["wq"].shape
        q = _rms_norm(x @ f("wq").reshape(d, d), w["ln_q"], eps)
        k = _rms_norm(x @ f("wk").reshape(d, d), w["ln_k"], eps)
        q, k, v = (a.reshape(t, n_heads, hd)
                   for a in (q, k, x @ f("wv").reshape(d, d)))
        blocks = []
        for start in range(0, t, _QUERY_ROWS):
            rows = slice(start, min(start + _QUERY_ROWS, t))
            scores = jnp.einsum("qhk,shk->hqs", q[rows], k) / jnp.sqrt(
                jnp.asarray(hd, F32))
            causal = (jnp.arange(t)[rows, None] >= jnp.arange(t)[None, :])
            scores = jnp.where(causal[None], scores, -jnp.inf)
            blocks.append(jnp.einsum(
                "hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v))
        attn = jnp.concatenate(blocks)
        mixed = attn.reshape(t, d) @ f("wo").reshape(d, d)
        return _swiglu_half(x, mixed, w, eps)


def hidden(params, tokens, cfg):
    """tokens [T] of one sequence -> the last block's output [T, d],
    before the final norm."""
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
    eps = cfg["rms_norm_eps"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    n_linear = n_full = 0
    for kind in kinds:
        if kind == "linear_attention":
            w = jax.tree.map(lambda a: a[n_linear], params["linear"])
            x, _ = _linear_layer(x, w, eps=eps,
                                 neg_eigval=cfg["linear_allow_neg_eigval"])
            n_linear += 1
        else:
            w = jax.tree.map(lambda a: a[n_full], params["full"])
            x = _full_layer(x, w, eps=eps)
            n_full += 1
    return x


def first_state(params, tokens, cfg):
    """tokens [T] of one sequence -> the FIRST layer's state after the
    last token, [H, dv, dk] float32 (``layer_types`` begins with a
    linear layer). Its inputs are the embedding's rows, which no
    earlier layer's rounding has touched: what a system's state there
    differs by is the precision of the state itself."""
    if cfg["layer_types"][0] != "linear_attention":
        raise ValueError("the first layer is not a linear one")
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
    w = jax.tree.map(lambda a: a[0], params["linear"])
    return _linear_layer(x, w, eps=cfg["rms_norm_eps"],
                         neg_eigval=cfg["linear_allow_neg_eigval"])[1]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_out, columns, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, ln_out, eps) @ columns.astype(F32)


def logits_at(params, tokens, rows, cfg):
    """Float32 logits [len(rows), V] at the (sequence, position) pairs
    ``rows`` from a full causal forward pass over tokens [B, T], each
    sequence read up to its last row."""
    tokens = np.asarray(tokens)
    ends = {s: max(p for q, p in rows if q == s) + 1 for s, _ in rows}
    xs = {s: hidden(params, tokens[s, :n], cfg) for s, n in ends.items()}
    picked = jnp.stack([xs[s][p] for s, p in rows])
    head = params["lm_head"]
    return jnp.concatenate(
        [_head(picked, params["ln_out"], head[:, c:c + _HEAD_COLUMNS],
               eps=cfg["rms_norm_eps"])
         for c in range(0, head.shape[1], _HEAD_COLUMNS)], axis=-1)
