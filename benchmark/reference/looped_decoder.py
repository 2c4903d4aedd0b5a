"""The plain reference of a LOOPED decoder (the Ouro family,
arXiv:2510.25741: "Scaling Latent Reasoning via Looped Language
Models"): one stack of sandwich-norm blocks that every token crosses
``total_ut_steps`` times with the same weights, an exit gate read after
every pass. Straightforward float32 ``jax.numpy``, written from the
layer equations of issue 64 and independent of ``ray_tpu/models/``: it
imports nothing from there.

    block_l(x):  a = Attn_l(RMS(x; g1_l));      x = x + RMS(a; g2_l)
                 m = SwiGLU_l(RMS(x; g3_l));    x = x + RMS(m; g4_l)
    Attn:        q, k, v = h Wq, h Wk, h Wv (no bias); rotate-half RoPE
                 on q and k at the token's position, the same in every
                 pass; causal softmax(q k^T / sqrt(head_dim)) v; Wo
    SwiGLU:      (silu(h Wg) * (h Wu)) Wd
    model:       h_0 = E[tokens]
                 for u = 1 .. T, THE SAME blocks:
                     h_u = RMS(block_L( .. block_1(h_{u-1}) ..); g_out)
                     lambda_u = sigmoid(h_u . w_gate + b_gate)
                 logits = h_T W_head

RMS(z; g) = z / sqrt(mean(z^2) + eps) * g. With no cache a pass's
attention reads the keys and values THAT PASS computed for the earlier
positions: what a program keeps as one cache entry a (pass, layer).

No kernels, no cache, no scan: a Python loop over passes and layers,
one sequence at a time and the head over blocks of rows (so that it
fits beside an engine that fills the chip), each layer's bf16 weights
widened to float32 on the way in, every product under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes).

It reads the SYSTEM's parameter tree, so both sides hold the same
numbers; two of that tree's conventions are undone here: norm gains
are stored as an offset from one (``g = 1 + stored``); ``wq [L, H x
hd, D]`` and ``wk, wv [L, KH x hd, D]`` are stored output-major (as a
checkpoint's ``q_proj.weight`` is), every other matrix input-major
(``wo [L, H x hd, D]``, ``w_gate, w_up [L, D, F]``, ``w_down [L, F,
D]``, ``head [D, V]``), a row's heads side by side.
``c`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_ROWS = 32      # rows of logits a call of the head makes


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


def _rotate_half(x, theta):
    """x [T, H, hd] at positions 0 .. T - 1."""
    t, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = jnp.arange(t, dtype=F32)[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@functools.partial(jax.jit, static_argnames=("eps", "theta", "head_dim"))
def _block(x, w, *, eps, theta, head_dim):
    """One application of one block to x [T, D] float32."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        wide = {k: v.astype(F32) for k, v in w.items()}
        n = _rms_norm(x, w["ln_attn"], eps)
        heads = lambda y: y.reshape(t, -1, head_dim)  # noqa: E731
        q = _rotate_half(heads(n @ wide["wq"].T), theta)
        k = _rotate_half(heads(n @ wide["wk"].T), theta)
        v = heads(n @ wide["wv"].T)
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhk,shk->hqs", q, k) / jnp.sqrt(
            jnp.asarray(q.shape[-1], F32))
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v)
        a = attn.reshape(t, -1) @ wide["wo"]
        x = x + _rms_norm(a, w["ln_attn_out"], eps)
        n = _rms_norm(x, w["ln_mlp"], eps)
        m = (jax.nn.silu(n @ wide["w_gate"]) * (n @ wide["w_up"])
             ) @ wide["w_down"]
        return x + _rms_norm(m, w["ln_mlp_out"], eps)


def passes(params, tokens, c):
    """tokens [T] of ONE sequence -> [h_1, .., h_T], each [T, D]: the
    normed state after every pass."""
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
    out = []
    for _ in range(c["total_ut_steps"]):
        for i in range(c["num_hidden_layers"]):
            layer = jax.tree.map(lambda a: a[i], params["blocks"])
            x = _block(x, layer, eps=eps, theta=theta, head_dim=c["head_dim"])
        x = _rms_norm(x, params["ln_out"], eps)
        out.append(x)
    return out


@jax.jit
def _head(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def _gates(states, params):
    gate = params["exit_gate"]
    return jnp.stack([jax.nn.sigmoid(
        jnp.sum(h * gate["w"].astype(F32), axis=-1) + gate["b"])
        for h in states])


def _by_sequence(rows):
    """rows [(sequence, position)] -> {sequence: [(index in rows,
    position)]}."""
    out = {}
    for i, (b, t) in enumerate(rows):
        out.setdefault(int(b), []).append((i, int(t)))
    return out


def both_at(params, tokens, rows, c):
    """(float32 logits [len(rows), V], lambda_u [passes, len(rows)]) at
    the (sequence, position) pairs ``rows``, from ONE full causal
    forward pass a sequence over tokens [B, T]."""
    tokens = np.asarray(tokens)
    logits = [None] * len(rows)
    gates = np.zeros((c["total_ut_steps"], len(rows)), np.float32)
    for b, picked in _by_sequence(rows).items():
        idx, at = zip(*picked)
        # Causal: nothing past the last row asked for is read.
        states = passes(params, tokens[b, :max(at) + 1], c)
        gates[:, list(idx)] = np.asarray(_gates(states, params))[:, list(at)]
        last = states[-1][jnp.asarray(at)]
        for lo in range(0, len(at), HEAD_ROWS):
            block = np.asarray(_head(last[lo:lo + HEAD_ROWS],
                                     params["head"]))
            for i, row in zip(idx[lo:lo + HEAD_ROWS], block):
                logits[i] = row
    return np.stack(logits), gates


def logits_at(params, tokens, rows, c):
    """Float32 logits [len(rows), V] at ``rows`` (``early_exit_threshold``
    1: every token's logits are the last pass's)."""
    return both_at(params, tokens, rows, c)[0]


def gates_at(params, tokens, rows, c):
    """lambda_u [passes, len(rows)] float32: the exit gate after every
    pass at ``rows``."""
    return both_at(params, tokens, rows, c)[1]


def exit_pass(gates, threshold: float):
    """The pass (from 1) after which a token would leave: the first u
    whose accumulated exit probability reaches ``threshold``, with p_u
    = lambda_u prod_{j<u}(1 - lambda_j) for u < T and p_T the rest. At
    the published threshold 1.0 that is T for every token."""
    gates = np.asarray(gates, np.float64)
    stay = np.cumprod(1.0 - gates[:-1], axis=0)
    p = np.concatenate([gates[:1], gates[1:-1] * stay[:-1], stay[-1:]])
    reached = np.cumsum(p, axis=0) >= threshold
    reached[-1] = True
    return reached.argmax(axis=0) + 1
