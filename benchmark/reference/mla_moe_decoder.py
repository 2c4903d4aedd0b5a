"""The plain reference of the GLM-4.7-Flash family (``glm4_moe_lite``):
the next-token forward pass in straightforward float32 ``jax.numpy``.

Written from the published description (the model's ``config.json`` and
its layer equations as issue 29 sets them out), independent of
``ray_tpu/models/glm_moe_lite.py``. Pre-norm residual blocks, RMSNorm
(eps from the file), a final norm, an untied head; d the hidden size, H
heads:

    c_q = RMSNorm(x W_dq);  q = c_q W_uq -> H x [nope | rope], RoPE on
    the rope part (rotate-half over all its columns, theta from the file)
    [c | k_r] = x W_dkv;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r), ONE
    for all heads;  [k_nope_h | v_h] = c_kv W_ukv
    score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(nope + rope)
    causal softmax, o_h = sum p v_h, out = concat_h(o_h) W_o

    layer 0 .. first_k_dense_replace - 1: a dense SwiGLU
    after: s = sigmoid(x W_r) in float32; the num_experts_per_tok largest
    of s + b are chosen (one group, so no group limit);
    g = routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-20);
    y = sum_e g_e E_e(x) + E_shared(x), E(x) = W_down(silu(W_gate x) * W_up x)

Always the EXPANDED attention (the system's decode step absorbs the
up-projections and attends over the latent), no cache, no kernels, no
sort: a Python loop over layers and, in an expert layer, over experts,
each applied to the tokens that chose it (found by a plain comparison;
their number padded up to a multiple of 256 with weight-zero repeats of
token 0 so that a handful of shapes compile), its bf16 matrices widened
to float32 one expert at a time. Every matrix product runs under
``jax.default_matmul_precision("highest")``. The multi-token-prediction
block is not part of the next-token forward pass and is not here.

It reads the SYSTEM's parameter tree; what is the system's convention
and not the published one is undone here:

- norm gains are stored as an offset from one (``g = 1 + stored``);
- the leading dense layers and the expert layers are two stacks,
  ``params["dense"]`` and ``params["moe"]``, each ``[layers, ...]``;
- matrices are stored input-major and split by head: ``w_dq [d, rq]``,
  ``w_uq [rq, H, nope + rope]``, ``w_dkv [d, rkv + rope]``, ``w_o
  [H, v, d]``; the published ``kv_b_proj`` is kept as its key half
  ``w_uk [rkv, H, nope]`` and its value half ``w_uv [rkv, H, v]``;
- experts: ``w_gate, w_up [E, d, f]``, ``w_down [E, f, d]``; the shared
  expert ``ws_*``; ``router [d, E]``, ``router_bias [E]`` (the
  published ``e_score_correction_bias``); ``embed [V, d]``,
  ``lm_head [d, V]``.

**Routing near a tie.** In bf16 a token's set of experts flips where
the k-th and the next ``s + b`` nearly tie, and a flipped expert moves
that row's logits by 5 to 15 %. `routed_logits_at` therefore takes the
system's choices: per expert layer it computes its OWN scores from its
own hidden state, reports how each system choice sits against its own
boundary, and then follows the system's choice (weighted by its own
unbiased scores), so that the logits compare like with like on every
row. ``benchmark/drivers/serve_routed.py`` holds the limits.

``cfg`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_PAD = 256      # an expert's token count is padded up to a multiple


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


def _rotate_half(x, theta):
    """x [T, H, r], row t at position t."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@functools.partial(jax.jit, static_argnames=("eps", "theta", "nope", "rkv"))
def _attention(x, w, *, eps, theta, nope, rkv):
    """x [T, d] float32 of ONE sequence -> x + MLA(RMSNorm(x)), and the
    second norm's output for the layer's feed-forward half."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        n = _rms_norm(x, w["ln_attn"], eps)
        c_q = _rms_norm(n @ f("w_dq"), w["ln_q"], eps)
        q = jnp.einsum("tr,rhk->thk", c_q, f("w_uq"))
        q_nope, q_rope = q[..., :nope], _rotate_half(q[..., nope:], theta)
        ckr = n @ f("w_dkv")
        c_kv = _rms_norm(ckr[:, :rkv], w["ln_kv"], eps)
        k_rope = _rotate_half(ckr[:, None, rkv:], theta)[:, 0]     # [T, r]
        k_nope = jnp.einsum("tr,rhk->thk", c_kv, f("w_uk"))
        v = jnp.einsum("tr,rhv->thv", c_kv, f("w_uv"))
        scores = (jnp.einsum("qhk,shk->hqs", q_nope, k_nope)
                  + jnp.einsum("qhr,sr->hqs", q_rope, k_rope))
        scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], F32))
        t = x.shape[0]
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(scores, axis=-1), v)
        x = x + jnp.einsum("qhv,hvd->qd", o, f("w_o"))
        return x, _rms_norm(x, w["ln_mlp"], eps)


@jax.jit
def _swiglu(n, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        gate, up = n @ w_gate.astype(F32), n @ w_up.astype(F32)
        return (jax.nn.silu(gate) * up) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("k",))
def _scores(n, router, bias, chosen, *, k):
    """n [N, d] -> the router's unbiased scores s [N, E], the experts to
    use [N, k] (the reference's own where ``chosen`` is -1), and, of
    each row against the reference's OWN boundary: whether the sets
    differ, how far the lowest score + bias of the set used lies under
    the reference's k-th, and the k-th's lead over the next — the last
    two as shares of the row's spread of score + bias."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(n @ router.astype(F32))
    biased = s + bias.astype(F32)
    order = jnp.argsort(-biased, axis=-1)
    own = order[:, :k]
    use = jnp.where(chosen[:, :1] < 0, own, chosen)
    ranked = jnp.take_along_axis(biased, order, axis=-1)
    kth, following = ranked[:, k - 1], ranked[:, k]
    spread = ranked[:, 0] - ranked[:, -1]
    lowest = jnp.min(jnp.take_along_axis(biased, use, axis=-1), axis=-1)
    differs = jnp.any(jnp.sort(use, -1) != jnp.sort(own, -1), axis=-1)
    return (s, use, differs, jnp.maximum(kth - lowest, 0.0) / spread,
            (kth - following) / spread)


def _expert_layer(n, w, chosen, cfg):
    """n [N, d] (every sequence's tokens) -> (y [N, d], differs,
    excess, lead), each [N]; ``w`` is one expert layer's weights."""
    k = cfg["num_experts_per_tok"]
    s, use, differs, excess, lead = _scores(
        n, w["router"], w["router_bias"], chosen, k=k)
    gates = jnp.take_along_axis(s, use, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * cfg["routed_scaling_factor"]
    y = _swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"])
    use_host, gates_host = np.asarray(use), np.asarray(gates)
    for e in range(cfg["n_routed_experts"]):
        token, place = np.nonzero(use_host == e)
        if not len(token):
            continue
        pad = -len(token) % _PAD
        weight = np.concatenate([gates_host[token, place],
                                 np.zeros(pad, np.float32)])
        token = np.concatenate([token, np.zeros(pad, token.dtype)])
        out = _swiglu(n[token], w["w_gate"][e], w["w_up"][e],
                      w["w_down"][e])
        y = y.at[token].add(out * weight[:, None])
    return y, differs, excess, lead


def _hidden(params, sequences, cfg, chosen):
    """sequences: a list of token arrays [T_i] -> the last block's
    output of each, before the final norm, and the routing report
    {differs, excess, lead}: each [expert layers, total tokens], the
    sequences' tokens side by side."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    nope, rkv = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    n_dense = cfg["first_k_dense_replace"]
    xs = [jnp.take(params["embed"], t, axis=0).astype(F32)
          for t in sequences]
    cuts = np.cumsum([len(t) for t in sequences])[:-1]
    report = []
    for i in range(cfg["num_hidden_layers"]):
        stack, j = (("dense", i) if i < n_dense else ("moe", i - n_dense))
        w = jax.tree.map(lambda a: a[j], params[stack])
        halves = [_attention(x, w, eps=eps, theta=theta, nope=nope, rkv=rkv)
                  for x in xs]
        n = jnp.concatenate([h[1] for h in halves])
        if stack == "dense":
            y = _swiglu(n, w["w_gate"], w["w_up"], w["w_down"])
        else:
            y, *about = _expert_layer(n, w, chosen[j], cfg)
            report.append([np.asarray(a) for a in about])
        xs = [h[0] + part for h, part in zip(halves, jnp.split(y, cuts))]
    differs, excess, lead = (np.stack(col) for col in zip(*report))
    return xs, {"differs": differs, "excess": excess, "lead": lead}


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_out, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, ln_out, eps) @ lm_head.astype(F32)


def routed_logits_at(params, tokens, rows, cfg, chosen=None):
    """Float32 logits [len(rows), V] at the (sequence, position) pairs
    ``rows`` from a full causal forward pass over tokens [B, T], each
    sequence read up to its last row; and the routing report.

    ``chosen`` [expert layers, B, T, k]: the experts the SYSTEM chose
    (-1: none known, the reference uses its own). The report's arrays
    are [expert layers, B, T] (zero past a sequence's last row):
    ``differs``, ``excess`` and ``lead`` as `_scores` defines them."""
    tokens = np.asarray(tokens)
    b, t = tokens.shape
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    k = cfg["num_experts_per_tok"]
    if chosen is None:
        chosen = np.full((n_moe, b, t, k), -1, np.int32)
    chosen = np.asarray(chosen)
    ends = [max(p for s, p in rows if s == i) + 1 for i in range(b)]
    xs, flat = _hidden(
        params, [jnp.asarray(tokens[i, :n]) for i, n in enumerate(ends)],
        cfg, jnp.asarray(np.concatenate(
            [chosen[:, i, :n] for i, n in enumerate(ends)], axis=1)))
    report = {}
    for name, a in flat.items():
        full = np.zeros((n_moe, b, t), a.dtype)
        for i, part in enumerate(np.split(a, np.cumsum(ends)[:-1], axis=1)):
            full[:, i, :ends[i]] = part
        report[name] = full
    picked = jnp.stack([xs[s][p] for s, p in rows])
    return _head(picked, params["ln_out"], params["lm_head"],
                 eps=cfg["rms_norm_eps"]), report


def logits_at(params, tokens, rows, cfg):
    """As the dense families' reference: the reference's own routing."""
    return routed_logits_at(params, tokens, rows, cfg)[0]
