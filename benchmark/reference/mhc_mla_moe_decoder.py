"""The plain reference of the Xing4.0 family (``xing4_0``) as one chip's
share of an expert-parallel replica: the next-token forward pass in
straightforward float32 ``jax.numpy``.

Written from the model's ``config.json``, from "mHC: Manifold-
Constrained Hyper-Connections" (arXiv:2512.24880) over
"Hyper-Connections" (arXiv:2409.19606) for the residual, and from the
DeepSeek-V3 family's published equations for latent attention, YaRN and
the sigmoid router, as issue 54 sets them out; independent of
``ray_tpu/models/xing_mhc.py`` and ``ray_tpu/ops/``. The publisher's
modelling code is not on this machine: what the config does not fix is
ASSUMED, and marked so below.

Between layers a token carries ``X`` in R^{n x C}, ``n`` = ``hc_mult``.
``X_0`` is the token's embedding in every one of the n rows; after the
last layer the n rows are SUMMED, then the final RMSNorm and the untied
head (ASSUMED: both ends as the hyper-connections paper has them). Each
layer has two sub-layers F (attention; then the dense SwiGLU in the
first ``first_k_dense_replace`` layers, the expert layer after), each
with its own pre-norm gain g and its own ``Phi`` in R^{nC x (2n +
n^2)}, ``b_pre``, ``b_post`` in R^n, ``B_res`` in R^{n x n}, scalars
``a_pre``, ``a_post``, ``a_res``:

1. ``u = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)`` (ASSUMED: no
   gain of its own, one would fold into ``Phi``; the config's eps).
2. ``[p | q | r] = u Phi``; ``Hpre~ = a_pre p + b_pre``, ``Hpost~ =
   a_post q + b_post``, ``Hres~ = a_res mat(r) + B_res`` (row-major:
   ``r[i, j]`` mixes stream j into stream i).
3. ``H_pre = sigmoid(Hpre~)``; ``H_post = 2 sigmoid(Hpost~)``; ``H_res
   = SK(clip(Hres~, mhc_h_res_clamp_min, mhc_h_res_clamp_max))``: from
   ``M = exp(.)``, ``hc_sinkhorn_iters`` times every row divided by its
   sum, then every column by its sum (ASSUMED: ``hc_eps`` is added to
   each sum) — a Python loop, as written.
4. ``x = H_pre X`` in R^C; ``y = F(RMSNorm_g(x))``.
5. ``X' = H_res X + H_post^T y``.

*Attention*: ``q = W_uq RMSNorm(W_dq h)`` split ``nope | rope`` a head;
``[c | k_r] = W_dkv h``, ``c~ = RMSNorm(c)``, one rotary key for all
heads; ``[k_nope | v] = c~ W_ukv`` a head; causal softmax; ``W_o``.
Always EXPANDED (the system's decode step absorbs the up-projections).
Rotation is rotate-half over the rope part (ASSUMED: the published
checkpoints' interleaved layout is a fixed permutation of it) with
YaRN's frequencies: of the ``rope/2`` frequencies ``theta^(-2i/rope)``
those that turn more than ``beta_fast`` times in
``original_max_position_embeddings`` positions are kept, those that
turn less than ``beta_slow`` times are divided by ``factor``, a linear
ramp over the index blends between (the published
``yarn_find_correction_range``, floor and ceiling included); cos and
sin times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
the softmax scale ``qk^-1/2 x mscale(factor, mscale_all_dim)^2`` with
``mscale(s, m) = 0.1 m ln s + 1``.

*Experts*: ``s = sigmoid(n W_r)`` in float32; the
``num_experts_per_tok`` largest of ``s + b`` (one group); ``gate_e =
routed_scaling_factor * s_e / (sum_chosen s + 1e-20)``; ``y = sum
gate_e E_e(n) + E_shared(n)`` over the chosen experts THIS CHIP HOLDS
(`held_experts`: ``n_routed_experts`` of the file counts them, the
router's width is the published count): what the absent ones would add
is left out, as in the program. A dense Python loop over the held
experts.

No cache, no kernels, no sort; each bf16 matrix widened to float32 as
it is used, every product under
``jax.default_matmul_precision("highest")``.

It reads the SYSTEM's parameter tree; what is the system's convention
and not the published one is undone here: norm gains are offsets from
one; two stacks (``dense``, ``moe``); matrices input-major and split by
head (``w_dq [d, rq]``, ``w_uq [rq, H, qk]``, ``w_dkv [d, rkv + r]``,
``w_uk [rkv, H, nope]``, ``w_uv [rkv, H, v]``, ``w_o [H, v, d]``);
``mhc_phi [2, 2n + n^2, nC]`` is ``Phi`` TRANSPOSED, attention's then
the feed-forward's, its rows p, q, vec(r); ``mhc_alpha [2, 3]``;
``mhc_bias [2, 2n + n^2]`` = b_pre ++ b_post ++ vec(B_res); experts
``w_gate, w_up [held, d, f]``, ``w_down [held, f, d]``, ``ws_*`` the
shared one, ``router [d, E]``, ``router_bias [E]``.

**Routing near a tie**: as ``mla_moe_decoder.py``: `routed_logits_at`
takes the system's choices, reports how each sits against ITS OWN
boundary, then follows them.

``cfg`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_PAD = 256      # an expert's token count is padded up to a multiple


def held_experts(cfg: dict):
    """(first, count, the router's width): this chip's share."""
    count = cfg["n_routed_experts"]
    total = cfg.get("reduced", {}).get("n_routed_experts", {}).get("source",
                                                                   count)
    chip = cfg.get("expert_parallel", {}).get("this_chip", 0)
    return chip * count, count, total


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


# YaRN ---------------------------------------------------------------------

def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim // 2`` inverse frequencies (float64)."""
    r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    sc = cfg.get("rope_scaling")
    if not sc:
        return plain
    if sc.get("type", sc.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {sc}")

    def index_of(turns):
        return (r * math.log(sc["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(index_of(sc["beta_fast"])), 0)
    high = min(math.ceil(index_of(sc["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    return plain / sc["factor"] * ramp + plain * (1.0 - ramp)


def yarn_scales(cfg: dict):
    """(what cos and sin are multiplied by, what the softmax scale is)."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    sc = cfg.get("rope_scaling")
    if not sc:
        return 1.0, qk ** -0.5
    all_dim = sc.get("mscale_all_dim", 0)
    rotation = (_mscale(sc["factor"], sc.get("mscale", 1))
                / _mscale(sc["factor"], all_dim))
    return rotation, qk ** -0.5 * (_mscale(sc["factor"], all_dim) ** 2
                                   if all_dim else 1.0)


def _rotate_half(x, inv_freq, scale):
    """x [T, H, r], row t at position t."""
    r = x.shape[-1]
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1) * scale
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1) * scale
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


# The residual -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "iters", "hc_eps", "lo",
                                             "hi", "eps"))
def _maps(streams, phi_t, alpha, bias, *, n, iters, hc_eps, lo, hi, eps):
    """streams [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n,
    n]), steps 1 to 3."""
    with jax.default_matmul_precision("highest"):
        flat = streams.reshape(streams.shape[0], -1)
        u = flat / jnp.sqrt(jnp.mean(jnp.square(flat), axis=-1,
                                     keepdims=True) + eps)
        raw = u @ phi_t.astype(F32).T
    p, q, r = raw[:, :n], raw[:, n:2 * n], raw[:, 2 * n:]
    h_pre = jax.nn.sigmoid(alpha[0] * p + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * q + bias[n:2 * n])
    m = jnp.exp(jnp.clip((alpha[2] * r + bias[2 * n:]).reshape(-1, n, n),
                         lo, hi))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)
    return h_pre, h_post, m


def _mhc_keys(cfg: dict) -> dict:
    return dict(n=cfg["hc_mult"], iters=cfg["hc_sinkhorn_iters"],
                hc_eps=cfg["hc_eps"], lo=float(cfg["mhc_h_res_clamp_min"]),
                hi=float(cfg["mhc_h_res_clamp_max"]),
                eps=cfg["rms_norm_eps"])


@jax.jit
def _collapse(streams, h_pre):
    return jnp.einsum("tn,tnc->tc", h_pre, streams)


@jax.jit
def _write_back(streams, y, h_post, h_res):
    return (jnp.einsum("tij,tjc->tic", h_res, streams)
            + h_post[:, :, None] * y[:, None, :])


# The sub-layers -----------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "nope", "rkv", "rotation",
                                             "scale"))
def _attention(x, w, inv_freq, *, eps, nope, rkv, rotation, scale):
    """x [T, C] float32 of ONE sequence (the streams' weighted sum) ->
    MLA(RMSNorm_g(x)) [T, C]."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        h = _rms_norm(x, w["ln_attn"], eps)
        c_q = _rms_norm(h @ f("w_dq"), w["ln_q"], eps)
        q = jnp.einsum("tr,rhk->thk", c_q, f("w_uq"))
        q_nope = q[..., :nope]
        q_rope = _rotate_half(q[..., nope:], inv_freq, rotation)
        ckr = h @ f("w_dkv")
        c_kv = _rms_norm(ckr[:, :rkv], w["ln_kv"], eps)
        k_rope = _rotate_half(ckr[:, None, rkv:], inv_freq, rotation)[:, 0]
        k_nope = jnp.einsum("tr,rhk->thk", c_kv, f("w_uk"))
        v = jnp.einsum("tr,rhv->thv", c_kv, f("w_uv"))
        t = x.shape[0]
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

        def head(of):           # one head at a time: [T, T] scores, not H
            qn, qr, kn, vh = of
            scores = (qn @ kn.T + qr @ k_rope.T) * scale
            scores = jnp.where(causal, scores, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        o = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in
                                    (q_nope, q_rope, k_nope, v)))
        o = o.transpose(1, 0, 2)                               # [T, H, v]
        return jnp.einsum("qhv,hvd->qd", o, f("w_o"))


@functools.partial(jax.jit, static_argnames=("eps",))
def _second_norm(x, gain, *, eps):
    return _rms_norm(x, gain, eps)


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


def expert_layer(n, w, chosen, cfg):
    """n [N, d] (every sequence's tokens, normed) -> (y [N, d], differs,
    excess, lead), each [N]; ``w`` is one expert layer's weights, of
    which the experts are this chip's alone."""
    first, count, _ = held_experts(cfg)
    s, use, differs, excess, lead = _scores(
        n, w["router"], w["router_bias"], chosen,
        k=cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, use, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * cfg["routed_scaling_factor"]
    y = _swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"])
    use_host, gates_host = np.asarray(use), np.asarray(gates)
    for e in range(count):
        token, place = np.nonzero(use_host == first + e)
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


def _streams_in(params, tokens, cfg):
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg["hc_mult"],
                                            x.shape[1]))


def _hidden(params, sequences, cfg, chosen):
    """sequences: a list of token arrays [T_i] -> the streams' SUM after
    the last layer of each [T_i, C], before the final norm, and the
    routing report {differs, excess, lead}: each [expert layers, total
    tokens], the sequences' tokens side by side."""
    eps = cfg["rms_norm_eps"]
    n_dense = cfg["first_k_dense_replace"]
    rotation, scale = yarn_scales(cfg)
    inv_freq = jnp.asarray(yarn_inv_freq(cfg), F32)
    attend = functools.partial(
        _attention, inv_freq=inv_freq, eps=eps, nope=cfg["qk_nope_head_dim"],
        rkv=cfg["kv_lora_rank"], rotation=rotation, scale=scale)
    keys = _mhc_keys(cfg)
    xs = [_streams_in(params, jnp.asarray(t), cfg) for t in sequences]
    cuts = np.cumsum([len(t) for t in sequences])[:-1]
    report = []
    for i in range(cfg["num_hidden_layers"]):
        stack, j = (("dense", i) if i < n_dense else ("moe", i - n_dense))
        w = jax.tree.map(lambda a: a[j], params[stack])
        maps = lambda x, sub: _maps(x, w["mhc_phi"][sub], w["mhc_alpha"][sub],
                                    w["mhc_bias"][sub], **keys)
        # Attention, a sequence at a time.
        after = []
        for x in xs:
            h_pre, h_post, h_res = maps(x, 0)
            y = attend(_collapse(x, h_pre), w)
            after.append(_write_back(x, y, h_post, h_res))
        # The feed-forward, every sequence's tokens side by side.
        x = jnp.concatenate(after)
        h_pre, h_post, h_res = maps(x, 1)
        n = _second_norm(_collapse(x, h_pre), w["ln_mlp"], eps=eps)
        if stack == "dense":
            y = _swiglu(n, w["w_gate"], w["w_up"], w["w_down"])
        else:
            y, *about = expert_layer(n, w, chosen[j], cfg)
            report.append([np.asarray(a) for a in about])
        xs = jnp.split(_write_back(x, y, h_post, h_res), cuts)
    differs, excess, lead = (np.stack(col) for col in zip(*report))
    return ([jnp.sum(x, axis=1) for x in xs],
            {"differs": differs, "excess": excess, "lead": lead})


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
        params, [tokens[i, :n] for i, n in enumerate(ends)], cfg,
        jnp.asarray(np.concatenate(
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


def first_maps(params, tokens, cfg):
    """tokens [T] -> the FIRST sub-layer's maps of every token, [T, 2n +
    n^2] float32: H_pre ++ H_post ++ vec(H_res) of layer 0's attention.
    Their input is the embedding's rows, which no earlier layer's
    rounding has touched, so a system's maps differ from these by the
    precision of the maps themselves (the norm, the product with
    ``Phi``, the Sinkhorn passes)."""
    w = jax.tree.map(lambda a: a[0], params["dense"])
    h_pre, h_post, h_res = _maps(
        _streams_in(params, jnp.asarray(tokens), cfg), w["mhc_phi"][0],
        w["mhc_alpha"][0], w["mhc_bias"][0], **_mhc_keys(cfg))
    return jnp.concatenate([h_pre, h_post,
                            h_res.reshape(h_res.shape[0], -1)], axis=-1)
