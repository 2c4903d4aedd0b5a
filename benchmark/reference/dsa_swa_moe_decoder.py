"""The plain reference of the dots3-note family (``dots3_note``) AS ONE
CHIP'S SHARE of an expert-parallel replica: the next-token forward pass
in straightforward float32 ``jax.numpy``.

Written from the layer equations of issue 42 (every shape from the
model's public ``config.json``), independent of ``ray_tpu/models/`` and
of ``ray_tpu/ops/``. d the hidden size; pre-norm residual blocks,
RMSNorm with weight (eps from the file), a final norm, an untied head.
For token t, ``h = RMSNorm(x_t)``; ``rho_q = sqrt(d / r_q)``, ``rho_kv
= sqrt(d / r_kv)``.

*Full layer* (``layer_types[i] == "full_attention"``; H heads, the
un-prefixed keys):

1. ``c_q = rho_q RMSNorm(h W_qa)``; ``q_j = c_q W_qb,j = [q_n ; q_r]``,
   RoPE (rotate-half, ``rope_theta``) on ``q_r``.
2. ``[c_kv ; k_r] = h W_kva``; ``c_kv <- rho_kv RMSNorm(c_kv)``; ``k_r
   <- RoPE(k_r)``, one for all heads.
3. ``[k_n,j ; v_j] = c_kv W_kvb,j``.
4. The indexer (``index_n_heads`` heads of ``index_head_dim``, float32):
   ``qI_i = c_q WI_qb,i``; ``kI = LayerNorm(h WI_k)`` (weight and bias,
   eps 1e-5); RoPE on the first ``qk_rope_head_dim`` columns of each;
   ``w = h WI_w``; ``I[t,s] = sum_i w[t,i] (heads x dim)^-1/2
   relu(qI[t,i] . kI[s])`` for s <= t. ``S_t`` = every s <= t while t +
   1 <= ``index_topk``, else the ``index_topk`` rows of largest
   ``I[t,.]``, ties to the lower s. WRITTEN AS A MASK OVER ROWS.
5. ``a[t,s,j] = (q_n . k_n + q_r . k_r) / sqrt(nope + rope)`` for s in
   ``S_t``; softmax over them; ``o_j = sum_s p v``.
6. ``g = sigmoid(h W_g)`` (one scalar a head); ``x <- x + concat_j(g_j
   o_j) W_o``.

*Sliding layer* (the ``swa_`` keys): steps 1, 2, 3, 5, 6 at its sizes,
no indexer, ``S_t = {s : t - (sliding_window_size - 1) <= s <= t}``, A
MASK OVER ROWS.

*Feed-forward*: layers under ``first_k_dense_replace`` a dense SwiGLU;
the rest ``s = sigmoid(n W_r)`` in float32 over ALL the published
experts, ``C`` = the ``num_experts_per_tok`` largest of ``s + b``,
``gamma_e = routed_scaling_factor s_e / (sum_{C} s + 1e-20)``; ``x <- x
+ sum_{e in C and HELD} gamma_e E_e(n) + E_shared(n)``. HELD is this
chip's share: the file's ``n_routed_experts`` counts the experts held
(``reduced.n_routed_experts.source`` is the router's width) and
``expert_parallel.this_chip`` says which run of them. What the absent
experts would add is left out, and that partial sum goes on to the next
layer. The vocabulary is whatever slice the parameters hold.

**Assumed** (the configuration file's ``assumed`` has each reason): the
rescale constants ``rho`` (the key ``apply_mla_qkv_lora_rescale`` says
only that one is applied); the gate reads the normed stream h, ``W_g``
is d x H, applied before ``W_o``; the indexer's queries come from
``c_q``, its key from h, rotary on the first ``qk_rope_head_dim``
columns, LayerNorm on the key; ``sliding_window_size`` counts the
query's own row; one routing group; rotate-half pairing.
**Left out, as departures**: the vision and audio towers and any
multi-token-prediction block (the config gives the language model
alone); the indexer's Hadamard rotation of ``qI`` and ``kI``
(orthogonal on both sides: no score changes).

No cache, no kernels, no chunks, no batching, no sort but the
reference's own ranking: a Python loop over layers; so that 20,000
tokens at the published widths fit beside a serving engine, a layer's
matrices are widened from bf16 to float32 a piece at a time, the index
scores and the attention run a block of query rows and a group of
heads at a time, the experts one at a time over the tokens that chose
them; a sequence is padded with token 0 to whole blocks of 2,048 rows
(of 256 while it is shorter than one), which no row before the padding
reads (causal), so that the jitted pieces have one shape for every
seed's lengths. None changes a number.
Every product runs under ``jax.default_matmul_precision("highest")``.

A choice of experts and a choice of rows are both top-k's: where the
k-th and the next nearly tie, bf16 takes the other and that row's
logits move by more than rounding. So `followed_logits_at` can FOLLOW
the system's experts (``experts``) and the rows its full layers
attended to (``selected``), and reports how each sits against the
reference's own boundary (`routed_logits_at`'s contract in
``mla_moe_decoder.py``, `selected_logits_at`'s in
``sparse_linear_decoder.py``).

It reads the SYSTEM's parameter tree; what is the system's convention
is undone here: norm gains stored as an offset from one; four stacks,
``params["full"]`` and ``params["sliding"]`` (attention, layer i of
``layer_types`` being the next of its kind) and ``params["dense"]`` and
``params["moe"]`` (the feed-forward halves with each layer's second
norm ``ln_mlp``); matrices input-major and split by head (``w_dq
[d,rq]``, ``w_uq [rq,H,nope+rope]``, ``w_dkv [d,rkv+rope]``, ``w_uk
[rkv,H,nope]``, ``w_uv [rkv,H,v]``, ``w_g [d,H]``, ``w_o [H,v,d]``;
the indexer's ``w_iq [rq,Hi,Di]``, ``w_ik [d,Di]``, ``ik_gain``,
``ik_bias [Di]``, ``w_iw [d,Hi]``); experts ``w_gate, w_up [held,d,f]``,
``w_down [held,f,d]``, the shared ``ws_*``, ``router [d,E]``,
``router_bias [E]``; ``embed [V,d]``, ``lm_head [d,V]``.

``cfg`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.mla_moe_decoder import (
    _PAD,
    _head,
    _rms_norm,
    _scores,
    _swiglu,
)

F32 = jnp.float32
FULL = "full_attention"
_QB = 256       # query rows a block
_ROWS = 2048    # a sequence is padded to whole blocks of so many rows
_HG = 16        # attention heads a group


def geometry(cfg: dict, kind: str) -> dict:
    """One kind of layer's sizes, by the config's own keys."""
    p = "" if kind == FULL else "swa_"
    return {"heads": cfg[p + "num_attention_heads"],
            "rq": cfg[p + "q_lora_rank"], "rkv": cfg[p + "kv_lora_rank"],
            "nope": cfg[p + "qk_nope_head_dim"],
            "rope": cfg[p + "qk_rope_head_dim"], "v": cfg[p + "v_head_dim"],
            "theta": float(cfg[p + "rope_theta"])}


def held_experts(cfg: dict):
    """(first, count, the router's width): this chip's share."""
    count = cfg["n_routed_experts"]
    total = cfg.get("reduced", {}).get("n_routed_experts", {}).get(
        "source", count)
    chip = cfg.get("expert_parallel", {}).get("this_chip", 0)
    return chip * count, count, total


def _rope(x, positions, theta):
    """Rotate-half over all the columns of x [T,H,r], row i at
    ``positions[i]``."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    angle = positions.astype(F32)[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _rho(cfg, rank):
    return ((cfg["hidden_size"] / rank) ** 0.5
            if cfg["apply_mla_qkv_lora_rescale"] else 1.0)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "rkv", "rho_q",
                                             "rho_kv"))
def _latents(x, w, *, eps, theta, rkv, rho_q, rho_kv):
    """x [T,d] -> (h, c_q [T,rq], c_kv [T,rkv], k_r [T,rope] rotated)."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, w["ln_attn"], eps)
        c_q = rho_q * _rms_norm(h @ w["w_dq"].astype(F32), w["ln_q"], eps)
        ckr = h @ w["w_dkv"].astype(F32)
        c_kv = rho_kv * _rms_norm(ckr[:, :rkv], w["ln_kv"], eps)
        k_r = _rope(ckr[:, None, rkv:], jnp.arange(x.shape[0]), theta)[:, 0]
        return h, c_q, c_kv, k_r


@functools.partial(jax.jit, static_argnames=("theta", "rope"))
def _index_parts(h, c_q, w, *, theta, rope):
    """-> (qI [T,Hi,Di], kI [T,Di], w [T,Hi] with the constant)."""
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("tr,rhk->thk", c_q, w["w_iq"].astype(F32))
        k = h @ w["w_ik"].astype(F32)
        k = k - jnp.mean(k, -1, keepdims=True)
        k = k / jnp.sqrt(jnp.mean(jnp.square(k), -1, keepdims=True) + 1e-5)
        k = k * (1.0 + w["ik_gain"].astype(F32)) + w["ik_bias"].astype(F32)

        def turn(x):        # [T,n,Di]: its first ``rope`` columns
            return jnp.concatenate(
                [_rope(x[..., :rope], jnp.arange(x.shape[0]), theta),
                 x[..., rope:]], -1)

        hi, di = q.shape[1:]
        weights = (h @ w["w_iw"].astype(F32)) * (hi * di) ** -0.5
        return turn(q), turn(k[:, None])[:, 0], weights


@functools.partial(jax.jit, static_argnames=("topk",))
def _select(q_i, w_i, k_i, first, followed, *, topk):
    """A block of queries at positions ``first + arange``: q_i
    [qb,Hi,Di], w_i [qb,Hi], k_i [T,Di]; ``followed`` [qb,T] bool, the
    rows the SYSTEM attended to (all False: none known) -> (the mask to
    attend under [qb,T], differs [qb]: followed rows the reference did
    not choose, excess [qb]: how far ACROSS the reference's boundary the
    two choices differ: the larger of how far under the reference's
    k-th score the lowest followed row lies and how far over it the
    highest row NOT followed lies (rounding exchanges rows at the
    boundary; a choice that misses a row high above it is another
    selection), as a share of the spread of the visible rows' scores;
    1.0 where the system followed a row past the query or another
    number of rows than the rule gives)."""
    with jax.default_matmul_precision("highest"):
        scores = 0.0
        for h0 in range(0, q_i.shape[1], _HG):      # a group of heads
            part = jnp.einsum("qhd,sd->qhs", q_i[:, h0:h0 + _HG], k_i)
            scores = scores + jnp.einsum("qhs,qh->qs", jax.nn.relu(part),
                                         w_i[:, h0:h0 + _HG])
    qb, t = scores.shape
    pos = first + jnp.arange(qb)
    visible = jnp.arange(t)[None, :] <= pos[:, None]
    ranked = jnp.where(visible, scores, -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)   # ties: lower row
    rank = jnp.argsort(order, axis=-1)
    own = jnp.where((pos + 1 <= topk)[:, None], visible,
                    visible & (rank < topk))
    known = jnp.any(followed, axis=-1)
    use = jnp.where(known[:, None], followed, own)
    # (A sequence shorter than topk selects nowhere: any rank will do.)
    last = min(topk, t) - 1
    kth = jnp.take_along_axis(ranked, order[:, last:last + 1], -1)[:, 0]
    top = jnp.max(ranked, -1)
    low = jnp.min(jnp.where(visible, scores, jnp.inf), -1)
    lowest = jnp.min(jnp.where(use, scores, jnp.inf), -1)
    missed = jnp.max(jnp.where(visible & ~use, scores, -jnp.inf), -1)
    excess = jnp.where(pos + 1 <= topk, 0.0,
                       jnp.maximum(jnp.maximum(kth - lowest, missed - kth),
                                   0.0) / jnp.maximum(top - low, 1e-30))
    wrong = (jnp.any(use & ~visible, -1)
             | (jnp.sum(use, -1) != jnp.minimum(pos + 1, topk)))
    differs = jnp.sum(use & ~own, -1)
    return (use, jnp.where(known, differs, 0),
            jnp.where(known, jnp.where(wrong, 1.0, excess), 0.0))


@functools.partial(jax.jit, static_argnames=("theta", "nope"))
def _keys_values(c_kv, k_r, w_uk, w_uv, *, theta, nope):
    """A group of heads' keys [T,g,nope+rope] and values [T,g,v]."""
    with jax.default_matmul_precision("highest"):
        k_n = jnp.einsum("tr,rhk->thk", c_kv, w_uk.astype(F32))
        v = jnp.einsum("tr,rhv->thv", c_kv, w_uv.astype(F32))
        k_r = jnp.broadcast_to(k_r[:, None], k_n.shape[:2] + k_r.shape[-1:])
        return jnp.concatenate([k_n, k_r], -1), v


@functools.partial(jax.jit, static_argnames=("theta", "nope"))
def _attend(c_q, h, first, w_uq, k, v, mask, w_g, w_o, *, theta, nope):
    """A block of queries (rows ``first + arange``), a group of heads,
    under ``mask`` [qb,T] -> this group's part of the layer's output
    [qb,d]: gated, through its rows of W_o."""
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("tr,rhk->thk", c_q, w_uq.astype(F32))
        q = jnp.concatenate(
            [q[..., :nope],
             _rope(q[..., nope:], first + jnp.arange(q.shape[0]), theta)],
            -1)
        scores = jnp.einsum("qhk,shk->hqs", q, k) / jnp.sqrt(
            jnp.asarray(q.shape[-1], F32))
        scores = jnp.where(mask[None], scores, -jnp.inf)
        o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(scores, -1), v)
        gate = jax.nn.sigmoid(h @ w_g.astype(F32))             # [qb,g]
        return jnp.einsum("qhv,hvd->qd", o * gate[..., None],
                          w_o.astype(F32))


def _attention(x, w, cfg, kind, followed):
    """x [T,d] of ONE sequence -> (x + attention, the selection's
    report {differs, excess} [T], zeros for a sliding layer).
    ``followed(q0, q1)`` -> [q1-q0, T] bool or None."""
    g = geometry(cfg, kind)
    t = x.shape[0]
    h, c_q, c_kv, k_r = _latents(
        x, w, eps=cfg["rms_norm_eps"], theta=g["theta"], rkv=g["rkv"],
        rho_q=_rho(cfg, g["rq"]), rho_kv=_rho(cfg, g["rkv"]))
    blocks = [(a, min(a + _QB, t)) for a in range(0, t, _QB)]
    differs, excess = np.zeros(t, np.int64), np.zeros(t, np.float32)
    masks = []
    if kind == FULL:
        q_i, k_i, w_i = _index_parts(h, c_q, w, theta=g["theta"],
                                     rope=g["rope"])
        none = jnp.zeros((1, t), bool)
        for a, b in blocks:
            f = followed(a, b) if followed is not None else None
            f = (jnp.broadcast_to(none, (b - a, t)) if f is None
                 else jnp.asarray(f))
            use, d, e = _select(q_i[a:b], w_i[a:b], k_i, a, f,
                                topk=cfg["index_topk"])
            masks.append(use)
            differs[a:b], excess[a:b] = np.asarray(d), np.asarray(e)
    else:
        reach = cfg["sliding_window_size"] - 1
        rows = jnp.arange(t)[None, :]
        for a, b in blocks:
            pos = jnp.arange(a, b)[:, None]
            masks.append((rows <= pos) & (rows >= pos - reach))
    out = [jnp.zeros((b - a, x.shape[1]), F32) for a, b in blocks]
    for h0 in range(0, g["heads"], _HG):
        hs = slice(h0, min(h0 + _HG, g["heads"]))
        k, v = _keys_values(c_kv, k_r, w["w_uk"][:, hs], w["w_uv"][:, hs],
                            theta=g["theta"], nope=g["nope"])
        for i, (a, b) in enumerate(blocks):
            out[i] = out[i] + _attend(
                c_q[a:b], h[a:b], a, w["w_uq"][:, hs], k, v, masks[i],
                w["w_g"][:, hs], w["w_o"][hs], theta=g["theta"],
                nope=g["nope"])
    return x + jnp.concatenate(out), {"differs": differs, "excess": excess}


def _expert_layer(n, w, chosen, cfg):
    """n [N,d] -> (y [N,d], differs, excess, lead), each [N]: the
    router over every published expert, the held ones' part of the sum
    and the shared expert."""
    first, count, total = held_experts(cfg)
    k = cfg["num_experts_per_tok"]
    assert w["router"].shape[-1] == total and w["w_gate"].shape[0] == count
    s, use, differs, excess, lead = _scores(
        n, w["router"], w["router_bias"], chosen, k=k)
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
        out = _swiglu(n[token], w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        y = y.at[token].add(out * weight[:, None])
    return y, differs, excess, lead


@functools.partial(jax.jit, static_argnames=("eps",))
def _second_norm(x, gain, *, eps):
    return _rms_norm(x, gain, eps)


def _hidden(params, tokens, cfg, experts, selected):
    """tokens [T] of ONE sequence -> (the last block's output [T,d],
    the routing report [expert layers, T] x 3, the selection's report
    [full layers, T] x 2). ``experts`` [expert layers, T, k] (-1: the
    reference's own); ``selected(layer, q0, q1)`` -> [q1-q0, T] bool or
    None."""
    eps = cfg["rms_norm_eps"]
    n_dense = cfg["first_k_dense_replace"]
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
    seen = {"full": 0, "sliding": 0}
    routed, chosen_rows = [], []
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][i]
        stack = "full" if kind == FULL else "sliding"
        j = seen[stack]
        seen[stack] += 1
        w = jax.tree.map(lambda a: a[j], params[stack])
        follow = None
        if kind == FULL and selected is not None:
            follow = functools.partial(selected, j)
        x, about = _attention(x, w, cfg, kind, follow)
        if kind == FULL:
            chosen_rows.append(about)
        if i < n_dense:
            f = jax.tree.map(lambda a: a[i], params["dense"])
            n = _second_norm(x, f["ln_mlp"], eps=eps)
            x = x + _swiglu(n, f["w_gate"], f["w_up"], f["w_down"])
        else:
            m = i - n_dense
            f = jax.tree.map(lambda a: a[m], params["moe"])
            n = _second_norm(x, f["ln_mlp"], eps=eps)
            y, *about = _expert_layer(n, f, jnp.asarray(experts[m]), cfg)
            routed.append([np.asarray(a) for a in about])
            x = x + y
    differs, excess, lead = (np.stack(col) for col in zip(*routed))
    return (x, {"differs": differs, "excess": excess, "lead": lead},
            {"differs": np.stack([c["differs"] for c in chosen_rows]),
             "excess": np.stack([c["excess"] for c in chosen_rows])})


def _whole_blocks(n: int) -> int:
    """Rows a sequence of ``n`` is padded to: whole blocks of `_ROWS`,
    or of `_QB` while it is shorter than one."""
    block = _ROWS if n > _ROWS else _QB
    return -(-n // block) * block


def _followed(known, n, m, layer, a, c):
    """``known(layer, a, c)`` (the system's rows of queries a..c-1 of a
    sequence of ``n`` rows) over the ``m`` rows it was padded to: the
    padding's queries, and rows, all False (none known)."""
    out = np.zeros((c - a, m), bool)
    if a < n:
        got = known(layer, a, min(c, n))
        if got is None:
            return None
        out[:min(c, n) - a, :n] = np.asarray(got)[:, :n]
    return out


def followed_logits_at(params, tokens, rows, cfg, experts=None,
                       selected=None):
    """Float32 logits [len(rows), V] at the (sequence, position) pairs
    ``rows`` from a full causal forward pass over tokens [B,T], each
    sequence read up to its last row; and two reports.

    ``experts`` [expert layers, B, T, k]: the experts the SYSTEM chose
    (-1: none known, the reference uses its own). ``selected``: a list,
    a sequence, of callables ``(full layer, q0, q1) -> [q1-q0, rows]
    bool`` (the rows the system's queries q0..q1-1 attended to, over at
    least the sequence's length; all False or None: none known).
    -> (logits, routing {differs, excess, lead} [expert layers, B, T],
    selection {differs, excess} [full layers, B, T]), zero past a
    sequence's last row."""
    tokens = np.asarray(tokens)
    b, t = tokens.shape
    # Whole blocks of `_ROWS` rows, the padding read by no row before it
    # (causal): the jitted pieces then have the same shapes for every
    # seed's lengths, and a compile cache serves them.
    padded = _whole_blocks(t)
    tokens = np.pad(tokens, ((0, 0), (0, padded - t)))
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    n_full = sum(k == FULL for k in
                 cfg["layer_types"][:cfg["num_hidden_layers"]])
    k = cfg["num_experts_per_tok"]
    if experts is None:
        experts = np.full((n_moe, b, t, k), -1, np.int32)
    experts = np.pad(np.asarray(experts),
                     ((0, 0), (0, 0), (0, padded - t), (0, 0)),
                     constant_values=-1)
    ends = [max(p for s, p in rows if s == i) + 1 for i in range(b)]
    routing = {name: np.zeros((n_moe, b, t), kind) for name, kind in
               (("differs", bool), ("excess", np.float32),
                ("lead", np.float32))}
    selection = {"differs": np.zeros((n_full, b, t), np.int64),
                 "excess": np.zeros((n_full, b, t), np.float32)}
    xs = []
    for i, n in enumerate(ends):
        m = _whole_blocks(n)            # the sequence's own
        follow = None
        if selected is not None and selected[i] is not None:
            follow = functools.partial(_followed, selected[i], n, m)
        x, routed, chose = _hidden(params, tokens[i, :m], cfg,
                                   experts[:, i, :m], follow)
        xs.append(x)
        for name in routing:
            routing[name][:, i, :n] = routed[name][:, :n]
        for name in selection:
            selection[name][:, i, :n] = chose[name][:, :n]
    picked = jnp.stack([xs[s][p] for s, p in rows])
    return (_head(picked, params["ln_out"], params["lm_head"],
                  eps=cfg["rms_norm_eps"]), routing, selection)


def routed_logits_at(params, tokens, rows, cfg, chosen=None):
    """`mla_moe_decoder.routed_logits_at`'s contract: the system's
    experts followed, the reference's own rows."""
    return followed_logits_at(params, tokens, rows, cfg, chosen)[:2]


def selected_logits_at(params, tokens, rows, cfg, selected=None):
    """`sparse_linear_decoder.selected_logits_at`'s contract: the
    system's rows followed, the reference's own experts."""
    logits, _, report = followed_logits_at(params, tokens, rows, cfg, None,
                                           selected)
    return logits, report


def logits_at(params, tokens, rows, cfg):
    """As the dense families' reference: its own experts and rows."""
    return followed_logits_at(params, tokens, rows, cfg)[0]


def expert_layer(n, w, cfg):
    """One expert layer's feed-forward half on normed rows n [N,d] with
    the reference's own routing: what `_expert_layer` adds to the
    stream (for the test that the shares add up to the uncut layer)."""
    k = cfg["num_experts_per_tok"]
    chosen = jnp.full((n.shape[0], k), -1, jnp.int32)
    return _expert_layer(jnp.asarray(n, F32), w, chosen, cfg)[0]
