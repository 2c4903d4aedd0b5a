"""The plain reference of the MiniCPM-SALA family (``minicpm_sala``):
the next-token forward pass in straightforward float32 ``jax.numpy``.

Written from the published description (the model's ``config.json``,
the family's published MiniCPM4 ``sparse_config``, lightning
attention's recurrence; the layer equations as issue 35 sets them out),
independent of ``ray_tpu/models/minicpm_sala.py`` and of
``ray_tpu/ops/``. ``mixer_types`` says which layers are sparse
(``minicpm4``) and which lightning (``lightning-attn``). ``x`` is a
block's input, ``a = scale_depth / sqrt(mup_denominator)``:

    h   = x + a Mixer(RMSNorm(x))                    (pre-norm, muP)
    out = h + a W_down (silu(W_gate n) * (W_up n)),  n = RMSNorm(h)
    x_0 = scale_emb * embedding row
    logits = W_head (RMSNorm(x_L) / (hidden_size / dim_model_base))

*Lightning layer*, per head h of H, width D = 128, n the normed input:
``q, k, v = n W_q, n W_k, n W_v``; ``q <- RMSNorm_D(q)``,
``k <- RMSNorm_D(k)``; rotate-half RoPE on q and k (``rope_theta``).
The state S in R^{D x D}, ``S_0 = 0``, ``lambda_h = exp(-2^(-8 (h+1) /
H))``:

    S_t = lambda_h S_{t-1} + k_t v_t^T
    o_t = S_t^T q_t / sqrt(D)

ONE TOKEN AT A TIME, exactly as written (never the chunked form the
system's prefill runs). ``y_t = RMSNorm_D(o_t) * sigmoid(n W_g)``, then
``concat_h(y_t) W_o``.

*Sparse layer* (H query heads, KH KV heads, G = H / KH): q, k, v
projected, per-head RMSNorm on q and k, no rotary. With ``sparse_config``
= (kernel_size K, kernel_stride s, block_size b, init_blocks,
window_size w, topk, dense_len), the query at position t (t + 1 rows
visible):

- ``t + 1 <= dense_len``: causal softmax attention over every row;
- else, a KV head at a time: ``kc_j = mean(k[s j : s j + K])`` for every
  j with ``s j + K <= t + 1``; ``p^h = softmax_j(q^h . kc_j / sqrt(D))``;
  ``score[j] = sum_{h in group} p^h[j]``; block n (rows b n .. b n + b - 1)
  scores the maximum over the windows that overlap it (0 if none);
  blocks ``< init_blocks`` and the blocks that hold rows ``t - w + 1 ..
  t`` are forced; the selected set is the forced blocks and the
  best-scoring visible others, ``topk`` in all (ties to the lower
  block); softmax attention over the rows ``r <= t`` of the selected
  blocks, WRITTEN AS A MASK OVER ROWS.

Then ``o <- o * sigmoid(n W_g)``, ``W_o``.

A selection is a top-k: where the k-th and the next block nearly tie,
bf16 picks the other one and that row's logits move by more than
rounding. So `selected_logits_at` can FOLLOW the system's choices
(``chosen``) and reports how each sits against the reference's own:
``differs`` (followed blocks the reference did not choose) and
``excess`` (how far under the reference's k-th score the worst of them
lies, as a share of the spread of the scores that compete; 1.0 where a
forced block was dropped, a block past the context taken, or the system
ran a selected row dense or a dense row selected).

No cache, no kernels, no chunks, no batching: a Python loop over
layers, each matrix widened from bf16 to float32 as it is used, every
product under ``jax.default_matmul_precision("highest")``. So that
25,000 tokens of 16 layers at the published widths fit beside a serving
engine, the SwiGLU half and the lightning recurrence run a block of rows
at a time (the state handed from block to block), the sparse layer's
queries, gate and output product a block of query rows at a time, the
head a block of vocabulary columns at a time, and a layer's matrices
are taken out of their stack as each half needs them; none changes a
number.

It reads the SYSTEM's parameter tree; what is the system's convention
is undone here: norm gains are stored as an offset from one; the layers
are two stacks, ``params["sparse"]`` and ``params["lightning"]``, layer
i of ``mixer_types`` being the next of its kind; the q, k, v and gate
projections are OUTPUT-major with their heads merged, head-major (sparse
``wq, w_g [H D,d]``, ``wk, wv [KH D,d]``; lightning ``w_q, w_k, w_v, w_g
[H D,d]``: ``x W`` is ``x @ w.T``), every other matrix input-major
(``wo, w_o [H D,d]``, ``w_gate, w_up [d,F]``, ``w_down [F,d]``, ``embed
[V,d]``, ``lm_head [d,V]``); ``ln_q, ln_k, ln_o [D]``, ``ln_in, ln_mlp,
ln_out [d]``.

``c`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_ROWS = 2048            # the SwiGLU half: rows a block
_QUERY_ROWS = 64        # sparse attention: query rows a block
_HEAD_COLUMNS = 16384   # the head: vocabulary columns a block
_MLP = ("ln_mlp", "w_gate", "w_up", "w_down")
_LIGHTNING = ("ln_in", "w_q", "w_k", "w_v", "w_g", "w_o", "ln_q", "ln_k",
              "ln_o")
_SPARSE = ("ln_in", "wq", "wk", "wv", "w_g", "wo", "ln_q", "ln_k")
_SIZES = ("kernel_size", "kernel_stride", "block_size", "init_blocks",
          "window_size", "topk", "dense_len")


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps", "a"))
def _swiglu_rows(x, mixed, w, *, eps, a):
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        h = x + a * mixed
        n = _rms_norm(h, w["ln_mlp"], eps)
        y = (jax.nn.silu(n @ f("w_gate")) * (n @ f("w_up"))) @ f("w_down")
        return h + a * y


def _layer(stack, i, names):
    """Layer ``i``'s arrays ``names`` out of a stack of layers."""
    return {name: stack[name][i] for name in names}


def _whole_rows(a, n=_ROWS):
    """a [R, ..] -> a with rows of zeros after it up to ``n`` rows: a
    last, short block of a sequence then runs the program of the whole
    ones (rows are independent, or masked by their count)."""
    return jnp.pad(a, ((0, n - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _swiglu_half(x, mixed, mlp, eps, a):
    """``h = x + a mixed``, then ``h + a SwiGLU(RMSNorm(h))``."""
    return jnp.concatenate(
        [_swiglu_rows(_whole_rows(x[r:r + _ROWS]),
                      _whole_rows(mixed[r:r + _ROWS]), mlp, eps=eps,
                      a=a)[:x.shape[0] - r]
         for r in range(0, x.shape[0], _ROWS)])


def _rope(x, theta, start):
    """Rotate-half RoPE: x [T, H, D] at positions start .. start+T-1."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (start + jnp.arange(t)).astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "n_heads"))
def _lightning_rows(x, start, real, state, w, *, eps, theta, n_heads):
    """x [R, d] float32: the rows at positions ``start`` .. of ONE
    sequence, the first ``real`` of them real (the rest steps no state),
    ``state`` [H, D, D] the state before them; ``w`` one lightning
    layer -> (the mixer's output [R, d], the state after the last real
    row)."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        t, d = x.shape
        hd = w["w_q"].shape[0] // n_heads
        n = _rms_norm(x, w["ln_in"], eps)
        proj = lambda name: (n @ f(name).T).reshape(t, n_heads, hd)
        q = _rope(_rms_norm(proj("w_q"), w["ln_q"], eps), theta, start)
        k = _rope(_rms_norm(proj("w_k"), w["ln_k"], eps), theta, start)
        v = proj("w_v")
        decay = jnp.exp(-jnp.exp2(
            -8.0 * jnp.arange(1, n_heads + 1, dtype=F32) / n_heads))

        def token(s, at):                       # s [H, D(k), D(v)]
            q_t, k_t, v_t, steps = at
            s = jnp.where(steps, decay[:, None, None] * s
                          + k_t[:, :, None] * v_t[:, None, :], s)
            return s, jnp.einsum("hkv,hk->hv", s, q_t) / jnp.sqrt(
                jnp.asarray(hd, F32))

        state, o = jax.lax.scan(token, state, (q, k, v, jnp.arange(t) < real))
        y = _rms_norm(o, w["ln_o"], eps) * jax.nn.sigmoid(proj("w_g"))
        return y.reshape(t, -1) @ f("w_o"), state


def _lightning_mixer(x, w, *, eps, theta, n_heads):
    """x [T, d] float32 of ONE sequence -> (the mixer's output [T, d],
    the state after the last token [H, D, D]): the recurrence from a
    zero state, a block of rows at a time with the state handed on."""
    hd = w["w_q"].shape[0] // n_heads
    state, outs = jnp.zeros((n_heads, hd, hd), F32), []
    for r in range(0, x.shape[0], _ROWS):
        n = min(_ROWS, x.shape[0] - r)
        out, state = _lightning_rows(_whole_rows(x[r:r + _ROWS]), r, n,
                                     state, w, eps=eps, theta=theta,
                                     n_heads=n_heads)
        outs.append(out[:n])
    return jnp.concatenate(outs), state


@functools.partial(jax.jit, static_argnames=("eps", "hd"))
def _sparse_kv(x, w, *, eps, hd):
    """x [T, d] -> the normed keys and the values [T, KH, D]."""
    with jax.default_matmul_precision("highest"):
        n = _rms_norm(x, w["ln_in"], eps)
        proj = lambda name: (n @ w[name].astype(F32).T).reshape(
            x.shape[0], -1, hd)
        return _rms_norm(proj("wk"), w["ln_k"], eps), proj("wv")


@functools.partial(jax.jit, static_argnames=("sizes", "eps"))
def _sparse_rows(x, w, t1, k, v, kc, chosen, *, sizes, eps):
    """A block of query rows of ONE sequence. x [R,d] whose queries see
    ``t1`` [R] rows; ``w`` the layer's ``ln_in``, ``wq``, ``ln_q``,
    ``w_g``, ``wo``; k, v [T,KH,D]; kc [NW,KH,D] (every window that
    ends inside the sequence); chosen [R,KH,topk] (the blocks to
    follow; -1: the reference's own) -> (the mixer's output [R,d],
    differs [R,KH], excess [R,KH])."""
    ksize, stride, block, init, window, topk, dense_len = sizes
    with jax.default_matmul_precision("highest"):
        t, kh, d = k.shape
        r = x.shape[0]
        n = _rms_norm(x, w["ln_in"], eps)
        proj = lambda name: (n @ w[name].astype(F32).T).reshape(r, -1, d)
        q = _rms_norm(proj("wq"), w["ln_q"], eps)
        gate = jax.nn.sigmoid(proj("w_g"))
        h = q.shape[1]
        nw, nblk = kc.shape[0], -(-t // block)
        qg = q.reshape(r, kh, h // kh, d)
        # The selection, the reference's own.
        logits = jnp.einsum("rkgd,wkd->rkgw", qg, kc) / jnp.sqrt(
            jnp.asarray(d, F32))
        complete = (stride * jnp.arange(nw)[None, :] + ksize
                    <= t1[:, None])                             # [R,NW]
        logits = jnp.where(complete[:, None, None], logits, -jnp.inf)
        p = jnp.nan_to_num(jax.nn.softmax(logits, axis=-1))
        s = jnp.sum(jnp.where(complete[:, None, None], p, 0.0), axis=2)
        first = jnp.arange(nblk) * block                        # [NBLK]
        overlap = ((stride * jnp.arange(nw)[None, :] < first[:, None] + block)
                   & (stride * jnp.arange(nw)[None, :] + ksize
                      > first[:, None]))                        # [NBLK,NW]
        score = jnp.max(jnp.where(overlap[None, None], s[:, :, None, :], 0.0),
                        axis=-1)                                # [R,KH,NBLK]
        blocks = jnp.arange(nblk)
        visible = blocks[None, :] * block < t1[:, None]         # [R,NBLK]
        forced = visible & ((blocks[None, :] < init) | (
            blocks[None, :] * block + block - 1 >= t1[:, None] - window))
        key = jnp.where(forced[:, None], jnp.inf, score)
        key = jnp.where(visible[:, None], key, -1.0)
        order = jnp.argsort(-key, axis=-1, stable=True)[..., :topk]
        own = jnp.any(order[..., None] == blocks, axis=-2)      # [R,KH,NBLK]
        selected_row = (t1 > dense_len)                         # [R]
        # What the system chose, where it said.
        said = chosen[..., 0] >= 0                              # [R,KH]
        theirs = jnp.any(chosen[..., None] == blocks, axis=-2)
        follow = jnp.where(said[..., None], theirs, own)
        extra = follow & ~own
        competing = visible[:, None] & ~forced[:, None]
        kth = jnp.min(jnp.where(own & competing, score, jnp.inf), axis=-1)
        spread = (jnp.max(jnp.where(competing, score, -jnp.inf), axis=-1)
                  - jnp.min(jnp.where(competing, score, jnp.inf), axis=-1))
        under = jnp.max(jnp.where(extra, kth[..., None] - score, 0.0),
                        axis=-1) / jnp.maximum(spread, 1e-30)
        broken = (jnp.any(forced[:, None] & ~follow, axis=-1)
                  | jnp.any(follow & ~visible[:, None], axis=-1))
        excess = jnp.where(broken, 1.0, under)
        differs = jnp.sum(extra, axis=-1)
        mismatch = said != selected_row[:, None]
        excess = jnp.where(selected_row[:, None],
                           jnp.where(mismatch, 1.0, excess),
                           jnp.where(mismatch, 1.0, 0.0))
        differs = jnp.where(selected_row[:, None], differs, 0)
        # Attention, the selection as a mask over rows.
        rows = jnp.arange(t)
        chosen_rows = jnp.repeat(follow, block, axis=-1)[..., :t]
        mask = (jnp.where(selected_row[:, None, None], chosen_rows, True)
                & (rows[None, None, :] < t1[:, None, None]))    # [R,KH,T]
        att = jnp.einsum("rkgd,tkd->rkgt", qg, k) / jnp.sqrt(
            jnp.asarray(d, F32))
        att = jax.nn.softmax(jnp.where(mask[:, :, None], att, -jnp.inf), -1)
        o = jnp.einsum("rkgt,tkd->rkgd", att, v).reshape(r, h, d)
        return ((o * gate).reshape(r, -1) @ w["wo"].astype(F32), differs,
                excess)


def _sizes(c):
    return tuple(int(c["sparse_config"][name]) for name in _SIZES)


def _sparse_mixer(x, w, c, chosen):
    """x [T, d] of ONE sequence; chosen [T,KH,topk] -> (the mixer's
    output [T, d], differs [T,KH], excess [T,KH])."""
    sizes = _sizes(c)
    ksize, stride = sizes[:2]
    t, eps = x.shape[0], c["rms_norm_eps"]
    # Rows and windows past the sequence's end are zeros that no query
    # sees (``t1`` masks both): with them, sequences of nearby lengths
    # share one compiled program.
    tp = -(-t // _ROWS) * _ROWS
    k, v = _sparse_kv(_whole_rows(x, tp),
                      {n: w[n] for n in ("ln_in", "wk", "ln_k", "wv")},
                      eps=eps, hd=c["head_dim"])
    rows = {n: w[n] for n in ("ln_in", "wq", "ln_q", "w_g", "wo")}
    nw = max((t - ksize) // stride + 1, 0)
    # kc_j = mean(k[stride j : stride j + K]) for every window that ends
    # inside the sequence (one row of zeros where there is none).
    inside = stride * np.arange(max(nw, 1))[:, None] + np.arange(ksize)
    kc = jnp.mean(k[np.minimum(inside, t - 1)], axis=1) * (nw > 0)
    kc = _whole_rows(kc, tp // stride)
    outs = []
    for r in range(0, t, _QUERY_ROWS):
        n = min(_QUERY_ROWS, t - r)
        pad = _QUERY_ROWS - n
        outs.append(jax.tree.map(
            lambda a: a[:n],
            _sparse_rows(jnp.pad(x[r:r + n], ((0, pad), (0, 0))), rows,
                         jnp.pad(jnp.arange(r, r + n) + 1, (0, pad),
                                 constant_values=1),
                         k, v, kc,
                         jnp.pad(chosen[r:r + n], ((0, pad), (0, 0), (0, 0)),
                                 constant_values=-1), sizes=sizes, eps=eps)))
    return tuple(jnp.concatenate(part) for part in zip(*outs))


def _embedded(params, tokens, c):
    return jnp.take(params["embed"], jnp.asarray(tokens),
                    axis=0).astype(F32) * c["scale_emb"]


def hidden(params, tokens, c, chosen=None):
    """tokens [T] of one sequence; chosen [sparse layers, T, KH, topk]
    or None -> (the last block's output [T, d] before the final norm,
    {differs, excess: [sparse layers, T, KH]})."""
    x = _embedded(params, tokens, c)
    eps = c["rms_norm_eps"]
    a = c["scale_depth"] / c["mup_denominator"] ** 0.5
    kinds = c["mixer_types"][:c["num_hidden_layers"]]
    kh, topk = c["num_key_value_heads"], c["sparse_config"]["topk"]
    n_sparse = n_lightning = 0
    report = {"differs": [], "excess": []}
    for kind in kinds:
        if kind == "lightning-attn":
            stack, i = params["lightning"], n_lightning
            mixed, _ = _lightning_mixer(
                x, _layer(stack, i, _LIGHTNING), eps=eps,
                theta=c["rope_theta"], n_heads=c["lightning_nh"])
            n_lightning += 1
        elif kind == "minicpm4":
            stack, i = params["sparse"], n_sparse
            said = (jnp.full((len(tokens), kh, topk), -1, jnp.int32)
                    if chosen is None else jnp.asarray(chosen[n_sparse]))
            mixed, differs, excess = _sparse_mixer(
                x, _layer(stack, i, _SPARSE), c, said)
            report["differs"].append(np.asarray(differs))
            # Nothing to hold against the reference's own choices.
            report["excess"].append(np.asarray(excess)
                                    * (chosen is not None))
            n_sparse += 1
        else:
            raise ValueError(f"mixer type {kind!r}")
        x = _swiglu_half(x, mixed, _layer(stack, i, _MLP), eps, a)
    return x, {k: np.stack(v) for k, v in report.items()}


def first_state(params, tokens, c, chosen=None):
    """tokens [T] of one sequence -> the FIRST lightning layer's state
    after the last token, [H, D, D] float32. The layers before it (the
    published order begins with a sparse one) are run, ``chosen``
    followed as in `hidden`, so that it reads what it reads in the
    model."""
    kinds = c["mixer_types"][:c["num_hidden_layers"]]
    before = kinds.index("lightning-attn")
    x = _embedded(params, tokens, c)
    if before:
        x = hidden(params, tokens, dict(c, num_hidden_layers=before),
                   chosen)[0]
    return _lightning_mixer(x, _layer(params["lightning"], 0, _LIGHTNING),
                            eps=c["rms_norm_eps"],
                            theta=c["rope_theta"],
                            n_heads=c["lightning_nh"])[1]


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _head(x, ln_out, columns, *, eps, scale):
    with jax.default_matmul_precision("highest"):
        return (_rms_norm(x, ln_out, eps) * scale) @ columns.astype(F32)


def selected_logits_at(params, tokens, rows, c, chosen=None):
    """Float32 logits [len(rows), V] at the (sequence, position) pairs
    ``rows`` from a full causal forward pass over tokens [B, T], each
    sequence read up to its last row; and the selection's report.

    ``chosen`` [sparse layers, B, T, KH, topk]: the blocks the SYSTEM
    selected (-1: none known or a dense row; the reference uses its
    own). The report's arrays are [sparse layers, B, T, KH] (zero past
    a sequence's last row)."""
    tokens = np.asarray(tokens)
    b, t = tokens.shape
    ends = [max(p for s, p in rows if s == i) + 1 for i in range(b)]
    xs, report = [], None
    for i, n in enumerate(ends):
        x, rep = hidden(params, tokens[i, :n], c,
                        None if chosen is None
                        else np.asarray(chosen)[:, i, :n])
        xs.append(x)
        if report is None:
            report = {k: np.zeros((v.shape[0], b, t) + v.shape[2:], v.dtype)
                      for k, v in rep.items()}
        for k, v in rep.items():
            report[k][:, i, :n] = v
    picked = jnp.stack([xs[s][p] for s, p in rows])
    head = params["lm_head"]
    scale = c["dim_model_base"] / c["hidden_size"]
    logits = jnp.concatenate(
        [_head(picked, params["ln_out"], head[:, col:col + _HEAD_COLUMNS],
               eps=c["rms_norm_eps"], scale=scale)
         for col in range(0, head.shape[1], _HEAD_COLUMNS)], axis=-1)
    return logits, report


def logits_at(params, tokens, rows, c):
    """As the dense families' reference: the reference's own selection."""
    return selected_logits_at(params, tokens, rows, c)[0]
