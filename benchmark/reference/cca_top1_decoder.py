"""The plain reference of the ZAYA1 family (``zaya``): the next-token
forward pass in straightforward float32 ``jax.numpy``.

Written from the layer equations as issue 40 sets them out (sizes from
the model's ``config.json``; the order of operations inside CCA from
Zyphra's "Compressed Convolutional Attention", arXiv:2510.04476; the
router from the ZAYA1 report, arXiv:2511.17127), independent of
``ray_tpu/models/zaya.py``. Pre-norm residual blocks, RMSNorm with
weight (eps from the file), a final norm, a TIED head; ``d`` the hidden
size, ``Hq`` query heads and ``Hkv`` KV heads of ``dh``, ``G = Hq/Hkv``.
For token ``t``, ``h_t = RMSNorm(x_t)``:

    1. q~_t = h_t W_q (Hq dh), k~_t = h_t W_k (Hkv dh), u_t = [q~_t ; k~_t]
    2. a_t[c] = w0[c,0] u_{t-1}[c] + w0[c,1] u_t[c] + b0[c]
    3. c_t[g] = a_{t-1}[g] W1[g,0] + a_t[g] W1[g,1] + b1[g]   (a group a head)
    4. m^q_{t,j} = (q~_{t,j} + k~_{t,j//G}) / 2;  m^k_{t,i} = mean_j m^q_{t,j}
       q_t = c_t[queries] + m^q_t;  k_t = c_t[keys] + m^k_t
    5. v_t = [h_t W_v1 ; h_{t-1} W_v2]
    6. q <- sqrt(dh) q/|q|;  k <- exp(tau_i) sqrt(dh) k/|k|;  rotate-half
       RoPE at position t on the first partial_rotary_factor x dh columns
    7. o = causal softmax(q k^T / sqrt(dh)) v, G query heads a KV head;
       x <- x + o W_o
    8. g = RMSNorm(x);  r = g W_d;  s = W_3 gelu(W_2 gelu(W_1 RMSNorm(r)));
       p = softmax(s);  e* = argmax(p + b);  x <- x + p_{e*} SwiGLU_{e*}(g)
       SwiGLU(g) = (silu(g W_gate) * (g W_up)) W_down

**Assumed** (``config.json`` does not fix them; the configuration file
repeats this list): biases ``b0``, ``b1`` on the two convolutions; each
convolution pads its OWN input with zeros (``u_{-1} = 0`` and
``a_{-1} = 0``, not ``b0``), and ``h_{-1} = 0``; ``exp(tau)`` as the
form of the key temperature; the router reads the normed stream ``g``,
has an inner RMSNorm with weight and the exact (erf) GELU; the gate is
the chosen expert's own softmax value; rotate-half pairing.
**Departures from the published model, each left out here and in the
program alike** (``config.json`` has no key for any of them): the
report's averaging of the router's input over depth, learned scales on
the residual stream, and a skip choice beside the 16 experts.

No cache, no kernel, no batching, no sort: a Python loop over layers
and, in a layer's second half, over experts, each applied to the tokens
that chose it (found by a plain comparison; their number padded up to a
multiple of 256 with weight-zero repeats of token 0 so that a handful
of shapes compile), its bf16 matrices widened to float32 one expert at
a time; the head in blocks of vocabulary rows, so that the reference
fits beside the program at the published widths. Every matrix product
runs under ``jax.default_matmul_precision("highest")``.

It reads the SYSTEM's parameter tree; what is the system's convention
and not the published one is undone here:

- norm gains are stored as an offset from one (``g = 1 + stored``);
- the layers are one stack ``params["layers"]``, each entry ``[L, ..]``;
- matrices are input-major; ``w_qk [d, Hq dh + Hkv dh]`` is ``W_q`` and
  ``W_k`` side by side, ``w_v [d, Hkv dh]`` is ``W_v1`` and ``W_v2``;
- ``conv0_w [C, 2]`` and ``conv1_w [head, 2, dh, dh]``: tap 0 multiplies
  the LAST token, tap 1 this one;
- the router: ``router_down [d, 256]``, ``ln_router``, ``router_1``,
  ``router_2 [256, 256]``, ``router_3 [256, E]``, ``router_bias [E]``;
- experts ``w_gate, w_up [E, d, f]``, ``w_down [E, f, d]``;
  ``embed [V, d]`` is also the head.

**Routing near a tie.** With ONE expert a token nothing cushions a
flipped choice. `routed_logits_at` therefore takes the system's
choices: a layer at a time it computes its OWN ``p + b`` from its own
hidden state, reports how each system choice sits against its own best,
and then follows the system's choice (weighted by its own ``p``), so
that the logits compare like with like on every row
(``benchmark/drivers/serve_routed.py`` holds the limits).
`router_probs` is the router alone, on an input that is handed to it.

``cfg`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_PAD = 256          # an expert's token count is padded up to a multiple
_VOCAB_BLOCK = 32768


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


def _rotate_half(x, theta, start=0):
    """x [T, H, r], row t at position ``start + t``: every column of it
    rotated (the caller hands in the rotary columns alone)."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    at = start + jnp.arange(x.shape[0], dtype=F32)
    angle = at[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _back_one(z):
    """z [T, ..] -> z_{t-1}, zeros before the sequence."""
    return jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)


def _dims(cfg):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    theta = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    return hq, hkv, dh, int(cfg["partial_rotary_factor"] * dh), theta


@functools.partial(jax.jit,
                   static_argnames=("eps", "theta", "hq", "hkv", "dh", "rot"))
def _attention(x, w, *, eps, theta, hq, hkv, dh, rot):
    """x [T, d] float32 of ONE sequence -> (x + CCA(RMSNorm(x)), the
    second norm's output for the layer's experts, and what the system
    keeps of this layer: u, a, the shifted value half's source, k, v)."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        t = x.shape[0]
        h = _rms_norm(x, w["ln_attn"], eps)
        w_q, w_k = f("w_qk")[:, :hq * dh], f("w_qk")[:, hq * dh:]
        q_lat, k_lat = h @ w_q, h @ w_k                         # step 1
        u = jnp.concatenate([q_lat, k_lat], axis=-1)
        w0, w1 = f("conv0_w"), f("conv1_w")
        a = w0[:, 0] * _back_one(u) + w0[:, 1] * u + f("conv0_b")  # step 2
        by_head = lambda z: z.reshape(t, hq + hkv, dh)
        c = (jnp.einsum("thc,hcd->thd", by_head(_back_one(a)), w1[:, 0])
             + jnp.einsum("thc,hcd->thd", by_head(a), w1[:, 1])
             + f("conv1_b"))                                    # step 3
        group = hq // hkv
        q_heads = q_lat.reshape(t, hq, dh)
        k_heads = k_lat.reshape(t, hkv, dh)
        m_q = 0.5 * (q_heads + jnp.repeat(k_heads, group, axis=1))
        m_k = jnp.mean(m_q.reshape(t, hkv, group, dh), axis=2)
        q, k = c[:, :hq] + m_q, c[:, hq:] + m_k                 # step 4
        half = hkv * dh // 2
        w_v1, w_v2 = f("w_v")[:, :half], f("w_v")[:, half:]
        v_next = h @ w_v2
        v = jnp.concatenate([h @ w_v1, _back_one(v_next)], axis=-1)
        v = v.reshape(t, hkv, dh)                               # step 5
        norm = lambda z: jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
        q = dh ** 0.5 * q / norm(q)
        k = jnp.exp(f("tau"))[:, None] * dh ** 0.5 * k / norm(k)
        rope = lambda z: jnp.concatenate(
            [_rotate_half(z[..., :rot], theta), z[..., rot:]], axis=-1)
        q, k = rope(q), rope(k)                                 # step 6
        scores = jnp.einsum("qhd,shd->hqs", q, jnp.repeat(k, group, axis=1))
        scores = scores / jnp.sqrt(jnp.asarray(dh, F32))
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        o = jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1),
                       jnp.repeat(v, group, axis=1))
        x = x + o.reshape(t, hq * dh) @ f("w_o")                # step 7
        kept = {"u": u, "a": a, "v_next": v_next, "k": k, "v": v}
        return x, _rms_norm(x, w["ln_mlp"], eps), kept


@functools.partial(jax.jit, static_argnames=("eps",))
def _router(g, w, *, eps):
    """g [N, d] -> p [N, E]: the router MLP, float32."""
    with jax.default_matmul_precision("highest"):
        f = lambda name: w[name].astype(F32)
        gelu = lambda z: jax.nn.gelu(z, approximate=False)
        r = _rms_norm(g @ f("router_down"), w["ln_router"], eps)
        s = gelu(gelu(r @ f("router_1")) @ f("router_2")) @ f("router_3")
        return jax.nn.softmax(s, axis=-1)


@jax.jit
def _choice(p, bias, chosen):
    """p [N, E], chosen [N] (-1: none known) -> the expert to use [N]
    (the reference's own where none is known) and, of each row against
    the reference's OWN best ``p + b``: whether the choice differs, how
    far the used expert's ``p + b`` lies under the best, and the best's
    lead over the second, the last two as shares of the row's spread of
    ``p + b``."""
    biased = p + bias.astype(F32)
    ranked = -jnp.sort(-biased, axis=-1)
    own = jnp.argmax(biased, axis=-1).astype(jnp.int32)
    use = jnp.where(chosen < 0, own, chosen)
    spread = ranked[:, 0] - ranked[:, -1]
    used = jnp.take_along_axis(biased, use[:, None], axis=-1)[:, 0]
    return (use, use != own, jnp.maximum(ranked[:, 0] - used, 0.0) / spread,
            (ranked[:, 0] - ranked[:, 1]) / spread)


@jax.jit
def _swiglu(g, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        gate, up = g @ w_gate.astype(F32), g @ w_up.astype(F32)
        return (jax.nn.silu(gate) * up) @ w_down.astype(F32)


_EXPERTS = ("w_gate", "w_up", "w_down")


def _layer(params, i):
    """Layer ``i``'s weights but its experts', which `_experts` takes
    out of the stack one expert at a time (a layer's are 1.2 GB)."""
    return {k: a[i] for k, a in params["layers"].items()
            if k not in _EXPERTS}


def _experts(g, w, experts, chosen, cfg):
    """g [N, d] (every sequence's tokens) -> (y [N, d], differs, excess,
    lead), each [N]; ``w`` is one layer's weights, ``experts(name, e)``
    one matrix of its expert ``e``."""
    p = _router(g, w, eps=cfg["rms_norm_eps"])
    use, differs, excess, lead = _choice(p, w["router_bias"], chosen)
    use_host = np.asarray(use)
    gate_host = np.asarray(jnp.take_along_axis(p, use[:, None], axis=-1))[:, 0]
    y = jnp.zeros_like(g)
    for e in range(cfg["num_experts"]):
        token = np.nonzero(use_host == e)[0]
        if not len(token):
            continue
        pad = -len(token) % _PAD
        weight = np.concatenate([gate_host[token], np.zeros(pad, np.float32)])
        token = np.concatenate([token, np.zeros(pad, token.dtype)])
        out = _swiglu(g[token], *(experts(name, e) for name in _EXPERTS))
        y = y.at[token].add(out * weight[:, None])
    return y, differs, excess, lead


def _hidden(params, sequences, cfg, chosen, keep=()):
    """sequences: a list of token arrays [T_i]; chosen [L, total tokens]
    -> the last block's output of each, before the final norm; the
    routing report {differs, excess, lead}, each [L, total tokens], the
    sequences' tokens side by side; and, for each layer in ``keep``,
    what `_attention` keeps of each sequence."""
    hq, hkv, dh, rot, theta = _dims(cfg)
    xs = [jnp.take(params["embed"], t, axis=0).astype(F32)
          for t in sequences]
    cuts = np.cumsum([len(t) for t in sequences])[:-1]
    report, kept = [], {}
    for i in range(cfg["num_hidden_layers"]):
        w = _layer(params, i)
        halves = [_attention(x, w, eps=cfg["rms_norm_eps"], theta=theta,
                             hq=hq, hkv=hkv, dh=dh, rot=rot) for x in xs]
        if i in keep:
            kept[i] = [h[2] for h in halves]
        g = jnp.concatenate([h[1] for h in halves])
        y, *about = _experts(
            g, w, lambda name, e, i=i: params["layers"][name][i, e],
            chosen[i], cfg)
        report.append([np.asarray(a) for a in about])
        xs = [h[0] + part for h, part in zip(halves, jnp.split(y, cuts))]
    differs, excess, lead = (np.stack(col) for col in zip(*report))
    return xs, {"differs": differs, "excess": excess, "lead": lead}, kept


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, ln_out, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, ln_out, eps) @ rows.astype(F32).T


def _head(x, params, cfg):
    """x [R, d] -> logits [R, V] over the tied embedding, a block of
    vocabulary rows at a time."""
    embed, v = params["embed"], params["embed"].shape[0]
    return jnp.concatenate(
        [_head_block(x, params["ln_out"], embed[at:at + _VOCAB_BLOCK],
                     eps=cfg["rms_norm_eps"])
         for at in range(0, v, _VOCAB_BLOCK)], axis=-1)


def _flat_choices(chosen, tokens, ends, cfg):
    b, t = tokens.shape
    if chosen is None:
        chosen = np.full((cfg["num_hidden_layers"], b, t, 1), -1, np.int32)
    chosen = np.asarray(chosen)[..., 0]
    return jnp.asarray(np.concatenate(
        [chosen[:, i, :n] for i, n in enumerate(ends)], axis=1))


def routed_logits_at(params, tokens, rows, cfg, chosen=None):
    """Float32 logits [len(rows), V] at the (sequence, position) pairs
    ``rows`` from a full causal forward pass over tokens [B, T], each
    sequence read up to its last row; and the routing report.

    ``chosen`` [L, B, T, 1]: the expert the SYSTEM chose (-1: none
    known, the reference uses its own). The report's arrays are
    [L, B, T] (zero past a sequence's last row): ``differs``,
    ``excess`` and ``lead`` as `_choice` defines them."""
    tokens = np.asarray(tokens)
    b, t = tokens.shape
    ends = [max(p for s, p in rows if s == i) + 1 for i in range(b)]
    xs, flat, _ = _hidden(
        params, [jnp.asarray(tokens[i, :n]) for i, n in enumerate(ends)],
        cfg, _flat_choices(chosen, tokens, ends, cfg))
    report = {}
    for name, a in flat.items():
        full = np.zeros((cfg["num_hidden_layers"], b, t), a.dtype)
        for i, part in enumerate(np.split(a, np.cumsum(ends)[:-1], axis=1)):
            full[:, i, :ends[i]] = part
        report[name] = full
    picked = jnp.stack([xs[s][p] for s, p in rows])
    return _head(picked, params, cfg), report


def logits_at(params, tokens, rows, cfg):
    """As the dense families' reference: the reference's own routing."""
    return routed_logits_at(params, tokens, rows, cfg)[0]


def kept_at(params, tokens, layers, cfg, chosen=None):
    """What the system keeps of ONE sequence tokens [T] in each layer of
    ``layers``, from a full forward pass (the system's experts followed
    where ``chosen`` [L, 1, T, 1] gives them): {layer: {"u", "a" [T, C]:
    the latents before the convolutions and the first convolution's
    output; "v_next" [T, Hkv dh / 2]: ``h_t W_v2``, the value half that
    token t + 1 takes; "k", "v" [T, Hkv, dh]: the token's rows after
    step 6}}. A slot's tail after token t is ``u[t] ++ a[t] ++
    v_next[t]``."""
    tokens = np.asarray(tokens).reshape(1, -1)
    flat = _flat_choices(chosen, tokens, [tokens.shape[1]], cfg)
    kept = _hidden(params, [jnp.asarray(tokens[0])], cfg, flat,
                   keep=tuple(layers))[2]
    return {i: {k: np.asarray(v) for k, v in kept[i][0].items()}
            for i in layers}


def router_probs(params, layer: int, g, cfg):
    """The router of ``layer`` alone on an input handed to it: g [N, d]
    float32 -> p [N, E]. What the program's router is held to on the
    program's OWN input, so that the precision of its arithmetic is
    read apart from the stream's."""
    return _router(jnp.asarray(g, F32), _layer(params, layer),
                   eps=cfg["rms_norm_eps"])
