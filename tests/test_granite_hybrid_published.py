"""The benchmark's plain reference of the dense ``granitemoehybrid``
family held to the PUBLISHER's own modelling code
(``transformers.models.granitemoehybrid``), on a tiny configuration with
seeded float32 weights, on the CPU. It is this test that makes
``benchmark/reference/mamba2_gqa_decoder.py`` the published model and
not this repository's reading of it. A machine without that code (or
without torch) skips; nothing is downloaded.
"""

from __future__ import annotations

import numpy as np
import pytest

hf = pytest.importorskip("transformers.models.granitemoehybrid")
torch = pytest.importorskip("torch")

import jax.numpy as jnp  # noqa: E402

from benchmark.reference import mamba2_gqa_decoder as reference  # noqa: E402

LAYERS = ["mamba", "mamba", "attention", "mamba", "attention", "mamba"]
# The three multipliers and the divisor away from 1, and a chunk that
# the sequences' length (19) is no multiple of.
PUBLISHED = dict(
    vocab_size=211, hidden_size=32, num_hidden_layers=len(LAYERS),
    layer_types=LAYERS, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=48, intermediate_size=48, num_local_experts=0,
    num_experts_per_tok=0, mamba_n_heads=4, mamba_d_head=16,
    mamba_expand=2, mamba_d_state=8, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
    position_embedding_type="nope", embedding_multiplier=3.0,
    residual_multiplier=0.37, attention_multiplier=0.09, logits_scaling=2.5,
    rms_norm_eps=1e-5, tie_word_embeddings=True, attention_bias=False,
    attention_dropout=0.0, max_position_embeddings=64,
    initializer_range=0.2, hidden_act="silu")


def _tree(model, cfg: dict) -> dict:
    """The publisher's weights in the reference's (the system's) tree:
    matrices transposed to input-major, norm gains as offsets from one,
    the layers of a kind stacked."""
    get = lambda t: np.asarray(t.detach().numpy(), np.float32)
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], cfg["hidden_size"] // heads
    kinds = {"mamba": [], "attention": []}
    for kind, layer in zip(cfg["layer_types"], model.model.layers):
        w = {"ln_mix": get(layer.input_layernorm.weight) - 1.0,
             "ln_mlp": get(layer.post_attention_layernorm.weight) - 1.0,
             "w_ff_in": get(layer.shared_mlp.input_linear.weight).T,
             "w_ff_out": get(layer.shared_mlp.output_linear.weight).T}
        if kind == "mamba":
            m = layer.mamba
            w_in = get(m.in_proj.weight).T
            w.update(w_in=w_in[:, :-m.num_heads], w_dt=w_in[:, -m.num_heads:],
                     conv_w=get(m.conv1d.weight)[:, 0, :],
                     conv_b=get(m.conv1d.bias), a_log=get(m.A_log),
                     dt_bias=get(m.dt_bias), d_skip=get(m.D),
                     ln_gate=get(m.norm.weight) - 1.0,
                     w_out=get(m.out_proj.weight).T)
        else:
            a = layer.self_attn
            w.update(wq=get(a.q_proj.weight).T.reshape(d, heads, hd),
                     wk=get(a.k_proj.weight).T.reshape(d, kv, hd),
                     wv=get(a.v_proj.weight).T.reshape(d, kv, hd),
                     wo=get(a.o_proj.weight).T.reshape(heads, hd, d))
        kinds[kind].append(w)
    stack = lambda layers: {k: jnp.asarray(np.stack([w[k] for w in layers]))
                            for k in layers[0]}
    return {"embed": jnp.asarray(get(model.model.embed_tokens.weight)),
            "mamba": stack(kinds["mamba"]),
            "attention": stack(kinds["attention"]),
            "ln_out": jnp.asarray(get(model.model.norm.weight) - 1.0)}


@pytest.fixture(scope="module")
def published():
    torch.manual_seed(46)
    config = hf.GraniteMoeHybridConfig(**PUBLISHED)
    model = hf.GraniteMoeHybridForCausalLM(config).to(torch.float32).eval()
    with torch.no_grad():
        # Every parameter a trained model moves off its initial value:
        # gains off one, the step, the decay and the skip off the
        # publisher's constants, the convolution's bias off zero.
        for name, p in model.named_parameters():
            if p.ndim == 1:
                p.add_(0.3 * torch.randn_like(p))
    return model


def test_published_config_has_the_mechanisms(published):
    layers = published.model.layers
    assert [l.mamba is not None for l in layers] == [
        k == "mamba" for k in LAYERS]
    assert not any(l.has_experts for l in layers)
    assert published.model.rotary_emb is None
    assert published.lm_head.weight is published.model.embed_tokens.weight


@pytest.mark.parametrize("length", [19, 8, 3])
def test_reference_is_the_published_model(published, length):
    rng = np.random.default_rng(length)
    tokens = rng.integers(0, PUBLISHED["vocab_size"], (2, length))
    with torch.no_grad():
        want = published(torch.as_tensor(tokens)).logits.numpy()
    rows = [(s, p) for s in range(2) for p in range(length)]
    got = np.asarray(reference.logits_at(
        _tree(published, PUBLISHED), tokens, rows, PUBLISHED))
    got = got.reshape(want.shape)
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 1e-4, rel


def test_a_dropped_mechanism_shows(published):
    """The comparison can fail: each mechanism of the issue's list,
    dropped from the reference's side, moves the logits by far more
    than the limit."""
    tokens = np.random.default_rng(0).integers(
        0, PUBLISHED["vocab_size"], (1, 19))
    with torch.no_grad():
        want = published(torch.as_tensor(tokens)).logits.numpy()[0]
    rows = [(0, p) for p in range(19)]
    tree = _tree(published, PUBLISHED)

    def off(cfg=PUBLISHED, **mamba):
        t = dict(tree, mamba=dict(tree["mamba"], **mamba))
        got = np.asarray(reference.logits_at(t, tokens, rows, cfg))
        return np.linalg.norm(got - want) / np.linalg.norm(want)

    zeros = lambda k: jnp.zeros_like(tree["mamba"][k])
    assert off() < 1e-4
    assert off(d_skip=zeros("d_skip")) > 1e-2
    assert off(conv_b=zeros("conv_b")) > 1e-2
    assert off(dict(PUBLISHED, residual_multiplier=1.0)) > 1e-2
    assert off(dict(PUBLISHED, attention_multiplier=8 ** -0.5)) > 1e-3
    assert off(dict(PUBLISHED, embedding_multiplier=1.0)) > 1e-2
    assert off(dict(PUBLISHED, logits_scaling=1.0)) > 1e-2
