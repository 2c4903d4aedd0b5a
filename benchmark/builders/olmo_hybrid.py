"""Builder ``olmo_hybrid``: the Olmo-Hybrid family — gated delta-rule
(linear-attention) layers with a fixed-size state among full-attention
layers, post-norm blocks — through ``ray_tpu/models/olmo_hybrid.py``.
Its plain reference is ``benchmark/reference/gated_delta_decoder.py``,
written from the published equations and independent of that module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

import functools

from benchmark.reference import gated_delta_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `OlmoHybridConfig`; no width changed."""
    import jax.numpy as jnp

    from ray_tpu.models import olmo_hybrid

    kinds = c["layer_types"][:c["num_hidden_layers"]]
    per = kinds.index("full_attention")
    period = ["linear_attention"] * per + ["full_attention"]
    if kinds != period * (len(kinds) // len(period)):
        raise ValueError("models/olmo_hybrid.py scans whole periods of "
                         f"linear layers and one full layer: {kinds}")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("grouped-query full layers are not in "
                         "models/olmo_hybrid.py")
    if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise ValueError("value heads in groups over a key head are not "
                         "in models/olmo_hybrid.py")
    if (c["rope_parameters"]["rope_theta"] is not None
            or c["tie_word_embeddings"] or c["attention_bias"]):
        raise ValueError("rotary embedding, a tied head and attention "
                         "biases are not in models/olmo_hybrid.py")
    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], linear_per_period=per,
        n_heads=c["num_attention_heads"],
        linear_heads=c["linear_num_value_heads"],
        linear_key_dim=c["linear_key_head_dim"],
        linear_value_dim=c["linear_value_head_dim"],
        conv_width=c["linear_conv_kernel_dim"],
        allow_neg_eigval=c["linear_allow_neg_eigval"],
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        norm_eps=c["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed."""
    import jax

    from ray_tpu.models import olmo_hybrid

    return jax.jit(functools.partial(olmo_hybrid.init_params, cfg))(
        jax.random.PRNGKey(seed))


def first_state(cfg, cache, slot: int):
    """The first (linear) layer's state of ``slot`` in the program's
    cache, as the reference has it: [H, dv, dk] float32."""
    from ray_tpu.ops import gated_delta

    return gated_delta.unpack_state(cache["state"][0, slot][None],
                                    cfg.state_group)[0]
