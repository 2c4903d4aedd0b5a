"""Builder ``granite_hybrid``: the dense ``granitemoehybrid`` family
(Granite-4.0-H: Mamba-2 layers with a fixed-size float32 state among a
few position-free grouped-query attention layers, pre-norm blocks with
the publisher's multipliers, a tied head) through
``ray_tpu/models/granite_hybrid.py``. Its plain reference is
``benchmark/reference/mamba2_gqa_decoder.py``, written from the
published equations, held to the publisher's own code
(``tests/test_granite_hybrid_published.py``) and independent of that
module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

import functools

from benchmark.reference import mamba2_gqa_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `GraniteHybridConfig`; no width changed."""
    import jax.numpy as jnp

    from ray_tpu.models import granite_hybrid

    kinds = tuple(c["layer_types"][:c["num_hidden_layers"]])
    if c["num_local_experts"] or c["num_experts_per_tok"]:
        raise ValueError("routed experts are not in "
                         "models/granite_hybrid.py")
    if (c["position_embedding_type"] != "nope" or c["attention_bias"]
            or c["mamba_proj_bias"] or not c["mamba_conv_bias"]
            or not c["tie_word_embeddings"] or c["mamba_n_groups"] != 1):
        raise ValueError("rotary embedding, projection biases, a "
                         "convolution without bias, an untied head and "
                         "several B/C groups are not in "
                         "models/granite_hybrid.py")
    if (c["mamba_n_heads"] * c["mamba_d_head"]
            != c["mamba_expand"] * c["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is not "
                         "mamba_expand x hidden_size")
    return granite_hybrid.GraniteHybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_kinds=kinds, n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        mamba_heads=c["mamba_n_heads"], mamba_head_dim=c["mamba_d_head"],
        mamba_state=c["mamba_d_state"], conv_width=c["mamba_d_conv"],
        mamba_chunk=c["mamba_chunk_size"],
        d_ff=c["shared_intermediate_size"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=c["residual_multiplier"],
        attention_multiplier=c["attention_multiplier"],
        logits_scaling=float(c["logits_scaling"]),
        max_seq_len=c["max_position_embeddings"],
        norm_eps=c["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed."""
    import jax

    from ray_tpu.models import granite_hybrid

    return jax.jit(functools.partial(granite_hybrid.init_params, cfg))(
        jax.random.PRNGKey(seed))


def first_state(cfg, cache, slot: int):
    """The first (Mamba) layer's state of ``slot`` in the program's
    cache, as the reference has it: [H, P, N] float32."""
    from ray_tpu.ops import mamba2

    return mamba2.unpack_state(cache["ssm"][0, slot][None],
                               cfg.state_group)[0]
