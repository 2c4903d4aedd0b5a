"""Builder ``dense_llama``: a dense decoder of the Llama layer equations
(RMSNorm, rotate-half RoPE, grouped-query attention, SwiGLU) through
``ray_tpu/models/llama.py`` untouched. The builder of every
configuration file that names no other.

A BUILDER is what belongs to one model family, a file of its own found
by a configuration file's optional ``builder`` key (as drivers, kinds
and metric readers are found by their names): ``config(c, **overrides)``
makes the program's configuration from the file's sizes,
``init_params(cfg, seed)`` the weights on the device, and ``reference``
is the family's plain reference, a module under ``benchmark/reference/``
with ``logits_at(params, tokens, rows, c)`` and, where it trains,
``loss(params, tokens, c)``. A later PR with another family (experts,
latent attention) adds a builder and a reference and edits no driver.
"""

from __future__ import annotations

import functools

from benchmark.reference import dense_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as a plain
    `LlamaConfig`: another family through the same code, no width
    changed, no edit to ``models/``."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    head_dim = c.get("head_dim", c["hidden_size"] // c["num_attention_heads"])
    if head_dim * c["num_attention_heads"] != c["hidden_size"]:
        raise ValueError("LlamaConfig ties head_dim to hidden_size / heads")
    if c.get("sliding_window") or c.get("rope_scaling"):
        raise ValueError("sliding windows and scaled RoPE are not in "
                         "models/llama.py")
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]],
        tie_embeddings=c["tie_word_embeddings"], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed."""
    import jax

    from ray_tpu.models import llama

    return jax.jit(functools.partial(llama.init_params, cfg))(
        jax.random.PRNGKey(seed))
