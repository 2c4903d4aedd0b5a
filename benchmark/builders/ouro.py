"""Builder ``ouro``: the looped decoder of the Ouro family — ONE stack
of sandwich-norm blocks that every token crosses ``total_ut_steps``
times with the same weights, each pass keeping K/V rows of its own, an
exit gate read after every pass — through ``ray_tpu/models/ouro.py``.
Its plain reference is ``benchmark/reference/looped_decoder.py``,
written from the layer equations of issue 64 and independent of that
module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

from benchmark.reference import looped_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `OuroConfig`; no width changed."""
    import jax.numpy as jnp

    from ray_tpu.models import ouro

    if (c["hidden_act"] != "silu" or c["tie_word_embeddings"]
            or c["rope_scaling"] or c["sliding_window"]
            or c["use_sliding_window"]
            or set(c["layer_types"]) != {"full_attention"}
            or len(c["layer_types"]) != c["num_hidden_layers"]):
        raise ValueError("models/ouro.py is written for the published "
                         "switches: SiLU, an untied head, plain RoPE, full "
                         "attention in every layer")
    return ouro.OuroConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], n_loops=c["total_ut_steps"],
        exit_threshold=float(c["early_exit_threshold"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed. The reference reads the
    weights this returns."""
    import jax

    from ray_tpu.models import ouro

    return jax.jit(lambda key: ouro.init_params(cfg, key))(
        jax.random.PRNGKey(seed))
