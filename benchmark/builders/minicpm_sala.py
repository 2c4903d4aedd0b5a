"""Builder ``minicpm_sala``: the MiniCPM-SALA family — layers of
block-sparse attention that chooses its own rows among layers of
lightning linear attention, pre-norm muP blocks — through
``ray_tpu/models/minicpm_sala.py``. Its plain reference is
``benchmark/reference/sparse_linear_decoder.py``, written from the
published equations and independent of that module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

import functools

from benchmark.reference import sparse_linear_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `MiniCPMSalaConfig`; no width changed."""
    import jax.numpy as jnp

    from ray_tpu.models import minicpm_sala
    from ray_tpu.ops.sparse_attention import Selection

    kinds = tuple(c["mixer_types"][:c["num_hidden_layers"]])
    if len(kinds) != c["num_hidden_layers"]:
        raise ValueError("mixer_types is shorter than num_hidden_layers")
    if c["lightning_nkv"] != c["lightning_nh"]:
        raise ValueError("lightning key heads in groups are not in "
                         "models/minicpm_sala.py")
    if (not c["lightning_use_rope"] or not c["qk_norm"]
            or not c["use_output_gate"] or not c["use_output_norm"]
            or not c["attn_use_output_gate"] or c["attention_bias"]
            or c["tie_word_embeddings"]
            or c["lightning_scale"] != "1/sqrt(d)"):
        raise ValueError("models/minicpm_sala.py is written for the "
                         "published switches: rotary on the lightning "
                         "layers, q/k norm, output norm and gates, no "
                         "bias, an untied head")
    s = c["sparse_config"]
    buckets = c["driver_args"]["engine"]["prompt_buckets"]
    if any(b % s["kernel_stride"] for b in buckets):
        raise ValueError("a prefill chunk must begin at a multiple of "
                         f"kernel_stride: prompt_buckets {buckets}")
    return minicpm_sala.MiniCPMSalaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        mixer_types=kinds, n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        lightning_heads=c["lightning_nh"],
        lightning_head_dim=c["lightning_head_dim"],
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), sparse_rope=c["attn_use_rope"],
        scale_emb=float(c["scale_emb"]),
        scale_depth=float(c["scale_depth"]),
        mup_denominator=c["mup_denominator"],
        dim_model_base=c["dim_model_base"], norm_eps=c["rms_norm_eps"],
        selection=Selection(
            kernel=s["kernel_size"], stride=s["kernel_stride"],
            block=s["block_size"], init_blocks=s["init_blocks"],
            window=s["window_size"], topk=s["topk"],
            dense_len=s["dense_len"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed."""
    import jax

    from ray_tpu.models import minicpm_sala

    return jax.jit(functools.partial(minicpm_sala.init_params, cfg))(
        jax.random.PRNGKey(seed))


def first_state(cfg, cache, slot: int):
    """The first lightning layer's state of ``slot`` in the program's
    cache, as the reference has it: [H, D(k), D(v)] float32."""
    return cache["state"][0, slot]
