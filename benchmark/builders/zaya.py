"""Builder ``zaya``: the ZAYA1 family — attention inside a compressed
latent with convolutional mixing over time (CCA), whose two-token tail
lives beside the K/V rows, and experts of which a router MLP picks ONE
a token — through ``ray_tpu/models/zaya.py``. Its plain reference is
``benchmark/reference/cca_top1_decoder.py``, written from the layer
equations of issue 40 and independent of that module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

import functools

from benchmark.reference import cca_top1_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `ZayaConfig`; no width changed."""
    import jax.numpy as jnp

    from ray_tpu.models import zaya

    n = c["num_hidden_layers"]
    if c["layer_types"] != ["hybrid"] * n:
        raise ValueError("models/zaya.py has one kind of layer (`hybrid`: "
                         "attention then experts), one entry a layer")
    if c["sliding_window"] or c["attention_bias"] or c["lm_head_bias"]:
        raise ValueError("a sliding window and biases on the projections "
                         "or the head are not in models/zaya.py")
    if not c["tie_word_embeddings"] or c["hidden_act"] != "silu":
        raise ValueError("models/zaya.py has a tied head and SwiGLU experts")
    rope = c["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default" or (
            rope["partial_rotary_factor"] != c["partial_rotary_factor"]):
        raise ValueError("scaled RoPE is not in models/zaya.py")
    return zaya.ZayaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=n,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        cca_time0=c["cca_time0"], cca_time1=c["cca_time1"],
        rotary_dim=int(c["partial_rotary_factor"] * c["head_dim"]),
        rope_theta=float(rope["rope_theta"]), n_experts=c["num_experts"],
        n_experts_per_tok=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"], router_d=c["router_hidden_size"],
        max_seq_len=c["max_position_embeddings"], norm_eps=c["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed."""
    import jax

    from ray_tpu.models import zaya

    return jax.jit(functools.partial(zaya.init_params, cfg))(
        jax.random.PRNGKey(seed))
