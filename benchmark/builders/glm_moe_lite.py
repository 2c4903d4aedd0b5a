"""Builder ``glm_moe_lite``: the GLM-4.7-Flash family — multi-head
latent attention over a latent cache, one leading dense layer, then
sigmoid-routed experts without drops — through
``ray_tpu/models/glm_moe_lite.py``. Its plain reference is
``benchmark/reference/mla_moe_decoder.py``, written from the published
equations and independent of that module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

import functools

from benchmark.reference import mla_moe_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `GlmMoeLiteConfig`; no width changed."""
    import jax.numpy as jnp

    from ray_tpu.models import glm_moe_lite

    if c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("group-limited routing is not in "
                         "models/glm_moe_lite.py")
    if c.get("rope_scaling") or c["partial_rotary_factor"] != 1:
        raise ValueError("scaled or partial RoPE is not in "
                         "models/glm_moe_lite.py")
    if c["tie_word_embeddings"] or c["num_nextn_predict_layers"]:
        raise ValueError("a tied head and the multi-token-prediction "
                         "module are not in models/glm_moe_lite.py")
    return glm_moe_lite.GlmMoeLiteConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["n_routed_experts"],
        n_experts_per_tok=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        norm_topk_prob=c["norm_topk_prob"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed."""
    import jax

    from ray_tpu.models import glm_moe_lite

    return jax.jit(functools.partial(glm_moe_lite.init_params, cfg))(
        jax.random.PRNGKey(seed))
