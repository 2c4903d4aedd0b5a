"""Builder ``xing_mhc``: the Xing4.0 family as one chip's share of an
expert-parallel replica — latent attention under YaRN and sigmoid-routed
experts of which this chip holds a run, every sub-layer inside a
residual of four streams mixed by Sinkhorn-normalised maps (mHC) —
through ``ray_tpu/models/xing_mhc.py``. Its plain reference is
``benchmark/reference/mhc_mla_moe_decoder.py``, written from the layer
equations of issue 54 and independent of that module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

from benchmark.reference import mhc_mla_moe_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `XingMhcConfig`; no width changed. ``n_routed_experts`` counts the
    experts HELD; the router's width is the published count."""
    import jax.numpy as jnp

    from ray_tpu.models import xing_mhc
    from ray_tpu.ops import mhc
    from ray_tpu.ops.rotary import YarnScaling

    if (c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc"
            or c["n_group"] != 1 or c["topk_group"] != 1
            or c["moe_layer_freq"] != 1 or c["num_nextn_predict_layers"]
            or c["tie_word_embeddings"] or c["hidden_act"] != "silu"
            or c["attention_bias"]
            or c["num_key_value_heads"] != c["num_attention_heads"]):
        raise ValueError("models/xing_mhc.py is written for the published "
                         "switches: sigmoid routing on score + bias in one "
                         "group in every layer after the dense ones, an "
                         "untied head, no multi-token block, no attention "
                         "bias, MLA with as many key heads as query heads")
    first, count, total = reference.held_experts(c)
    return xing_mhc.XingMhcConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_experts=total, held_experts=(first, count),
        n_experts_per_tok=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=c["norm_topk_prob"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]),
        rope_scaling=(YarnScaling.of(c["rope_scaling"])
                      if c["rope_scaling"] else None),
        norm_eps=c["rms_norm_eps"],
        mhc=mhc.MhcSpec(
            n=c["hc_mult"], sinkhorn_iters=c["hc_sinkhorn_iters"],
            hc_eps=c["hc_eps"], clamp_min=float(c["mhc_h_res_clamp_min"]),
            clamp_max=float(c["mhc_h_res_clamp_max"]),
            norm_eps=c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed. The reference reads the
    weights this returns."""
    import jax

    from ray_tpu.models import xing_mhc

    return jax.jit(lambda key: xing_mhc.init_params(cfg, key))(
        jax.random.PRNGKey(seed))
