"""Builder ``dots3_note``: the dots3-note family as one chip's share of
an expert-parallel replica — multi-head latent attention at two
geometries (full layers whose queries choose single rows by a learned
indexer, sliding layers that keep a ring of their last rows), a
headwise output gate, and routed experts of which this chip holds a
run — through ``ray_tpu/models/dots3_note.py``. Its plain reference is
``benchmark/reference/dsa_swa_moe_decoder.py``, written from the layer
equations of issue 42 and independent of that module.

The program's module is imported where it is used: `manifest.check`
loads every cell's builder, and on a program that has no such module
(the parent of the PR that added this family) the other cells must
still run. A cell of this family ends there at once, with the import's
error and a non-zero exit, when the driver asks for its configuration.
"""

from __future__ import annotations

import functools

from benchmark.reference import dsa_swa_moe_decoder as reference  # noqa: F401


def config(c: dict, **overrides):
    """The configuration file's (Hugging Face) keys as the program's
    `Dots3NoteConfig`; no width changed. ``n_routed_experts`` counts the
    experts HELD; the router's width is the published count."""
    import jax.numpy as jnp

    from ray_tpu.models import dots3_note

    n = c["num_hidden_layers"]
    kinds = tuple(c["layer_types"][:n])
    if len(kinds) != n:
        raise ValueError("layer_types is shorter than num_hidden_layers")
    if (c["attention_gate_type"] != "headwise"
            or c["swa_attention_gate_type"] != "headwise"
            or c["attention_bias"] or c["tie_word_embeddings"]
            or c["rope_scaling"] is not None or c["hidden_act"] != "silu"
            or c["scoring_func"] != "sigmoid"
            or c["topk_method"] != "noaux_tc" or c["moe_layer_freq"] != 1
            or c["num_key_value_heads"] != c["num_attention_heads"]
            or c["swa_num_key_value_heads"] != c["swa_num_attention_heads"]):
        raise ValueError("models/dots3_note.py is written for the published "
                         "switches: headwise gates, no bias, an untied "
                         "head, unscaled RoPE, sigmoid noaux_tc routing in "
                         "every layer after the dense ones, MLA with as "
                         "many key heads as query heads")
    first, count, total = reference.held_experts(c)

    def geometry(kind):
        g = reference.geometry(c, kind)
        return dots3_note.LatentGeometry(
            n_heads=g["heads"], q_lora_rank=g["rq"], kv_lora_rank=g["rkv"],
            qk_nope_head_dim=g["nope"], qk_rope_head_dim=g["rope"],
            v_head_dim=g["v"], rope_theta=g["theta"])

    return dots3_note.Dots3NoteConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=kinds, n_dense_layers=c["first_k_dense_replace"],
        full=geometry(dots3_note.FULL), sliding=geometry(dots3_note.SLIDING),
        index_heads=c["index_n_heads"], index_head_dim=c["index_head_dim"],
        index_topk=c["index_topk"], window=c["sliding_window_size"],
        lora_rescale=c["apply_mla_qkv_lora_rescale"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_experts=total, held_experts=(first, count),
        n_experts_per_tok=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=c["norm_topk_prob"],
        max_seq_len=c["max_position_embeddings"],
        norm_eps=c["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            c["torch_dtype"]], **overrides)


def init_params(cfg, seed: int):
    """The model's weights on the device, in the type they are served
    in, in ONE jitted call from the seed."""
    import jax

    from ray_tpu.models import dots3_note

    return jax.jit(functools.partial(dots3_note.init_params, cfg))(
        jax.random.PRNGKey(seed))
