"""Every Pallas kernel of ``ray_tpu/ops`` carries a stable name: the
``name=`` of its ``pl.pallas_call`` (Mosaic's ``kernel_name``, the call
site's named scope) and the same string as ``kernel_metadata``, the one
field that still identifies the kernel in a device trace where a
batching loop renames the instruction ``closed_call.N`` (the engine's
``vmap``ped step did, until PR 26; its batched step calls the kernel
directly and the instruction is ``rtpu_decode_attention.N``).
Here on the jaxpr; ``tests/test_chip_compile.py`` checks the text the
chip's compiler produces."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu import ops
from ray_tpu.ops import dsa_prefill, swa_prefill


def _pallas_eqns(jaxpr):
    """Every pallas_call equation, through scans, conds and calls."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def _kernels(fn, *args):
    closed = jax.make_jaxpr(fn)(*args)
    return [(e.params["name"], dict(e.params["metadata"] or {}))
            for e in _pallas_eqns(closed.jaxpr)]


F32 = jnp.float32
_X = jax.ShapeDtypeStruct((2, 8, 128), F32)
_Q = jax.ShapeDtypeStruct((2, 8, 4, 32), F32)
_K = jax.ShapeDtypeStruct((2, 8, 2, 32), F32)
_POS = jax.ShapeDtypeStruct((2, 8), jnp.int32)
_SCALE = jax.ShapeDtypeStruct((128,), F32)
_CACHE = jax.ShapeDtypeStruct((2, 2, 128, 128), F32)     # [B,KH,S,D]
# A dispatcher of ``ops/`` that takes ``interpret``: its kernel's name ->
# (the call, its arguments' shapes).
GLUE = {
    "rtpu_fused_rms_norm": (ops.fused_rms_norm, _X, _SCALE),
    "rtpu_fused_rms_norm_residual": (
        ops.fused_rms_norm_residual, _X, _X, _SCALE),
    "rtpu_fused_qk_rope": (ops.fused_qk_rope, _Q, _K, _POS),
    "rtpu_fused_swiglu": (ops.fused_swiglu, _X, _X),
    "rtpu_decode_attention": (
        functools.partial(ops.decode_attention, layout="bksd"),
        jax.ShapeDtypeStruct((2, 4, 128), F32), _CACHE, _CACHE,
        jax.ShapeDtypeStruct((2,), jnp.int32)),
    dsa_prefill.NAME: (
        functools.partial(dsa_prefill.dsa_prefill_attention, scale=0.1),
        jax.ShapeDtypeStruct((1, 8, 2, 192), F32),        # q [B,T,H,qk]
        jax.ShapeDtypeStruct((1, 128, 256), F32),         # rows [B,S,W]
        jax.ShapeDtypeStruct((1, 8, 128), jnp.bool_),     # keep [B,T,S]
        jax.ShapeDtypeStruct((128, 2, 128), F32),         # w_uk [r,H,nope]
        jax.ShapeDtypeStruct((128, 2, 128), F32),         # w_uv [r,H,v]
        jax.ShapeDtypeStruct((), jnp.int32)),             # rows_seen
    swa_prefill.NAME: (
        functools.partial(swa_prefill.swa_prefill_attention, reach=128,
                          scale=0.1),
        jax.ShapeDtypeStruct((1, 256, 2, 128), F32),      # q [B,T,H,qk]
        jax.ShapeDtypeStruct((1, 2, 384, 128), F32),      # k [B,H,reach+T,qk]
        jax.ShapeDtypeStruct((1, 2, 384, 128), F32),      # v [B,H,reach+T,v]
        jax.ShapeDtypeStruct((1, 256), jnp.int32),        # q_pos
        jax.ShapeDtypeStruct((1, 384), jnp.int32)),       # k_pos
}


@pytest.mark.parametrize("name", GLUE)
def test_glue_kernel_carries_its_name(name):
    fn, *args = GLUE[name]
    assert _kernels(functools.partial(fn, interpret=True), *args) \
        == [(name, {"kernel": name})]


@pytest.mark.parametrize("name", GLUE)
def test_glue_lowers_to_its_jnp_twin_off_the_tpu(name):
    """With ``interpret`` unset the dispatcher decides from the platform
    alone: off the TPU no ``pallas_call`` is left, so what the CPU
    tier-1 compares against is the twin and no flag has to force it."""
    assert jax.default_backend() != "tpu"
    fn, *args = GLUE[name]
    assert _kernels(fn, *args) == []


# A served family's configuration file -> the kernels of its decode
# step, under the names the benchmark's readers look for in a trace
# (``benchmark/metrics/*_ms_per_step.py``).
SERVED = {
    "mistral-7b-v0.3-l16": {"rtpu_decode_attention"},
    "glm-4.7-flash-l7": {"rtpu_mla_decode_attention"},
    "olmo-hybrid-7b-l16": {"rtpu_gdn_decode", "rtpu_decode_attention"},
    "minicpm-sala-l16": {"rtpu_sparse_decode_attention",
                         "rtpu_lightning_decode"},
    "zaya1-8b-l16": {"rtpu_decode_attention"},
    "dots3-note-prev-l5-ep8": {"rtpu_dsa_select",
                               "rtpu_dsa_decode_attention",
                               "rtpu_swa_decode_attention"},
    "granite-4.0-h-micro": {"rtpu_mamba2_decode", "rtpu_decode_attention"},
    "kimi-linear-48b-a3b-ep16": {"rtpu_kda_decode",
                                 "rtpu_mla_decode_attention"},
    "xing4.0-29b-a4b-ep8": {"rtpu_mhc_pre", "rtpu_mhc_post",
                            "rtpu_mla_decode_attention"},
    "ouro-2.6b": {"rtpu_decode_attention"},
}


@pytest.mark.parametrize("config", SERVED)
def test_decode_chunk_carries_its_attention_kernels_name(config):
    """The engine's own program, every served family's at its
    configuration file's rehearsal sizes with `interpret_kernels` (the
    one hook every family's configuration has): the kernels of the
    scanned layer bodies are the named ones, and no other."""
    from benchmark.harness import manifest
    from ray_tpu.serve.engine.decode_loop import DecodeLoop, serving_params

    suite = manifest.load()
    c = suite.config({"config": config})
    cfg = suite.builder(c).config({**c, **c["rehearse"]},
                                  interpret_kernels=True)
    loop = DecodeLoop(cfg, max_len=64, chunk=2)
    slots = 2
    params = jax.eval_shape(lambda: serving_params(
        cfg, cfg.model.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: cfg.model.init_kv_cache(cfg, slots, 64))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    kernels = _kernels(
        loop.decode_chunk, params, cache,
        jax.ShapeDtypeStruct((slots, 1), jnp.int32), vec, vec, vec,
        jax.ShapeDtypeStruct((slots,), jnp.bool_))
    assert {name for name, _ in kernels} == SERVED[config]
    assert all(meta == {"kernel": name} for name, meta in kernels)
