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

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from ray_tpu import ops
from ray_tpu.models import llama


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
GLUE = {
    "rtpu_fused_rms_norm": (
        lambda x, s: ops.fused_rms_norm(x, s, interpret=True),
        _X, jax.ShapeDtypeStruct((128,), F32)),
    "rtpu_fused_rms_norm_residual": (
        lambda x, r, s: ops.fused_rms_norm_residual(x, r, s, interpret=True),
        _X, _X, jax.ShapeDtypeStruct((128,), F32)),
    "rtpu_fused_qk_rope": (
        lambda q, k, p: ops.fused_qk_rope(q, k, p, interpret=True),
        _Q, _K, _POS),
    "rtpu_fused_swiglu": (
        lambda g, u: ops.fused_swiglu(g, u, interpret=True), _X, _X),
}


@pytest.mark.parametrize("name", GLUE)
def test_glue_kernel_carries_its_name(name):
    fn, *args = GLUE[name]
    assert _kernels(fn, *args) == [(name, {"kernel": name})]


@pytest.mark.parametrize("knob, name", [
    ("use_decode_kernel", "rtpu_decode_attention"),
    ("paged_decode", "rtpu_paged_decode_attention"),
])
def test_decode_chunk_carries_its_attention_kernels_name(knob, name):
    """The engine's own program: the one kernel of the scanned layer
    body, called once a layer for all slots, is the named one."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg = dataclasses.replace(llama.tiny_config(max_seq_len=64),
                              **{knob: "interpret"})
    loop = DecodeLoop(cfg, max_len=64, chunk=2)
    slots = 2
    params = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: llama.init_kv_cache(cfg, slots, 64))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    kernels = _kernels(
        loop.decode_chunk, params, cache,
        jax.ShapeDtypeStruct((slots, 1), jnp.int32), vec, vec, vec,
        jax.ShapeDtypeStruct((slots,), jnp.bool_))
    assert kernels and all(k == (name, {"kernel": name}) for k in kernels)
