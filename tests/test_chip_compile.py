"""The chip's compiler, asked from the CPU sandbox.

The TPU compiler is installed here and compiles for a v5e that is
DESCRIBED, not attached: every Pallas kernel on the serve/train path at
Llama-1B and 8B head geometry, the engine's decode and prefill programs
at full `LLAMA3_1B` size, and the ``fsdp=2 x tp=2`` loss+grad on a mesh
of the described devices. Nothing runs, so this says nothing about
results or times (`chip_smoke.py` does, on the chip) — it catches what
the interpreter cannot: a block shape off the (8, 128) tiling, an op
Mosaic has no lowering for, a kernel XLA cannot partition, a program
that does not fit 16 GB.

Only one process may load the TPU library, and pytest-xdist workers
all import every test file: the topology is therefore described inside
a fixture (never at import time, in a ``skipif`` or a ``parametrize``
argument), every test compiles in its own process, and all of these
tests live in this ONE file so that one worker owns the library.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from ray_tpu import ops
from ray_tpu.models import llama
from ray_tpu.parallel.mesh import mesh_2d, param_shardings

# (n_heads, n_kv_heads, head_dim, d_model, d_ff) of Llama-3 1B and 8B.
GEOMETRY = {"1b": (32, 8, 64, 2048, 8192), "8b": (32, 8, 128, 4096, 14336)}
BATCH, SEQ = 8, 2048
# [B, S] of the three programs that call the glue kernels.
ROPE_SHAPES = {"train": (8, 2048), "prefill": (1, 512), "decode": (8, 1)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture
def chip(topo, monkeypatch):
    """One described chip's sharding, with the program's dispatchers
    steered onto their TPU branch. Traces made under the steering must
    not outlive it (and CPU traces made earlier must not be reused)."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _names_kernel(compiled, name: str) -> bool:
    """The kernel's name in what the chip's compiler printed: as
    ``kernel_metadata`` and as the instruction's own name, which is
    what a device trace shows for the operation (``<name>.N``: the
    engine's step calls the kernel directly, once a layer for all
    slots, so no batching loop renames it ``closed_call``)."""
    text = "".join(compiled.as_text().split())
    return (f'kernel_metadata={{"kernel":"{name}"}}' in text
            and f"%{name}." in text)


# ------------------------------------------------------------- kernels

@pytest.mark.parametrize("geom", GEOMETRY)
def test_decode_attention_compiles(chip, geom):
    h, kh, hd, _, _ = GEOMETRY[geom]
    c = _compile(
        functools.partial(ops.decode_attention, layout="bksd"),
        _sds(chip, (BATCH, h, hd)), _sds(chip, (BATCH, kh, SEQ, hd)),
        _sds(chip, (BATCH, kh, SEQ, hd)), _sds(chip, (BATCH,), jnp.int32))
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_decode_attention")


@pytest.mark.parametrize("geom", GEOMETRY)
def test_fused_rms_norm_kernels_compile(chip, geom):
    d = GEOMETRY[geom][3]
    x, s = _sds(chip, (BATCH, SEQ, d)), _sds(chip, (d,))
    c = _compile(ops.fused_rms_norm, x, s)
    assert _kernel_calls(c) == 1 and _names_kernel(c, "rtpu_fused_rms_norm")
    c = _compile(ops.fused_rms_norm_residual, x, x, s)
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_fused_rms_norm_residual")


@pytest.mark.parametrize("geom", GEOMETRY)
def test_fused_swiglu_compiles(chip, geom):
    f = GEOMETRY[geom][4]
    g = _sds(chip, (BATCH, SEQ, f))
    c = _compile(ops.fused_swiglu, g, g)
    assert _kernel_calls(c) == 1 and _names_kernel(c, "rtpu_fused_swiglu")


@pytest.mark.parametrize("shape", ROPE_SHAPES)
@pytest.mark.parametrize("geom", GEOMETRY)
def test_fused_qk_rope_compiles_forward_and_backward(chip, geom, shape):
    h, kh, hd, _, _ = GEOMETRY[geom]
    b, s = ROPE_SHAPES[shape]
    q, k = _sds(chip, (b, s, h, hd)), _sds(chip, (b, s, kh, hd))
    pos = _sds(chip, (b, s), jnp.int32)
    c = _compile(ops.fused_qk_rope, q, k, pos)
    assert _kernel_calls(c) == 1 and _names_kernel(c, "rtpu_fused_qk_rope")

    def loss(q, k, pos):
        oq, ok = ops.fused_qk_rope(q, k, pos)
        return (jnp.sum(oq.astype(jnp.float32))
                + jnp.sum(ok.astype(jnp.float32) ** 2))

    # The VJP is the same kernel at negated positions (forward + one
    # backward call; nothing falls back to apply_rope's autodiff).
    assert _kernel_calls(
        _compile(jax.grad(loss, argnums=(0, 1)), q, k, pos)) == 2


# (batch, heads, kv heads, head size) at SEQ: the two Llama geometries
# and a device's share of a layer of `smollm2.sft.fsdp2tp2` (32
# sequences over dp x fsdp = 2, 32 heads of 64 over tp = 2).
FLASH_SHAPES = {"1b": (BATCH, 32, 8, 64), "8b": (BATCH, 32, 8, 128),
                "smollm2_shard": (16, 16, 16, 64)}
# A row statistic broadcast along lanes in HBM on its way to a backward
# kernel (the library's dq wrapper wrote `di` out `block_k_major` lanes
# wide, f32[16,16,2048,1024]: 2.1 GB a layer; and `l`, `m`, `di` at 128).
_STAT_BROADCAST = re.compile(r"f32\[\d+,\d+,2048,\d+\]\S* broadcast\(")


@pytest.mark.parametrize("geom", FLASH_SHAPES)
def test_flash_attention_compiles_forward_and_backward(chip, geom):
    b, h, kh, hd = FLASH_SHAPES[geom]
    q = _sds(chip, (b, SEQ, h, hd))
    kv = _sds(chip, (b, SEQ, kh, hd))
    c = _compile(ops.full_causal_attention, q, kv, kv)
    assert _kernel_calls(c) == 1 and "%flash_attention." in c.as_text()

    def loss(q, k, v):
        return jnp.sum(ops.full_causal_attention(q, k, v)
                       .astype(jnp.float32))

    # Forward + the dkv and dq kernels (`ops/flash_attention.py`),
    # under the names a device trace shows them by.
    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    text = c.as_text()
    assert _kernel_calls(c) == 3
    for name in ("flash_mha_bwd_dkv", "flash_mha_bwd_dq"):
        assert f"%{name}." in text, name
    # The one statistic the backward reads is the forward kernel's own
    # output, and `di` is made inside the kernels.
    assert not _STAT_BROADCAST.findall(text)


# ------------------------------------------------------ whole programs

_CFG_1B = dataclasses.replace(llama.LLAMA3_1B, max_seq_len=SEQ)


def _abstract(sharding, fn, *args):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(fn, *args))


# Mistral-7B's widths (benchmark/configs/mistral-7b-v0.3-l16.json) at 2
# layers — the layer body is scanned, so its HLO is the 16-layer one's —
# with the serving cells' 32 slots of 1024 rows; and LLAMA3_1B whole.
_MISTRAL_2L = llama.LlamaConfig(
    vocab_size=32768, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq_len=1024, rope_theta=1e6)
ENGINES = {"mistral7b_2l": (_MISTRAL_2L, 32, 1024),
           "llama3_1b": (_CFG_1B, BATCH, SEQ)}


def _engine_args(chip, cfg, slots, rows):
    params = _abstract(chip, functools.partial(llama.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: llama.init_kv_cache(cfg, slots, rows))
    return params, cache


def _lower_decode_chunk(chip, loop, params, cache, slots):
    vec = _sds(chip, (slots,), jnp.int32)
    return loop.decode_chunk.lower(
        params, cache, _sds(chip, (slots, 1), jnp.int32), vec, vec, vec,
        _sds(chip, (slots,), jnp.bool_)).compile()


def _assert_cache_in_place(compiled, cache):
    """The program rewrites the cache it was given: its output aliases
    the donated input, and no computation it calls (the step loop, the
    layer loop, their fusions) copies an array of the cache's shape —
    a scatter that re-lays the cache out shows as such copies too.

    At a head size of 128 nothing else copies it either, and the
    temporaries stay below one cache. At 64 (LLAMA3_1B) the chip keeps
    ``[.., S, 64]`` with S minor (64 would be padded to 128 lanes), the
    kernel's copies want whole lane tiles, and the step slices a layer
    out and pads it a call (``ops/decode_attention.py``): a layer's
    copy, never the cache's, which only a kernel for that layout
    removes (PERF.md section 7)."""
    k = cache["k"]
    nbytes = 2 * k.size * k.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    shape = "bf16[" + ",".join(map(str, k.shape)) + "]"

    def copies(text):
        return [line for line in text.splitlines()
                if shape in line.split("(")[0]
                and " copy" in line.split("(")[0]]

    called, _, entry = compiled.as_text().partition("\nENTRY ")
    assert entry and copies(called) == []
    if k.shape[-1] % 128 == 0:
        assert copies(entry) == []
        assert mem.temp_size_in_bytes < nbytes
    else:
        assert len(copies(entry)) <= 4


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_decode_chunk_updates_the_cache_in_place(chip, engine):
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, slots, rows = ENGINES[engine]
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params, cache = _engine_args(chip, cfg, slots, rows)
    c = _lower_decode_chunk(chip, loop, params, cache, slots)
    # One kernel, in the scanned layer body (a program that does not
    # fit the chip's 16 GB is refused by the compile itself), called
    # once a layer for all slots and so under its own name.
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_decode_attention")
    _assert_cache_in_place(c, cache)


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_tick_prefill_updates_the_cache_in_place(chip, engine):
    """The tick's prefill (donated, hands back a token) beside the
    check's (functional, the bucket's logits): under one program name
    in a trace. No array of bucket x vocabulary leaves the tick's: its
    outputs are smaller than the check's by the logits."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, slots, rows = ENGINES[engine]
    bucket = 512
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params, cache = _engine_args(chip, cfg, slots, rows)
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, bucket), jnp.int32), scalar, scalar)
    lowered = loop.prefill_inplace.lower(*args, scalar)
    assert "jit_prefill" in lowered.as_text()[:200]
    tick = lowered.compile()
    _assert_cache_in_place(tick, cache)
    token, _ = jax.eval_shape(loop.prefill_inplace, *args, scalar)
    assert (token.shape, token.dtype) == ((1,), jnp.int32)
    assert f"[1,{bucket},{cfg.vocab_size}]" not in tick.as_text()
    functional = loop.prefill.lower(*args).compile()
    assert functional.memory_analysis().alias_size_in_bytes == 0
    logits = jax.eval_shape(loop.prefill, *args)[0]
    assert logits.shape == (1, bucket, cfg.vocab_size)
    assert (functional.memory_analysis().output_size_in_bytes
            - tick.memory_analysis().output_size_in_bytes
            >= 0.99 * logits.size * logits.dtype.itemsize)


@pytest.mark.parametrize("bucket", [128, 256])
def test_engine_paired_tick_prefill_updates_the_cache_in_place(chip, bucket):
    """The tick's prefill for TWO waiting prompts (`prefill_pair`:
    tokens [2, Pb], two slots, an index and a last row each) at
    Mistral's widths: under the tick prefill's program name, both
    slots' rows rewritten where they lie, two 4-byte tokens out and no
    bucket of logits."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, slots, rows = ENGINES["mistral7b_2l"]
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params, cache = _engine_args(chip, cfg, slots, rows)
    two = _sds(chip, (2,), jnp.int32)
    args = (params, cache, _sds(chip, (2, bucket), jnp.int32), two, two, two)
    lowered = loop.prefill_pair.lower(*args)
    assert "jit_prefill" in lowered.as_text()[:200]
    pair = lowered.compile()
    _assert_cache_in_place(pair, cache)
    tokens, _ = jax.eval_shape(loop.prefill_pair, *args)
    assert [(t.shape, t.dtype) for t in tokens] == [((1,), jnp.int32)] * 2
    assert f"{bucket},{cfg.vocab_size}]" not in pair.as_text()


# ------------------------------------- the latent cache and the experts

# GLM-4.7-Flash's widths (benchmark/configs/glm-4.7-flash-l7.json) with
# the dense layer and 2 of its expert layers (both stacks are scanned,
# so the HLO is the 7-layer one's), the cell's 32 slots of 4096 rows.
def _glm_3l():
    from ray_tpu.models import glm_moe_lite

    return glm_moe_lite, glm_moe_lite.GlmMoeLiteConfig(n_layers=3,
                                                       max_seq_len=4096)


# (slots, rows a slot, heads, with ``keep``) of the cells that call the
# latent kernel over rows of 640 columns, 512 of them the values.
MLA_CELLS = {"glm47flash.code.flood": (32, 4096, 20, False),
             "xing4.rag.flood": (32, 2048, 32, False),
             "kimilinear.reason.flood": (64, 2048, 32, False),
             "dots3.longdoc.flood": (16, 32768, 128, True)}


@pytest.mark.parametrize("cell", sorted(MLA_CELLS))
def test_mla_decode_attention_compiles(chip, cell):
    """The latent row at the width the cache holds it (576 values
    padded to 640: five whole lane tiles) at the four cells' slots,
    rows and heads: one kernel, under its name, the layer picked out of
    the whole cache, which stays in HBM (no copy, no temporaries), the
    block found from the shapes, and the kernel's buffers inside the
    compiler's own VMEM limit (none is asked for)."""
    from ray_tpu.ops.mla_decode import mla_block_rows, mla_decode_attention

    glm, cfg = _glm_3l()
    assert (cfg.cache_row_values, cfg.cache_row_dim) == (576, 640)
    slots, rows, heads, kept = MLA_CELLS[cell]
    name = "rtpu_dsa_decode_attention" if kept else \
        "rtpu_mla_decode_attention"
    args = [_sds(chip, (slots, heads, 640)), _sds(chip, (3, slots, rows, 640)),
            _sds(chip, (slots,), jnp.int32), _sds(chip, (), jnp.int32)]
    if kept:
        args.append(_sds(chip, (slots, rows), jnp.bool_))
    c = _compile(
        lambda q, cache, lens, layer, keep=None: mla_decode_attention(
            q, cache, lens, layer=layer, v_dim=cfg.kv_lora_rank,
            scale=cfg.attn_scale, keep=keep, name=name), *args)
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, name)
    assert "vmem_limit_bytes" not in c.as_text()
    # Under ``keep`` the mask as float32, a block's tile a row.
    assert c.memory_analysis().temp_size_in_bytes < (
        2 ** 20 + kept * slots * rows * 4)
    assert mla_block_rows(rows, 640, 2, heads) == (512 if kept else 256)


def test_glm_decode_chunk_updates_the_latent_cache_in_place(chip):
    """The new family's step through the engine's own `decode_chunk`:
    the latent kernel once in each stack's scanned body, the expert
    products as the chip compiler's grouped matmul (three a layer), the
    donated cache aliased and no array of its shape copied."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    glm, cfg = _glm_3l()
    slots, rows = 32, 4096
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params = _abstract(chip, functools.partial(glm.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: glm.init_kv_cache(cfg, slots, rows))
    c = _lower_decode_chunk(chip, loop, params, cache, slots)
    text = c.as_text()
    assert text.count("%rtpu_mla_decode_attention.") >= 2
    assert len(re.findall(r"%ragged-dot-none[.\d]* = bf16\[128,", text)) == 3
    kv = cache["kv"]
    nbytes = kv.size * kv.dtype.itemsize
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    # No layer's experts are sliced out of their stack either (1.2 GB a
    # layer a step, once): the grouped product reads the stack whole.
    assert mem.temp_size_in_bytes < 2 ** 28
    shape = "bf16[" + ",".join(map(str, kv.shape)) + "]"
    assert not [line for line in text.splitlines()
                if shape in line.split("(")[0]
                and " copy" in line.split("(")[0]]
    # The chunk hands on the step's counters, not what a check reads of
    # it; the step whole (functional: a check's) compiles too, and
    # returns its logits and each token's experts.
    vec = _sds(chip, (slots,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache, _sds(chip, (slots, 1), jnp.int32),
        vec, vec, vec, _sds(chip, (slots,), jnp.bool_))
    assert len(out) == 8 and "experts" not in out[7]
    whole = (params, cache, _sds(chip, (slots, 1), jnp.int32), vec)
    loop.decode_step_whole.lower(*whole).compile()
    logits, _, counters, seen = jax.eval_shape(loop.decode_step_whole, *whole)
    assert logits.shape == (slots, cfg.vocab_size)
    assert set(counters) == set(out[7])
    assert seen["experts"].shape == (cfg.n_moe_layers, slots, 1,
                                     cfg.n_experts_per_tok)


def test_glm_tick_prefill_returns_one_row_of_logits(chip):
    """The tick's prefill at the largest bucket: the head reads one
    row and its argmax comes back (not 1.27 GB of [1, 4096, vocab]; the
    row itself from the check's twin), the flash kernel and the two
    grouped kernels are in it, the cache aliased; under the one name
    every family's tick prefill has in a trace."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    glm, cfg = _glm_3l()
    loop = DecodeLoop(cfg, max_len=4096, chunk=8)
    params = _abstract(chip, functools.partial(glm.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: glm.init_kv_cache(cfg, 32, 4096))
    scalar = _sds(chip, (), jnp.int32)
    lowered = loop.prefill_inplace.lower(
        params, cache, _sds(chip, (1, 4096), jnp.int32), scalar, scalar,
        scalar)
    assert "jit_prefill" in lowered.as_text()[:200]
    c = lowered.compile()
    out = jax.eval_shape(loop.prefill_inplace, params, cache,
                         _sds(chip, (1, 4096), jnp.int32), scalar, scalar,
                         scalar)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    # Beside token and cache the counters alone: the row of logits and
    # the experts that the functional twin (`prefill_last`, a check's)
    # returns stay behind.
    assert len(out) == 3 and "experts" not in out[2]
    row = jax.eval_shape(loop.prefill_last, params, cache,
                         _sds(chip, (1, 4096), jnp.int32), scalar, scalar,
                         scalar)
    assert row[0].shape == (1, cfg.vocab_size) and "experts" in row[3]
    text = c.as_text()
    # A bucket's rows (256 an expert) take the repo's grouped kernels,
    # which read the experts' stacks where they lie.
    assert "%flash_attention" in text and "%ragged-dot-none" not in text
    assert _names_kernel(c, "rtpu_grouped_swiglu")
    assert _names_kernel(c, "rtpu_grouped_matmul")
    assert _copies_of(text, {k: params["moe"][k]
                             for k in ("w_gate", "w_down")}) == []
    kv = cache["kv"]
    assert c.memory_analysis().alias_size_in_bytes >= (
        kv.size * kv.dtype.itemsize)


def _computations(text: str):
    """The compiled module's computations, each a list of lines in
    scheduled order."""
    return [c.split("\n") for c in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()",
                                              text)]


def _fsdp2_tp2_loss_and_grad(topo, cfg, batch):
    """(compiled loss-and-gradient on the four described chips, the
    parameters' shapes)."""
    mesh = mesh_2d(4, tp=2, devices=list(topo.devices))
    assert dict(mesh.shape)["fsdp"] == 2 and dict(mesh.shape)["tp"] == 2
    shapes = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, param_shardings(mesh, llama.param_logical_axes(cfg)))
    tokens = jax.ShapeDtypeStruct(
        (batch, SEQ), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), "sp")))

    def loss(p, t):
        return llama.loss_fn(p, t, cfg, mesh=mesh)[0]

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        c = jax.jit(jax.value_and_grad(loss)).lower(params, tokens).compile()
    return c, shapes


# policy: (Mosaic kernels, rope's calls among them, ring hops) a layer
# body pair. "nothing":
# forward, the remat's forward again, dq and dkv; 4 hops forward
# (q/k/v, wo, gate/up, w_down) + 7 backward, the remat's first three
# again and the four transposes (one travelling copy serves q, k and v
# in both directions). "attention" keeps q and k after rope, the
# kernel's output and its statistic: the backward runs the forward
# kernel no second time, and of the remat's q/k/v ring v's product
# alone (the hop stays: the transposed products need the other
# shard's rows of the normed stream, which nobody kept). Rope's kernel
# (`rtpu_fused_qk_rope`, q and k in one call) runs forward and backward
# under either policy, and in the remat's forward too where q and k
# are not kept: 3 + 2 and 4 + 3.
_REMAT_PROGRAMS = {"attention": (5, 2, 11), "nothing": (7, 3, 11)}


def _assert_no_pass_around_the_rope_kernel(text: str, layer_bodies, cfg):
    """A layer body holds, for rope, the kernel's calls and nothing of
    `apply_rope`: no cos or sin table, no float32 copy of a device's q
    or k (as [B, S, H, D], its half sequence or its halves of a head),
    no bf16 halves joined in a pass of their own. The products are
    written with a row's heads side by side and reach the forward call
    through the ring's assembly alone (its [B, S, H·D] as it is: no
    copy, no reshape that moves a byte); the calls' results leave flat
    too, which is what the policy keeps."""
    assert not re.search(r"\b(cosine|sine)\(", text)
    b, half = BATCH // 2, cfg.head_dim // 2
    heads = f"({cfg.n_heads // 2}|{cfg.n_kv_heads // 2})"
    widened = re.compile(
        rf"= \(?(f32\[{b},(2048|1024),{heads},({cfg.head_dim}|{half})\]"
        rf"|bf16\[{b},(2048|1024),{heads},{half}\])")
    calls = []
    for lines in layer_bodies:
        for line in lines:
            assert not widened.search(line), line
        by_name = {m.group(1): l for l in lines
                   if (m := re.match(r"\s*%([\w.\-]+) = ", l))}
        for i, line in enumerate(lines):
            m = re.match(r"\s*%rtpu_fused_qk_rope[\w.]* = \((\S+), (\S+)\) "
                         r"custom-call\(([^)]*)\)", line)
            if not m:
                continue
            # (The kernel's metadata is printed over three lines.)
            line = " ".join(lines[i:i + 3])
            rows = b * SEQ
            assert m.group(1).startswith(
                f"bf16[{rows},{cfg.n_heads // 2 * cfg.head_dim}]"), line
            assert m.group(2).startswith(
                f"bf16[{rows},{cfg.n_kv_heads // 2 * cfg.head_dim}]"), line
            operands = [by_name[o.strip().lstrip("%")]
                        for o in m.group(3).split(",")]
            calls.append(("transpose(jvp" in line, operands[2:]))
    # (`ConcatBitcast`: the compiler's own prefetch of an operand into
    # the other memory space, pieces of it under way at a time.)
    forward = [o for backward, ops in calls if not backward for o in ops]
    assert forward and all(" bitcast(" in o or "ConcatBitcast" in o
                           for o in forward), "\n".join(forward)
    return len(calls)


@pytest.mark.parametrize("policy", _REMAT_PROGRAMS)
def test_fsdp2_tp2_loss_and_grad_compile_on_described_mesh(topo, chip,
                                                           policy):
    """Real width, 2 layers, on a mesh of the four described chips: the
    flash kernel must sit inside a shard_map (XLA cannot partition a
    Mosaic kernel), and the fsdp/tp collectives must be there.

    The tp ring's engagement is decided at compile time, so its witness
    is the program's text (`parallel/collective_matmul.py`): a layer's
    tensor-parallel sums are ring hops of half the residual stream, each
    with a product between its start and its done, and no blocking
    collective of the whole stream is left in a layer body. So is what
    the remat policy keeps: the kernels and hops left in the backward.
    And where rope runs: in `rtpu_fused_qk_rope` behind the ring, chosen
    by what the code observes, with no pass of `apply_rope` around it."""
    assert llama.LlamaConfig().remat_policy == "attention"
    cfg = dataclasses.replace(_CFG_1B, n_layers=2, remat_policy=policy)
    kernels, rope_calls, ring_hops = _REMAT_PROGRAMS[policy]
    c, shapes = _fsdp2_tp2_loss_and_grad(topo, cfg, BATCH)
    text = c.as_text()
    assert text.count("tpu_custom_call") == kernels
    assert _names_kernel(c, "rtpu_fused_qk_rope")
    for collective in ("all-gather", "all-reduce"):
        assert re.search(rf"\b{collective}(-start)?\(", text), collective
    # Each device holds a quarter of the weights, not the model.
    per_device = c.memory_analysis().argument_size_in_bytes
    total = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert per_device < 0.3 * total

    comps = _computations(text)
    products = {re.match(r"(?:ENTRY )?%([\w.\-]+)", c[0]).group(1)
                for c in comps if any(" convolution(" in l for l in c)}
    stream = f"bf16[{BATCH // 2},{SEQ},{cfg.d_model}]"   # a device's batch
    hop = f"bf16[{BATCH // 2},{SEQ // 2},{cfg.d_model}]"
    hops, layer_bodies = 0, []
    for lines in comps:
        started = {}
        for i, line in enumerate(lines):
            m = re.match(r"\s*%([\w.\-]+) = .*? (collective-permute-"
                         r"(?:start|done))\((?:%([\w.\-]+)\))?", line)
            if m and m.group(2).endswith("start"):
                assert hop in line, line
                started[m.group(1)] = i
            elif m:
                between = lines[started.pop(m.group(3)) + 1:i]
                assert any(
                    " convolution(" in l or (
                        c := re.search(r"calls=%([\w.\-]+)", l)
                    ) and c.group(1) in products for l in between), (
                    f"nothing multiplies while {m.group(3)} travels")
                hops += 1
        if not any("collective-permute-start(" in l for l in lines):
            continue
        # A layer body: the whole stream crosses no link in one piece.
        layer_bodies.append(lines)
        for line in lines:
            assert not re.search(
                rf"= {re.escape(stream)}\S* (all-reduce|all-gather)"
                r"(-start)?\(", line), line
    assert hops == text.count("collective-permute-start(") == ring_hops
    assert _assert_no_pass_around_the_rope_kernel(
        text, layer_bodies, cfg) == rope_calls
    # The embedding is looked up through its vocab shards, never gathered.
    assert not re.search(
        rf"= bf16\[{cfg.vocab_size},{cfg.d_model}\]\S* all-gather", text)


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_attention_policy_keeps_four_arrays_a_layer_a_device(topo, chip):
    """`smollm2.sft.fsdp2tp2`'s widths, depth and a device's share of
    its batch (32 x 2,048 over fsdp 2): what the default policy holds
    over "nothing" is, a layer, q, k and the kernel's output with a
    row's heads side by side (bf16 [16, 2048, 1024], 67 MB each: as
    [16, 16, 2048, 64] the chip's 128-lane tiles would make each 134)
    and the statistic as ONE float32 a row (2 MB, not 268 MB of
    lanes)."""
    cfg = dataclasses.replace(_CFG_1B, n_layers=24, n_kv_heads=32,
                              vocab_size=49152, tie_embeddings=True)
    held, stacked = {}, {}
    for policy in ("attention", "nothing"):
        c, _ = _fsdp2_tp2_loss_and_grad(
            topo, dataclasses.replace(cfg, remat_policy=policy), 32)
        held[policy] = _program_bytes(c)
        # What the forward's layer loop stacks for the backward's.
        loop = next(l for l in c.as_text().split("\n") if " while(" in l)
        stacked[policy] = sorted(
            s for s in re.findall(r"\w+\[24,[\d,]+\]", loop.split(" while(")[0])
            if s.count(",") >= 3 and "2048" in s)
    stream, named, stat = ("bf16[24,16,1024,2048]", "bf16[24,16,2048,1024]",
                           "f32[24,16,16,1,2048]")
    assert stacked["nothing"] == [stream]
    assert stacked["attention"] == sorted([stream, named, named, named, stat])
    # The whole program grows by what is kept and by a sixth more: the
    # compiler's working set of the layer in flight is another one
    # (237 MB a layer where 203 are kept, and a padded stack would be
    # 404).
    a_layer = (held["attention"] - held["nothing"]) / 24
    kept = 3 * 16 * 2048 * 1024 * 2 + 16 * 16 * 2048 * 4
    assert kept == 203_423_744
    assert kept <= a_layer < 1.25 * kept, (a_layer, kept)


# ------------------------------------- the shard_map seam, numerically

def test_sharded_flash_wrapper_matches_unsharded(monkeypatch):
    """CPU mesh, with the dense reference standing in for the Mosaic
    kernel: the shard_map over (batch, heads) — GQA heads repeated per
    shard — must not change the math."""
    from ray_tpu.ops import attention

    def stand_in(q, k, v, scale):
        return attention.causal_attention(q, k, v, scale=scale)

    monkeypatch.setattr(attention, "_flash_attention", stand_in)
    mesh = mesh_2d(4, tp=2, devices=jax.devices()[:4])
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (4, 16, 8, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, 4, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (4, 16, 4, 8))
    for kk, vv in ((k, v), (k[:, :, :1], v[:, :, :1])):  # kh % tp != 0 too
        got = jax.jit(functools.partial(
            attention._sharded_flash_attention, scale=8 ** -0.5,
            mesh=mesh))(q, kk, vv)
        ref = attention.causal_attention(q, kk, vv)
        assert jnp.allclose(got, ref, atol=1e-5), float(
            jnp.max(jnp.abs(got - ref)))


# ------------------------------------- state beside rows in one cache

# Olmo-Hybrid's widths (benchmark/configs/olmo-hybrid-7b-l16.json) at one
# period of its pattern (the periods are scanned, so the HLO is the
# 16-layer one's), the cell's 32 slots of 2048 rows.
def _olmo_1p():
    from ray_tpu.models import olmo_hybrid

    return olmo_hybrid, olmo_hybrid.OlmoHybridConfig(n_layers=4,
                                                     max_seq_len=2048)


def _copies_of(text: str, cache) -> list:
    """Copies of an array of any of the cache's shapes in a program."""
    shapes = [f"{'bf16' if a.dtype == jnp.bfloat16 else 'f32'}"
              f"[{','.join(map(str, a.shape))}]" for a in cache.values()]
    return [line for line in text.splitlines()
            if " copy" in line.split("(")[0]
            and any(s in line.split("(")[0] for s in shapes)]


# [L, B, KH, S, D] and query heads a KV head of the two served cells.
CELL_CACHES = {"mistral7b": ((16, 32, 8, 1024, 128), 4),
               "olmohybrid": ((4, 32, 30, 2048, 128), 1)}


@pytest.mark.parametrize("cell", CELL_CACHES)
def test_decode_attention_compiles_at_one_query_head_a_kv_head(chip, cell):
    """The shared kernel at the cells' geometries: Mistral's 8 KV heads
    in groups of 4, the hybrid's full layers' 30 in groups of ONE; the
    layer picked out of the whole [L, B, KH, S, D] cache, which stays
    in HBM (no temporary), the block found from the shapes, and the
    kernel's buffers inside the compiler's own VMEM limit (none is
    asked for)."""
    cache, group = CELL_CACHES[cell]
    _, slots, kh, rows, d = cache
    c = _compile(
        lambda q, k, v, lens, layer: ops.decode_attention(
            q, k, v, lens, layer=layer, layout="bksd"),
        _sds(chip, (slots, kh * group, d)), _sds(chip, cache),
        _sds(chip, cache), _sds(chip, (slots,), jnp.int32),
        _sds(chip, (), jnp.int32))
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_decode_attention")
    assert "vmem_limit_bytes" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20
    from ray_tpu.ops.decode_attention import decode_block_rows
    assert decode_block_rows(rows, kh, d, 2) == 128


def test_gdn_decode_steps_the_state_where_it_lies(chip):
    """The state step at the published sizes: S^T of 2 heads side by
    side ([96, 384]: whole tiles, 2,211,840 B a slot a layer, nothing
    padded), the whole [L, B, ..] array the operand, aliased to the
    output: one kernel, under its name, no temporaries."""
    from ray_tpu.ops import gated_delta

    olmo, cfg = _olmo_1p()
    assert cfg.state_group == 2
    state = _sds(chip, (12, 32, 15, 96, 384), jnp.float32)
    assert state.size * 4 == 12 * 32 * 2_211_840
    f32 = functools.partial(_sds, chip, dtype=jnp.float32)
    c = jax.jit(gated_delta.gdn_decode, donate_argnums=(0,)).lower(
        state, _sds(chip, (), jnp.int32), f32((32, 30, 96)),
        f32((32, 30, 96)), f32((32, 30, 192)), f32((32, 30)),
        f32((32, 30))).compile()
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_gdn_decode")
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4
    assert mem.temp_size_in_bytes < 2 ** 20


def test_olmo_hybrid_decode_chunk_updates_rows_and_state_in_place(chip):
    """The family's step through the engine's own `decode_chunk`: both
    kernels in the scanned period under their names (the state step
    called directly: never ``closed_call``, which the decode-attention
    reader counts as its own), all four cache arrays aliased, no array
    of any of their shapes copied, temporaries far under one cache."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    olmo, cfg = _olmo_1p()
    slots, rows = 32, 2048
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params = _abstract(chip, functools.partial(olmo.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: olmo.init_kv_cache(cfg, slots, rows))
    assert set(cache) == {"k", "v", "state", "conv"}
    c = _lower_decode_chunk(chip, loop, params, cache, slots)
    text = c.as_text()
    assert "%rtpu_gdn_decode." in text
    assert "%rtpu_decode_attention." in text and "%closed_call" not in text
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 2 ** 28
    assert _copies_of(text, cache) == []
    vec = _sds(chip, (slots,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache, _sds(chip, (slots, 1), jnp.int32),
        vec, vec, vec, _sds(chip, (slots,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "gdn_slot_steps", "decode_attn_rows", "decode_attn_rows_streamed"}


def test_olmo_hybrid_tick_prefill_resets_the_slot_in_the_program(chip):
    """The tick's prefill at the largest bucket: the chunked scan and
    the flash kernel in it, one token and the two counters out, the
    cache aliased and no array of its shapes copied (the slot's rows,
    state and conv tail are sliced out and written back)."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    olmo, cfg = _olmo_1p()
    loop = DecodeLoop(cfg, max_len=2048, chunk=8)
    params = _abstract(chip, functools.partial(olmo.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: olmo.init_kv_cache(cfg, 32, 2048))
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 2048), jnp.int32), scalar, scalar,
            scalar)
    lowered = loop.prefill_inplace.lower(*args)
    assert "jit_prefill" in lowered.as_text()[:200]
    c = lowered.compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and set(out[2]) == {"gdn_prefill_tokens",
                                             "state_resets"}
    text = c.as_text()
    assert "%flash_attention" in text and " while(" in text
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 2 ** 30
    assert _copies_of(text, cache) == []


# ---------------------------------------------------- the MiniCPM-SALA cell

def _sala_l16():
    """The cell's configuration (benchmark/configs/minicpm-sala-l16.json):
    every published width, 16 layers in the published order."""
    from ray_tpu.models import minicpm_sala as sala

    s, l = sala.SPARSE, sala.LIGHTNING
    return sala, sala.MiniCPMSalaConfig(
        mixer_types=(s,) + (l,) * 6 + (s,) * 2 + (l,) * 4 + (s,) + (l,) * 2,
        max_seq_len=32768)


SALA_SLOTS, SALA_ROWS = 16, 32768
GIB = 2 ** 30


def test_lightning_decode_steps_the_state_where_it_lies(chip):
    """The lightning state step at the published sizes: 128 x 128 a
    head, whole tiles as the mathematics has them, the whole [L, B, ..]
    array the operand, aliased to the output: one kernel, under its
    name, no temporaries."""
    from ray_tpu.ops import lightning

    state = _sds(chip, (12, SALA_SLOTS, 32, 128, 128), jnp.float32)
    assert state.size * 4 == 12 * SALA_SLOTS * 2_097_152
    f32 = functools.partial(_sds, chip, dtype=jnp.float32)
    c = jax.jit(lightning.lightning_decode, donate_argnums=(0,)).lower(
        state, _sds(chip, (), jnp.int32), f32((SALA_SLOTS, 32, 128)),
        f32((SALA_SLOTS, 32, 128)), f32((SALA_SLOTS, 32, 128)),
        f32((SALA_SLOTS, 32))).compile()
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_lightning_decode")
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4
    assert mem.temp_size_in_bytes < 2 ** 20


def test_sparse_decode_attention_reads_a_block_list(chip):
    """The block-list kernel at the cell's geometry: 2 KV heads in
    groups of 16 query heads, a list of 128 blocks of 64 rows a (slot,
    head) in SMEM, the layer picked out of the whole [L, B, KH, S, D]
    cache, which stays in HBM."""
    from ray_tpu.ops import sparse_attention as sa

    cache = (4, SALA_SLOTS, 2, SALA_ROWS, 128)
    n_list = sa.Selection().list_len(SALA_ROWS)
    assert n_list == 128
    vec = _sds(chip, (SALA_SLOTS,), jnp.int32)
    c = _compile(
        lambda q, k, v, ids, count, seen, layer: sa.sparse_decode_attention(
            q, k, v, ids, count, seen, layer=layer, block=64),
        _sds(chip, (SALA_SLOTS, 32, 128)), _sds(chip, cache),
        _sds(chip, cache), _sds(chip, (SALA_SLOTS, 2, n_list), jnp.int32),
        vec, vec, _sds(chip, (), jnp.int32))
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_sparse_decode_attention")
    assert "vmem_limit_bytes" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20


def _sala_args(chip):
    sala, cfg = _sala_l16()
    params = _abstract(chip, functools.partial(sala.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: sala.init_kv_cache(cfg, SALA_SLOTS,
                                                       SALA_ROWS))
    assert set(cache) == {"k", "v", "kc", "state"}
    # 16 slots x 32,768 rows: 4,096 B of K and V and 128 B of compressed
    # keys a token, 25.2 MB of state a slot.
    assert cache["kc"].shape[3] * 16 == cache["k"].shape[3] == SALA_ROWS
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    assert nbytes(params) == 10_078_800_896
    assert nbytes(cache) == 2_617_245_696
    return cfg, params, cache, nbytes(params) + nbytes(cache)


def test_minicpm_sala_decode_chunk_fits_and_updates_its_cache_in_place(chip):
    """The cell's `decode_chunk` whole: 16 layers in the published
    irregular order (six runs, each a scan), both kernels under their
    names, all four cache arrays aliased and none copied, no stack of
    weights laid out again (the projections are stored output-major:
    `minicpm_sala._proj`), and weights + cache + temporaries inside the
    chip's 16 GiB with room for the check's reference."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params, cache, held = _sala_args(chip)
    loop = DecodeLoop(cfg, max_len=SALA_ROWS, chunk=8)
    c = _lower_decode_chunk(chip, loop, params, cache, SALA_SLOTS)
    text = c.as_text()
    assert "%rtpu_lightning_decode." in text
    assert "%rtpu_sparse_decode_attention." in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= held - 10_078_800_896
    assert mem.temp_size_in_bytes < 2 ** 28
    assert held + mem.temp_size_in_bytes < 13 * GIB
    assert _copies_of(text, cache) == []
    vec = _sds(chip, (SALA_SLOTS,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache,
        _sds(chip, (SALA_SLOTS, 1), jnp.int32), vec, vec, vec,
        _sds(chip, (SALA_SLOTS,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "sparse_rows_held", "sparse_rows_selected", "sparse_select_steps",
        "lightning_state_steps"}


def test_minicpm_sala_tick_prefill_chunk_fits_beside_the_cache(chip):
    """The tick's prefill at the chunk's size (2,048 tokens at any
    ``cache_index``): the masked attention's loop over the slot's rows
    and the chunked scan in it, one token and the two counters out, the
    cache aliased and no array of its shapes copied; its temporaries
    (the score tiles) leave the chip room."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params, cache, held = _sala_args(chip)
    loop = DecodeLoop(cfg, max_len=SALA_ROWS, chunk=8)
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 2048), jnp.int32), scalar, scalar,
            scalar)
    lowered = loop.prefill_inplace.lower(*args)
    assert "jit_prefill" in lowered.as_text()[:200]
    c = lowered.compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and set(out[2]) == {"prefill_chunks",
                                             "state_resets"}
    text = c.as_text()
    assert " while(" in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= held - 10_078_800_896
    assert mem.temp_size_in_bytes < GIB
    assert held + mem.temp_size_in_bytes < 13 * GIB
    assert _copies_of(text, cache) == []


# ------------------------------------------------------------ the ZAYA1 cell

ZAYA_SLOTS, ZAYA_ROWS = 64, 2048


def _zaya_args(chip):
    """The cell's configuration (benchmark/configs/zaya1-8b-l16.json):
    every published width, 16 layers, 64 slots of 2,048 rows."""
    from ray_tpu.models import zaya

    cfg = zaya.ZayaConfig(n_layers=16, max_seq_len=ZAYA_ROWS)
    params = _abstract(chip, functools.partial(zaya.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: zaya.init_kv_cache(cfg, ZAYA_SLOTS,
                                                       ZAYA_ROWS))
    assert set(cache) == {"k", "v", "tail"}
    # 1,024 B of K and V a token a layer; a tail of 2,688 float32 (21
    # whole lane tiles) a slot a layer.
    assert cache["k"].shape == (16, ZAYA_SLOTS, 2, ZAYA_ROWS, 128)
    assert cache["tail"].shape == (16, ZAYA_SLOTS, 2688)
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    assert nbytes(params) == 7_748_146_304
    assert nbytes(cache) == 2_158_493_696
    return cfg, params, cache, nbytes(params) + nbytes(cache)


def test_zaya_decode_chunk_fits_and_updates_rows_and_tail_in_place(chip):
    """The cell's `decode_chunk` whole: one scan over the 16 layers, the
    decode-attention kernel in it under its name (called directly, 4
    query heads a KV head), the experts as the chip compiler's grouped
    matmul (three a layer, 64 rows: one expert a token), rows and tail
    aliased and none copied, no layer's experts sliced out of their
    stack, and weights + cache + temporaries inside the chip with room
    for the check's two further caches."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params, cache, held = _zaya_args(chip)
    loop = DecodeLoop(cfg, max_len=ZAYA_ROWS, chunk=8)
    c = _lower_decode_chunk(chip, loop, params, cache, ZAYA_SLOTS)
    text = c.as_text()
    assert "%rtpu_decode_attention." in text and "%closed_call" not in text
    assert len(re.findall(r"%ragged-dot-none[.\d]* = bf16\[64,", text)) == 3
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= held - 7_748_146_304
    assert mem.temp_size_in_bytes < 2 ** 28
    assert held + mem.temp_size_in_bytes < 10.5 * GIB
    assert _copies_of(text, cache) == []
    vec = _sds(chip, (ZAYA_SLOTS,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache,
        _sds(chip, (ZAYA_SLOTS, 1), jnp.int32), vec, vec, vec,
        _sds(chip, (ZAYA_SLOTS,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "moe_layer_steps", "moe_expert_hits", "moe_decode_load_max",
        "decode_attn_rows", "decode_attn_rows_streamed"}
    whole = (params, cache, _sds(chip, (ZAYA_SLOTS, 1), jnp.int32), vec)
    logits, _, counters, seen = jax.eval_shape(loop.decode_step_whole, *whole)
    assert logits.shape == (ZAYA_SLOTS, cfg.vocab_size)
    assert set(counters) == set(out[7])
    assert seen["experts"].shape == (16, ZAYA_SLOTS, 1, 1)
    assert seen["router_p"].shape == (16, ZAYA_SLOTS, 1, 16)
    assert seen["router_in"].shape == (16, ZAYA_SLOTS, 1, 2048)


def test_zaya_tick_prefill_resets_the_tail_in_the_program(chip):
    """The tick's prefill at the largest bucket: the flash kernel and
    the two grouped kernels in it, one token and the counters out (the
    262,272-column row of logits stays behind), the cache aliased and
    no array of its shapes copied."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params, cache, held = _zaya_args(chip)
    loop = DecodeLoop(cfg, max_len=ZAYA_ROWS, chunk=8)
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 512), jnp.int32), scalar, scalar,
            scalar)
    lowered = loop.prefill_inplace.lower(*args)
    assert "jit_prefill" in lowered.as_text()[:200]
    c = lowered.compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and set(out[2]) == {
        "moe_prefill_tokens", "moe_prefill_load_max",
        "moe_prefill_load_mean", "state_resets"}
    row = jax.eval_shape(loop.prefill_last, *args)
    assert row[0].shape == (1, cfg.vocab_size)
    assert set(row[3]) == {"experts", "router_in", "router_p"}
    text = c.as_text()
    # 32 rows an expert: a prefill's, so the repo's grouped kernels.
    assert "%flash_attention" in text and "%ragged-dot-none" not in text
    assert _names_kernel(c, "rtpu_grouped_swiglu")
    assert _names_kernel(c, "rtpu_grouped_matmul")
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= held - 7_748_146_304
    assert mem.temp_size_in_bytes < GIB
    assert _copies_of(text, cache) == []


# ------------------------------------------------------- the dots3-note cell

DOTS3_SLOTS, DOTS3_ROWS = 16, 32768


def _dots3_args(chip):
    """The cell's configuration (benchmark/configs/
    dots3-note-prev-l5-ep8.json) through its builder: every published
    width, 5 layers, 32 of 256 experts held, 16 slots of 32,768 rows."""
    import json

    from benchmark.builders import dots3_note as builder
    from benchmark.harness import manifest
    from ray_tpu.models import dots3_note

    with open(manifest.BENCH_DIR / "configs"
              / "dots3-note-prev-l5-ep8.json") as f:
        cfg = builder.config(json.load(f))
    params = _abstract(chip, functools.partial(dots3_note.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: dots3_note.init_kv_cache(
        cfg, DOTS3_SLOTS, DOTS3_ROWS))
    # Three kinds of entry: a latent row of 1,280 B and an index key of
    # 256 B a token a full layer; 640 rows of 2,304 B a slot a sliding
    # layer, whatever the slot's length.
    assert cache["kv"].shape == (2, DOTS3_SLOTS, DOTS3_ROWS, 640)
    assert cache["ik"].shape == (2, DOTS3_SLOTS, DOTS3_ROWS, 128)
    assert cache["win"].shape == (3, DOTS3_SLOTS, 640, 1152)
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    assert nbytes(params) == 8_186_107_904
    assert nbytes(cache) == 1_681_391_616
    return cfg, params, cache, nbytes(params) + nbytes(cache)


def test_dsa_select_scores_and_chooses_in_one_kernel(chip):
    """A decode step's choice of rows at the published sizes: 64 index
    heads of 128 over a slot's 32,768 index keys, blocks of 2,048 rows,
    the scores and both searches in fast memory: one kernel, under its
    name, the index keys read where they lie."""
    from ray_tpu.ops import row_select

    c = row_select.select_decode_rows.lower(
        _sds(chip, (DOTS3_SLOTS, 64, 128)),
        _sds(chip, (DOTS3_SLOTS, 64), jnp.float32),
        _sds(chip, (2, DOTS3_SLOTS, DOTS3_ROWS, 128)),
        _sds(chip, (DOTS3_SLOTS,), jnp.int32),
        layer=_sds(chip, (), jnp.int32), k=2048).compile()
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_dsa_select")
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("queries", [512, 1024])
def test_dsa_prefill_attention_compiles_at_the_shorter_buckets(chip, queries):
    """The full layers' prefill attention at the published sizes and the
    buckets a prompt's last chunk takes (the tick's whole prefill below
    holds 2,048): 128 heads over one slot's 32,768 latent rows of 640
    lanes where they lie, the mask as int8, ONE kernel under its name
    and no [heads, queries, rows] array beside it."""
    from ray_tpu.ops.dsa_prefill import dsa_prefill_attention

    c = dsa_prefill_attention.lower(
        _sds(chip, (1, queries, 128, 192)), _sds(chip, (1, DOTS3_ROWS, 640)),
        _sds(chip, (1, queries, DOTS3_ROWS), jnp.bool_),
        _sds(chip, (512, 128, 128)), _sds(chip, (512, 128, 128)),
        _sds(chip, (), jnp.int32), scale=192 ** -0.5).compile()
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_dsa_prefill_attention")
    # The padded queries, the mask as int8 and the output: no scores.
    assert c.memory_analysis().temp_size_in_bytes < queries * (
        128 * 256 * 2 + DOTS3_ROWS + 128 * 128 * 4) * 1.1


@pytest.mark.parametrize("queries", [512, 1024, 2048])
def test_swa_prefill_attention_compiles_at_the_cells_buckets(chip, queries):
    """The window layers' prefill attention at the published sizes and
    the cell's three buckets: 64 heads of 192 + 64 and 128 columns over
    the chunk's rows and the 512 before them, expanded head-major
    outside: ONE kernel under its name (and not the full layers'), a
    block's scores in fast memory and no [blocks, 64, 512, 1024] float32
    array beside it, inside the fast memory it asks for."""
    from ray_tpu.ops.swa_prefill import swa_prefill_attention

    rows = 512 + queries
    c = swa_prefill_attention.lower(
        _sds(chip, (1, queries, 64, 256)), _sds(chip, (1, 64, rows, 256)),
        _sds(chip, (1, 64, rows, 128)), _sds(chip, (1, queries), jnp.int32),
        _sds(chip, (1, rows), jnp.int32), reach=512,
        scale=256 ** -0.5).compile()
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_swa_prefill_attention")
    assert "rtpu_dsa_prefill_attention" not in c.as_text()
    out = jax.eval_shape(
        functools.partial(swa_prefill_attention, reach=512, scale=0.0625),
        _sds(chip, (1, queries, 64, 256)), _sds(chip, (1, 64, rows, 256)),
        _sds(chip, (1, 64, rows, 128)), _sds(chip, (1, queries), jnp.int32),
        _sds(chip, (1, rows), jnp.int32))
    assert [(o.shape, o.dtype) for o in out] == [
        ((1, queries, 64, 128), jnp.float32), ((1, queries), jnp.int32),
        ((1, queries), jnp.int32)]
    # Nothing but the outputs' own reshapes: no scores, no gathered span.
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_mla_decode_attention_compiles_at_the_window_layers_width(chip):
    """The latent kernel at its second geometry: 64 heads over rows of
    1,088 values padded to 1,152 (nine whole lane tiles), the ring of
    640 rows in one block, under the window's mask and the name the
    family gives it."""
    from ray_tpu.ops.mla_decode import mla_decode_attention

    c = _compile(
        lambda q, cache, lens, layer, keep: mla_decode_attention(
            q, cache, lens, layer=layer, v_dim=1024, scale=256 ** -0.5,
            block_s=640, keep=keep, name="rtpu_swa_decode_attention"),
        _sds(chip, (DOTS3_SLOTS, 64, 1152)),
        _sds(chip, (3, DOTS3_SLOTS, 640, 1152)),
        _sds(chip, (DOTS3_SLOTS,), jnp.int32), _sds(chip, (), jnp.int32),
        _sds(chip, (DOTS3_SLOTS, 640), jnp.bool_))
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_swa_decode_attention")
    assert "vmem_limit_bytes" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_dots3_decode_chunk_fits_and_updates_its_three_entries_in_place(chip):
    """The cell's `decode_chunk` whole: the layers as three scans in
    published order, the selection kernel and the latent kernel under
    its two names, the grouped matmul over the 128 pairs a step (those
    on absent experts in no group), latent rows, index keys and rings
    aliased and none copied, and weights + cache + temporaries inside
    the chip with room for the check's cache and reference."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params, cache, held = _dots3_args(chip)
    loop = DecodeLoop(cfg, max_len=DOTS3_ROWS, chunk=8)
    c = _lower_decode_chunk(chip, loop, params, cache, DOTS3_SLOTS)
    text = c.as_text()
    for kernel in ("rtpu_dsa_select", "rtpu_dsa_decode_attention",
                   "rtpu_swa_decode_attention"):
        assert f"%{kernel}." in text
    # Two runs of expert layers (full, then sliding), three products each.
    assert len(re.findall(r"%ragged-dot-none[.\d]* = bf16\[128,", text)) == 6
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= held - 8_186_107_904
    assert mem.temp_size_in_bytes < 2 ** 29
    assert held + mem.temp_size_in_bytes < 11 * GIB
    assert _copies_of(text, cache) == []
    vec = _sds(chip, (DOTS3_SLOTS,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache,
        _sds(chip, (DOTS3_SLOTS, 1), jnp.int32), vec, vec, vec,
        _sds(chip, (DOTS3_SLOTS,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "dsa_rows_visible", "dsa_rows_selected", "dsa_rows_attended",
        "dsa_queries_selected", "window_rows_read", "moe_layer_steps",
        "moe_expert_hits", "moe_pairs_routed", "moe_pairs_held"}


def test_dots3_tick_prefill_chunk_fits_beside_the_cache(chip):
    """The tick's prefill at the chunk's size (2,048 queries at any
    ``cache_index``, each with its own 2,048 rows): the scoring's loop
    over the slot's rows, the masked attention as ONE kernel under its
    name (no [128, 2048, 512] float32 tile of scores among the
    temporaries), the window layers' as another, one token and the counters out, the three entries
    aliased and none copied; its temporaries (the score array, the
    mask) leave the chip room."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params, cache, held = _dots3_args(chip)
    loop = DecodeLoop(cfg, max_len=DOTS3_ROWS, chunk=8)
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 2048), jnp.int32), scalar, scalar,
            scalar)
    lowered = loop.prefill_inplace.lower(*args)
    assert "jit_prefill" in lowered.as_text()[:200]
    c = lowered.compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and set(out[2]) == {
        "prefill_chunks", "dsa_queries_selected",
        "dsa_prefill_rows_attended", "moe_pairs_routed", "moe_pairs_held",
        "moe_prefill_expert_hits"}
    text = c.as_text()
    assert " while(" in text and "%ragged-dot-none" not in text
    assert _names_kernel(c, "rtpu_grouped_swiglu")
    assert _names_kernel(c, "rtpu_grouped_matmul")
    assert "%rtpu_dsa_prefill_attention." in text
    assert "f32[1,128,2048,128]" not in text and "f32[128,2048," not in text
    # The window layers' attention is a kernel too (PR 60): no block's
    # scores and no gathered span of keys among the arrays.
    assert "%rtpu_swa_prefill_attention." in text
    assert "f32[4,64,512,1024]" not in text and "[4,1024,64," not in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= held - 8_186_107_904
    assert mem.temp_size_in_bytes < 2 * GIB
    assert held + mem.temp_size_in_bytes < 12.5 * GIB
    assert _copies_of(text, cache) == []


# ---------------------------------------------- the Granite-4.0-H cell

# granite-4.0-h-micro's widths (benchmark/configs/granite-4.0-h-micro.json)
# at one run of each kind and a second Mamba run (a run is scanned, so
# the HLO is the 40-layer one's with fewer runs), the cell's 64 slots of
# 2048 rows.
def _granite_4l():
    from ray_tpu.models import granite_hybrid as granite

    return granite, granite.GraniteHybridConfig(
        layer_kinds=("mamba", "mamba", "attention", "mamba"),
        max_seq_len=2048)


def test_mamba2_decode_steps_the_state_where_it_lies(chip):
    """The state step at the published sizes: S^T of 2 heads side by
    side ([128, 128]: whole tiles, 2,097,152 B a slot a layer, nothing
    padded), the whole [L, B, ..] array the operand, aliased to the
    output: one kernel, under its name, no temporaries."""
    from ray_tpu.ops import mamba2

    _, cfg = _granite_4l()
    assert cfg.state_group == 2 and cfg.kv_pack == 2
    state = _sds(chip, (36, 64, 32, 128, 128), jnp.float32)
    assert state.size * 4 == 36 * 64 * 2_097_152
    f32 = functools.partial(_sds, chip, dtype=jnp.float32)
    c = jax.jit(mamba2.mamba2_decode, donate_argnums=(0,)).lower(
        state, _sds(chip, (), jnp.int32), f32((64, 64, 64)), f32((64, 64)),
        f32((64,)), f32((64, 128)), f32((64, 128))).compile()
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_mamba2_decode")
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4
    assert mem.temp_size_in_bytes < 2 ** 21


def test_granite_hybrid_decode_chunk_updates_rows_and_state_in_place(chip):
    """The family's step through the engine's own `decode_chunk`: both
    kernels in the scanned runs under their names, the attention
    layers' rows read where they lie at head size 64 (two KV heads side
    by side in a 128-lane row: no layer sliced out and padded), all
    four cache arrays aliased, no array of any of their shapes copied,
    temporaries far under one cache."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    granite, cfg = _granite_4l()
    slots, rows = 64, 2048
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params = _abstract(chip, functools.partial(granite.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: granite.init_kv_cache(cfg, slots, rows))
    assert set(cache) == {"k", "v", "ssm", "conv"}
    assert cache["k"].shape == (1, 64, 4, 2048, 128)
    c = _lower_decode_chunk(chip, loop, params, cache, slots)
    text = c.as_text()
    assert "%rtpu_mamba2_decode." in text
    assert "%rtpu_decode_attention." in text and "%closed_call" not in text
    assert "bf16[64,4,2048,128]" not in text      # no layer sliced out
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 2 ** 28
    assert _copies_of(text, cache) == []
    vec = _sds(chip, (slots,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache, _sds(chip, (slots, 1), jnp.int32),
        vec, vec, vec, _sds(chip, (slots,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "mamba2_slot_steps", "decode_attn_rows", "decode_attn_rows_streamed"}


def test_granite_hybrid_tick_prefill_resets_the_slot_in_the_program(chip):
    """The tick's prefill at the largest bucket: the chunked scan and
    the flash kernel (head size 64, 4 query heads a KV head) in it, one
    token and the two counters out, the cache aliased and no array of
    its shapes copied."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    granite, cfg = _granite_4l()
    loop = DecodeLoop(cfg, max_len=2048, chunk=8)
    params = _abstract(chip, functools.partial(granite.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: granite.init_kv_cache(cfg, 64, 2048))
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 512), jnp.int32), scalar, scalar,
            scalar)
    lowered = loop.prefill_inplace.lower(*args)
    assert "jit_prefill" in lowered.as_text()[:200]
    c = lowered.compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and set(out[2]) == {"mamba2_prefill_tokens",
                                             "state_resets"}
    text = c.as_text()
    assert "%flash_attention" in text and " while(" in text
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 2 ** 30
    assert _copies_of(text, cache) == []


# granite-4.0-h-small-l10-ep2 as its cell runs it
# (benchmark/configs/granite-4.0-h-small-l10-ep2.json): one period of ten
# layers at the published widths, 36 of 72 experts held, ten a token, 32
# slots of 2048 rows.
def _granite_small():
    from ray_tpu.models import granite_hybrid as granite

    return granite, granite.GraniteHybridConfig(
        vocab_size=50176, d_model=4096,
        layer_kinds=tuple("attention" if i == 5 else "mamba"
                          for i in range(10)),
        head_dim=128, mamba_heads=128, d_ff=1536, n_experts=72,
        n_experts_per_tok=10, d_expert=768, held=(0, 36),
        attention_multiplier=0.0078125, logits_scaling=16.0,
        max_seq_len=2048)


def test_granite_small_decode_chunk_takes_the_kernels_at_ten_a_token(chip):
    """The cell's step through the engine's own `decode_chunk`: 320
    pairs over 36 held groups (8.9 a group) take the grouped kernels on
    rows padded to 384, the state kernel at 128 heads and the attention
    kernel at head size 128 under their names, the four cache arrays
    aliased and none copied, no expert stack copied, weights + cache +
    temporaries inside the chip. The check's own step is that step,
    donated, at all 32 slots: one of 3 slots (30 pairs, under the rule)
    would take ``ragged_dot`` over the stack's 360 groups, which the
    chip's compiler refuses with the INTERNAL bitcast error it has for
    xing4's 304 (compiled for this description, PR 61)."""
    from ray_tpu.ops import grouped_experts as ge
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    granite, cfg = _granite_small()
    slots, rows = 32, 2048
    assert cfg.state_group == 2 and cfg.kv_pack == 1
    assert ge._takes_kernels(slots * cfg.n_experts_per_tok, 36, None)
    assert not ge._takes_kernels(3 * cfg.n_experts_per_tok, 36, None)
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params = _abstract(chip, functools.partial(granite.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: granite.init_kv_cache(cfg, slots, rows))
    c = _lower_decode_chunk(chip, loop, params, cache, slots)
    text = c.as_text()
    assert "%rtpu_mamba2_decode." in text
    assert "%rtpu_decode_attention." in text
    assert "%rtpu_grouped_swiglu." in text and "ragged-dot" not in text
    assert "bf16[384,768]" in text and "bf16[384,4096]" in text
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert _copies_of(text, cache) == []
    assert _copies_of(text, {k: params["experts"][k]
                             for k in ge.EXPERT_STACKS}) == []
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(params))
    assert 9.4e9 < weights < 9.6e9
    assert weights + nbytes + mem.temp_size_in_bytes < 13 * 2 ** 30
    vec = _sds(chip, (slots,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache, _sds(chip, (slots, 1), jnp.int32),
        vec, vec, vec, _sds(chip, (slots,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "mamba2_slot_steps", "decode_attn_rows", "decode_attn_rows_streamed",
        "moe_layer_steps", "moe_expert_hits", "moe_pairs_routed",
        "moe_pairs_held"}
    # The check's step is the window's, all 32 slots in the engine's own
    # cache, donated (`serve_routed_mamba2._replay`).
    check = loop.decode_step_whole_inplace.lower(
        params, cache, _sds(chip, (slots, 1), jnp.int32), vec,
        _sds(chip, (slots,), jnp.bool_)).compile()
    assert "%rtpu_grouped_swiglu." in check.as_text()
    assert check.memory_analysis().alias_size_in_bytes >= nbytes


def test_granite_small_tick_prefill_routes_a_bucket_through_the_kernels(chip):
    """The tick's prefill at the middle bucket: 10,240 pairs over 36
    held groups through the grouped kernels, the chunked scan and the
    flash kernel in it, one token and the counters out, the cache
    aliased, no array of its shapes and no expert stack copied."""
    from ray_tpu.ops import grouped_experts as ge
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    granite, cfg = _granite_small()
    loop = DecodeLoop(cfg, max_len=2048, chunk=8)
    params = _abstract(chip, functools.partial(granite.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: granite.init_kv_cache(cfg, 32, 2048))
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 1024), jnp.int32), scalar, scalar,
            scalar)
    c = loop.prefill_inplace.lower(*args).compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and {"mamba2_prefill_tokens", "state_resets",
                              "moe_pairs_held",
                              "moe_prefill_load_max"} <= set(out[2])
    text = c.as_text()
    assert "%flash_attention" in text and " while(" in text
    assert "%rtpu_grouped_swiglu." in text and "ragged-dot" not in text
    # In the engine's own program too (PR 62): the pairs' rows are bf16
    # wherever they are written, none selected, none laid token-major
    # (where the family's widening was hoisted above the zeroing pass
    # and the gather back: `f32[20480,4096]`, `f32[2048,10,4096]`).
    written = "\n".join(_written(text))
    assert re.findall(r"f32\[(?:10240|10,1024|1024,10),4096\]", written) == []
    assert re.findall(r"bf16\[10240,4096\]\S* select\(", written) == []
    assert "[1024,10,4096]" not in text and "bf16[10,1024,4096]" in written
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert _copies_of(text, cache) == []
    assert _copies_of(text, {k: params["experts"][k]
                             for k in ge.EXPERT_STACKS}) == []
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(params))
    assert weights + nbytes + mem.temp_size_in_bytes < 14 * 2 ** 30


# (rows, groups held, layers in the stack, d, d_ff): GLM's largest bucket
# (4,096 tokens x 4 of 64 experts, 6 expert layers) and dots3's chunk
# (2,048 tokens x 8, 32 of 256 experts held, 4 expert layers).
GROUPED = {"glm": (16384, 64, 6, 2048, 1536),
           "dots3": (16384, 32, 4, 5120, 1536)}


@pytest.mark.parametrize("cell", sorted(GROUPED))
def test_grouped_expert_kernels_compile_at_the_prefill_shapes(chip, cell):
    """A prefill's grouped products (``ops/grouped_experts.py``) at the
    cells' widths: two kernels under their names, the experts' stacks
    read where they lie (all of the layers', the layer found by its
    index: no copy, no slice), the matrices' two buffers and the tiles
    within the kernel's fast memory, and nothing between the two
    kernels but ``hidden``."""
    from ray_tpu.ops import grouped_experts as ge

    rows, held, layers, d, d_ff = GROUPED[cell]

    def products(xs, stacks, load, layer):
        return ge._kernel_products(
            xs, *(stacks[m] for m in ge.EXPERT_STACKS), load, layer * held,
            interpret=False)

    assert ge._takes_kernels(rows, held, None)
    stacks = {"w_gate": _sds(chip, (layers * held, d, d_ff)),
              "w_up": _sds(chip, (layers * held, d, d_ff)),
              "w_down": _sds(chip, (layers * held, d_ff, d))}
    c = _compile(products, _sds(chip, (rows, d)), stacks,
                 _sds(chip, (held,), jnp.int32), _sds(chip, (), jnp.int32))
    assert _kernel_calls(c) == 2
    assert _names_kernel(c, ge.SWIGLU) and _names_kernel(c, ge.MATMUL)
    assert _copies_of(c.as_text(), stacks) == []
    # ``hidden`` and the walk's tables: no second copy of rows or output.
    assert c.memory_analysis().temp_size_in_bytes < 1.1 * rows * d_ff * 2


# (T, k, d, d_ff, the router's experts, held, expert layers in the stack):
# one expert layer at a 2,048-token bucket of Granite, xing4 and dots3
# and at GLM's largest.
EXPERT_LAYERS = {"granite4hsmall": (2048, 10, 4096, 768, 72, (0, 36), 10),
                 "xing4": (2048, 4, 3584, 1024, 64, (0, 8), 38),
                 "dots3": (2048, 8, 5120, 1536, 256, (64, 32), 4),
                 "glm47flash": (4096, 4, 2048, 1536, 64, None, 6)}
_RESULT = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")


def _by_name(text: str) -> dict:
    """The compiled module's computations by their names."""
    return {comp[0].removeprefix("ENTRY ").split(" ")[0][1:]: comp
            for comp in _computations(text)}


def _written(text: str) -> list:
    """The lines of a compiled program whose results are written to
    memory: every computation's but a fusion's own (what a fusion
    computes on the way to its root stays in registers)."""
    fused = set(re.findall(r"fusion\(.*calls=%([\w.\-]+)", text))
    return [line for name, comp in _by_name(text).items()
            if name not in fused for line in comp]


def _results_of(lines, elements: int) -> list:
    """(name, dtype, op) of the instructions whose result has at least
    ``elements`` elements."""
    found = []
    for m in filter(None, map(_RESULT.match, lines)):
        size = functools.reduce(
            lambda a, b: a * int(b), filter(None, m.group(3).split(",")), 1)
        if size >= elements:
            found.append((m.group(1), m.group(2), m.group(4)))
    return found


@pytest.mark.parametrize("cell", sorted(EXPERT_LAYERS))
def test_expert_layer_writes_its_pairs_by_two_gathers_and_two_kernels(
        chip, cell):
    """One expert layer (sort, kernels, unsort, the gates' sum:
    `grouped_swiglu` then `gated_sum`, under a bucket's ``valid``) as
    the chip's compiler schedules it: of the arrays of ``T * k x d``
    elements that cross HBM, one is the gather into expert order, one
    `rtpu_grouped_matmul`'s result, one the gather back, and there is
    NO other: no fill select of a ``take``, no zeroing pass, no relayout
    to ``[T, k, d]`` (a copy where k is no whole tile of sublanes: 10,
    4), no widened copy. The rows gathered back are bitcast to
    ``[k, T, d]`` and read by the one fusion that writes ``f32[T, d]``."""
    from ray_tpu.ops import grouped_experts as ge

    t, k, d, d_ff, n_experts, held, layers = EXPERT_LAYERS[cell]
    count = n_experts if held is None else held[1]

    def layer(x, experts, gates, stacks, valid, layer_idx):
        pairs, load = ge.grouped_swiglu(x, experts, stacks, layer_idx,
                                        n_experts, valid, held=held)
        return ge.gated_sum(pairs, gates), load

    assert ge._takes_kernels(t * k, count, None)
    stacks = {"w_gate": _sds(chip, (layers * count, d, d_ff)),
              "w_up": _sds(chip, (layers * count, d, d_ff)),
              "w_down": _sds(chip, (layers * count, d_ff, d))}
    c = _compile(layer, _sds(chip, (t, d)), _sds(chip, (t, k), jnp.int32),
                 _sds(chip, (t, k), jnp.float32), stacks,
                 _sds(chip, (t,), jnp.bool_), _sds(chip, (), jnp.int32))
    text = c.as_text()
    assert _names_kernel(c, ge.SWIGLU) and _names_kernel(c, ge.MATMUL)
    assert _kernel_calls(c) == 2 and "ragged-dot" not in text
    assert _copies_of(text, stacks) == []
    body = _by_name(text)
    entry = next(comp for comp in body.values()
                 if comp[0].startswith("ENTRY"))
    pairs = [r for r in _results_of(entry, t * k * d)
             if r[2] not in ("bitcast", "parameter")]
    calls = dict(re.findall(r"%(\S+) = .* fusion\(.*calls=%([\w.\-]+)",
                            "\n".join(entry)))
    gathers = [r for r in pairs if any(
        " gather(" in line for line in body.get(calls.get(r[0]), []))]
    assert len(gathers) == 2
    assert sorted(r[0].split(".")[0] for r in pairs if r not in gathers) == [
        ge.MATMUL], pairs
    assert {r[1] for r in pairs} == {"bf16"}
    assert [r for r in _results_of(_written(text), t * k * d)
            if r[1] == "f32" or r[2] in ("select", "copy", "reshape")] == []
    # The sum: the gather back, bitcast to [k, T, d], into one fusion.
    assert re.search(rf"bf16\[{k},{t},{d}\]\S* bitcast\(%{gathers[1][0]}\)",
                     text) or k == 1
    assert f"[{t},{k},{d}]" not in text
    out = [r for r in _results_of(entry, t * d)
           if r[1] == "f32" and r[2] == "fusion"]
    assert len(out) == 1, out


# ------------------------------------------------ the Kimi-Linear cell

# kimi-linear-48b-a3b-ep16's widths
# (benchmark/configs/kimi-linear-48b-a3b-ep16.json) at a run of each
# kind and one period that repeats (the dense KDA layer, then K K M
# twice: a run is scanned and a period's repetitions are scanned around
# its runs, so the HLO is the 27-layer one's with fewer runs and
# repetitions), 16 of 256 experts held, the cell's 64 slots of 2048
# rows and its slice of the vocabulary.
def _kimi_7l():
    from ray_tpu.models import kimi_linear as kimi

    return kimi, kimi.KimiLinearConfig(
        vocab_size=20480, kinds=("kda",) + ("kda", "kda", "mla") * 2,
        held_experts=(0, 16), max_seq_len=2048)


def _kimi_served(chip, kimi, cfg):
    """The tree the family's programs read, as the engine makes it of
    the published one (`kimi_linear.serving_params`)."""
    params = _abstract(
        chip, lambda key: kimi.serving_params(kimi.init_params(cfg, key),
                                              cfg), jax.random.PRNGKey(0))
    assert params["kda"]["w_in"].shape == (5, 3 * 32 * 128 + 2 * 128 + 32,
                                           2304)
    return params


# A KDA layer's projections out of their stack, in the served form and
# in the published one (what PR 50's programs sliced out and turned a
# layer-step: 3 x 18.9 MB).
_KDA_PROJECTION_SLICES = ("bf16[1,12576,2304]", "bf16[12576,2304]",
                          "bf16[2304,12576]", "bf16[1,2304,32,128]",
                          "bf16[288,8,32,128]")
# One published matrix with its heads merged; in the 512-token prefill
# also the shape of the 4,096 routed rows, so only the step looks for it.
_ONE_PROJECTION = ("bf16[4096,2304]", "bf16[2304,4096]")


def _written_out(text: str, shapes) -> list:
    """The instructions of a compiled program whose result is an array
    of one of ``shapes`` WRITTEN to memory: a copy anywhere, or any
    instruction outside a fusion's body (a fusion itself among them: a
    slice taken out of its stack and handed on). Inside a fused
    computation the same shape is read in passing, by the product that
    takes it where it lies."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found, inside = [], False
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(2) in fused
            continue
        result = line.partition(" = ")[2].split("(")[0]
        if (any(s in result for s in shapes)
                and (not inside or " copy" in result)):
            found.append(line.strip()[:160])
    return found


def test_kda_decode_steps_the_state_where_it_lies(chip):
    """The state step at the published sizes: a head's state is a whole
    [128, 128] tile (2,097,152 B a slot a layer, nothing padded or
    packed), the whole [L, B, ..] array the operand, aliased to the
    output: one kernel, under its name, no temporaries."""
    from ray_tpu.ops import kda

    state = _sds(chip, (20, 64, 32, 128, 128), jnp.float32)
    assert state.size * 4 == 20 * 64 * 2_097_152
    f32 = functools.partial(_sds, chip, dtype=jnp.float32)
    c = jax.jit(kda.kda_decode, donate_argnums=(0,)).lower(
        state, _sds(chip, (), jnp.int32), f32((64, 32, 128)),
        f32((64, 32, 128)), f32((64, 32, 128)), f32((64, 32, 128)),
        f32((64, 32))).compile()
    assert _kernel_calls(c) == 1
    assert _names_kernel(c, "rtpu_kda_decode")
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= state.size * 4
    assert mem.temp_size_in_bytes < 2 ** 24


def test_kimi_linear_decode_chunk_updates_its_three_entries_in_place(chip):
    """The family's step through the engine's own `decode_chunk`: the
    state kernel and the latent kernel in the scanned runs under their
    names, the held experts' grouped products as the rule of
    ``ops/grouped_experts.py`` gives them (512 rows over 16 held
    groups: the repo's kernels), all three cache entries aliased, no
    array of any of their shapes copied, no expert stack copied."""
    from ray_tpu.ops import grouped_experts as ge
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    kimi, cfg = _kimi_7l()
    slots, rows = 64, 2048
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params = _kimi_served(chip, kimi, cfg)
    cache = _abstract(chip, lambda: kimi.init_kv_cache(cfg, slots, rows))
    assert set(cache) == {"kv", "state", "conv"}
    assert [times for times, _ in cfg.periods] == [1, 2]
    assert cache["kv"].shape == (2, 64, 2048, 640)
    assert cache["state"].shape == (5, 64, 32, 128, 128)
    assert cache["conv"].shape == (5, 64, 3 * 12288)
    c = _lower_decode_chunk(chip, loop, params, cache, slots)
    text = c.as_text()
    assert "%rtpu_kda_decode." in text and "%closed_call" not in text
    assert "%rtpu_mla_decode_attention." in text
    assert ge._takes_kernels(slots * cfg.n_experts_per_tok, 16, None)
    assert f"%{ge.SWIGLU}." in text and f"%{ge.MATMUL}." in text
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    # PR 50's program, the three stacks [nk, d, H, dk]: 39,663,104 B.
    assert mem.temp_size_in_bytes < 39_663_104
    assert _copies_of(text, cache) == []
    assert _copies_of(text, {k: params["moe"][k]
                             for k in ge.EXPERT_STACKS}) == []
    # The six maps of a KDA layer's normed stream are ONE product, which
    # slices the stack inside its own fusion: no layer's matrix is
    # taken out of the stack or turned first, and the stack (a width of
    # 12,576 is no multiple of 128 lanes) is not laid out again.
    assert "btd,cd->btc" in text
    assert _copies_of(text, {"w_in": params["kda"]["w_in"]}) == []
    assert _written_out(text,
                        _KDA_PROJECTION_SLICES + _ONE_PROJECTION) == []
    vec = _sds(chip, (slots,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache, _sds(chip, (slots, 1), jnp.int32),
        vec, vec, vec, _sds(chip, (slots,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "kda_slot_steps", "moe_layer_steps", "moe_expert_hits",
        "moe_pairs_routed", "moe_pairs_held", "mla_decode_rows",
        "mla_decode_rows_streamed"}


def test_kimi_linear_tick_prefill_resets_the_slot_in_the_program(chip):
    """The tick's prefill at the largest bucket: the chunked scan, the
    flash kernel on keys and values padded to 256 and the grouped
    kernels in it, one token and the counters out, the cache aliased
    and no array of its shapes copied."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    kimi, cfg = _kimi_7l()
    loop = DecodeLoop(cfg, max_len=2048, chunk=8)
    params = _kimi_served(chip, kimi, cfg)
    cache = _abstract(chip, lambda: kimi.init_kv_cache(cfg, 64, 2048))
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 512), jnp.int32), scalar, scalar,
            scalar)
    c = loop.prefill_inplace.lower(*args).compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and {"kda_prefill_tokens", "state_resets",
                              "moe_pairs_held"} <= set(out[2])
    text = c.as_text()
    assert "%flash_attention" in text and " while(" in text
    assert "%rtpu_grouped_swiglu." in text and "ragged-dot" not in text
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    # PR 50's program: 250,536,960 B; a projection's matrix is 18.9 MB.
    assert mem.temp_size_in_bytes < 250_536_960 - 2304 * 4096 * 2
    assert _copies_of(text, cache) == []
    assert _written_out(text, _KDA_PROJECTION_SLICES) == []


# --------------------------------------------- the four-stream residual

@pytest.mark.parametrize("rows", (32, 512, 2048))
def test_mhc_kernels_compile_at_the_step_and_the_buckets(chip, rows):
    """The two mixes of a sub-layer at the published sizes (4 streams
    of 3584, ``Phi`` as [24, 14336]): a decode step's 32 rows and a
    prefill bucket's tokens are ONE kernel each over another grid,
    under its own name; the write-back aliases the streams it read."""
    from ray_tpu.ops import mhc

    spec, c = mhc.MhcSpec(), 3584
    f32 = functools.partial(_sds, chip, dtype=jnp.float32)
    streams = f32((rows, spec.n * c))
    pre = _compile(functools.partial(mhc.mhc_pre, spec=spec), streams,
                   f32((spec.n_maps, spec.n * c)), f32((3,)),
                   f32((spec.n_maps,)))
    assert _kernel_calls(pre) == 1 and _names_kernel(pre, mhc.PRE)
    # What the benchmark's readers parse: the call's FIRST result has
    # the call's own rows (a step's 32 are not padded to a tile outside
    # the kernel), the maps follow.
    bare = re.sub(r"\{[^}]*\}", "", pre.as_text())    # without layouts
    assert re.search(rf"%{mhc.PRE}[.\d]* = \(([^)]*)\) custom-call",
                     bare).group(1) == (
        f"f32[{rows},{c}], f32[{rows},{mhc.LANES}]")
    post = jax.jit(functools.partial(mhc.mhc_post, spec=spec),
                   donate_argnums=(0,)).lower(
        streams, f32((rows, c)), f32((rows, mhc.LANES))).compile()
    assert _kernel_calls(post) == 1 and _names_kernel(post, mhc.POST)
    assert post.memory_analysis().alias_size_in_bytes >= rows * spec.n * c * 4


def _xing_5l():
    from ray_tpu.models import xing_mhc

    return xing_mhc, xing_mhc.XingMhcConfig(
        vocab_size=16384, n_layers=5, held_experts=(0, 8), max_seq_len=2048)


def test_xing_mhc_decode_chunk_mixes_the_streams_in_named_kernels(chip):
    """The family's step through the engine's own `decode_chunk` (the
    layer body is scanned, so 5 layers' HLO is the 40's): both mHC
    kernels and the latent kernel under their names in the scanned
    layers, the held experts' grouped products as the rule of
    ``ops/grouped_experts.py`` gives them (128 rows over 8 held groups:
    the repo's kernels), the cache aliased and no array of its shape
    copied, no expert stack copied."""
    from ray_tpu.ops import grouped_experts as ge, mhc
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    xing, cfg = _xing_5l()
    slots, rows = 32, 2048
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params = _abstract(chip, functools.partial(xing.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: xing.init_kv_cache(cfg, slots, rows))
    assert cache["kv"].shape == (5, 32, 2048, 640)
    c = _lower_decode_chunk(chip, loop, params, cache, slots)
    text = c.as_text()
    assert f"%{mhc.PRE}." in text and f"%{mhc.POST}." in text
    assert "%rtpu_mla_decode_attention." in text
    assert ge._takes_kernels(slots * cfg.n_experts_per_tok, 8, None)
    assert f"%{ge.SWIGLU}." in text and f"%{ge.MATMUL}." in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= cache["kv"].size * 2
    assert mem.temp_size_in_bytes < 2 ** 28
    assert _copies_of(text, cache) == []
    assert _copies_of(text, {k: params["moe"][k]
                             for k in ge.EXPERT_STACKS}) == []
    vec = _sds(chip, (slots,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache, _sds(chip, (slots, 1), jnp.int32),
        vec, vec, vec, _sds(chip, (slots,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "mhc_step_rows", "mhc_sinkhorn_err_max",
        "moe_layer_steps", "moe_expert_hits", "moe_pairs_routed",
        "moe_pairs_held", "mla_decode_rows", "mla_decode_rows_streamed"}


def test_xing_mhc_tick_prefill_returns_one_token(chip):
    """The tick's prefill at the largest bucket: the mHC kernels over
    2,048 rows, the flash kernel on keys and values padded to 256 and
    the grouped kernels in it, one token and the counters out, the
    cache aliased and no array of its shape copied."""
    from ray_tpu.ops import mhc
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    xing, cfg = _xing_5l()
    loop = DecodeLoop(cfg, max_len=2048, chunk=8)
    params = _abstract(chip, functools.partial(xing.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: xing.init_kv_cache(cfg, 32, 2048))
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, 2048), jnp.int32), scalar, scalar,
            scalar)
    c = loop.prefill_inplace.lower(*args).compile()
    out = jax.eval_shape(loop.prefill_inplace, *args)
    assert (out[0].shape, out[0].dtype) == ((1,), jnp.int32)
    assert len(out) == 3 and {"mhc_prefill_rows", "mhc_sinkhorn_err_max",
                              "moe_pairs_held"} <= set(out[2])
    text = c.as_text()
    assert f"%{mhc.PRE}." in text and f"%{mhc.POST}." in text
    assert "%flash_attention" in text and " while(" in text
    assert "%rtpu_grouped_swiglu." in text and "ragged-dot" not in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= cache["kv"].size * 2
    assert mem.temp_size_in_bytes < 2 ** 30
    assert _copies_of(text, cache) == []


def test_xing_mhc_check_programs_rewrite_the_engines_own_cache(chip):
    """What `benchmark/drivers/serve_routed_mhc.py` replays at set-up,
    at the shapes the window times (a second 3.36 GB cache does not fit
    beside the weights): the donating twins of `prefill_last` and
    `decode_step_whole` alias the cache and copy no array of its shape,
    run the same kernels as the tick's programs (32 slots x 4 experts a
    token are whole row tiles: the grouped kernels, no ``ragged_dot``),
    and return logits and everything the check reads beside them."""
    from ray_tpu.ops import mhc
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    xing, cfg = _xing_5l()
    slots, rows = 32, 2048
    loop = DecodeLoop(cfg, max_len=rows, chunk=8)
    params = _abstract(chip, functools.partial(xing.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: xing.init_kv_cache(cfg, slots, rows))
    scalar = _sds(chip, (), jnp.int32)
    step = (params, cache, _sds(chip, (slots, 1), jnp.int32),
            _sds(chip, (slots,), jnp.int32))
    prefill = (params, cache, _sds(chip, (1, 512), jnp.int32), scalar,
               scalar, scalar)
    for program, args, width in (
            (loop.decode_step_whole_inplace, step, slots),
            (loop.prefill_last_inplace, prefill, 1)):
        c = program.lower(*args).compile()
        text = c.as_text()
        assert f"%{mhc.PRE}." in text and f"%{mhc.POST}." in text
        assert "%rtpu_grouped_swiglu." in text and "ragged-dot" not in text
        mem = c.memory_analysis()
        assert mem.alias_size_in_bytes >= cache["kv"].size * 2
        assert _copies_of(text, cache) == []
        logits, _, counters, seen = jax.eval_shape(program, *args)
        assert logits.shape == (width, cfg.vocab_size)
        mixes = seen["mhc_mixes"]
        assert mixes["first"].shape == (width, 4 * 3584)
        assert mixes["after"].shape == (5, 2, width, 4 * 3584)
        assert mixes["y"].shape == (5, 2, width, 3584)
        assert seen["mhc_end"]["streams"].shape == (width, 4, 3584)



# ------------------------------------------------- the looped family

def _ouro_args(chip, slots=8, rows=512):
    """Ouro-2.6B's widths (benchmark/configs/ouro-2.6b.json) at 2 layers
    — the layer body is scanned, so its HLO is the 48-layer one's — with
    the cell's 8 slots of 512 rows and all four passes."""
    from ray_tpu.models import ouro
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg = ouro.OuroConfig(n_layers=2)
    params = _abstract(chip, functools.partial(ouro.init_params, cfg),
                       jax.random.PRNGKey(0))
    cache = _abstract(chip, lambda: ouro.init_kv_cache(cfg, slots, rows))
    assert cache["k"].shape == (8, slots, 16, rows, 128)
    return cfg, DecodeLoop(cfg, max_len=rows, chunk=8), params, cache


def _moves_of(text: str, params) -> list:
    """Copies whose result is one of the blocks' matrices (a layer's
    ``[1, ..]`` or the stack's): a weight MOVED in HBM where a product
    should read it where it lies. (A fusion whose root is a layer's
    slice into ``S(1)`` is the compiler's prefetch of that matrix into
    fast memory: its one read, not a copy.)"""
    shapes = {tuple(a.shape[1:]) for a in params["blocks"].values()
              if a.ndim == 3}
    moved = re.compile(r"= bf16\[(?:\d+,)?(\d+),(\d+)\]\S* copy\(")
    return [line.strip()[:120] for line in text.splitlines()
            for m in [moved.search(line)]
            if m and (int(m.group(1)), int(m.group(2))) in shapes]


def test_ouro_decode_chunk_reads_the_stack_four_times_and_copies_it_never(
        chip):
    """The looped step through the engine's own `decode_chunk`: four
    scans over the layers inside the scan over the chunk's steps, the
    decode-attention kernel once a scan body under its name, the
    192-entry cache aliased and never copied, and NO matrix of the
    stack copied or sliced out on its own (`models/common.py`
    ``_layer_of``'s trap, PR 33; the q, k, v stacks stored output-major
    are read where they lie: stored by head the compiler re-laid all
    three out once a program)."""
    cfg, loop, params, cache = _ouro_args(chip)
    c = _lower_decode_chunk(chip, loop, params, cache, 8)
    text = c.as_text()
    assert text.count(" while(") == 1 + cfg.n_loops
    assert text.count("%rtpu_decode_attention.") >= cfg.n_loops
    _assert_cache_in_place(c, cache)
    # Three stacks of 2 layers re-laid out would be 50 MB held.
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 24
    assert _moves_of(text, params) == []
    vec = _sds(chip, (8,), jnp.int32)
    out = jax.eval_shape(
        loop.decode_chunk, params, cache, _sds(chip, (8, 1), jnp.int32),
        vec, vec, vec, _sds(chip, (8,), jnp.bool_))
    assert len(out) == 8 and set(out[7]) == {
        "decode_attn_rows", "decode_attn_rows_streamed", "loop_passes",
        "loop_layer_steps"}


@pytest.mark.parametrize("bucket", [64, 256])
def test_ouro_prefills_write_each_pass_once_and_copy_no_weight(chip, bucket):
    """The tick's prefill and the check's donating twin at the cell's
    smallest and largest bucket: one token (or one row of logits and
    what the check reads) out, the cache aliased, no matrix of the
    stack moved; what they hold beside the cache is the slot's rows
    (`in_slot`: 0.03 GB at 2 layers, 0.8 at 48) and the new rows."""
    cfg, loop, params, cache = _ouro_args(chip)
    scalar = _sds(chip, (), jnp.int32)
    args = (params, cache, _sds(chip, (1, bucket), jnp.int32), scalar,
            scalar, scalar)
    for program in (loop.prefill_inplace, loop.prefill_last_inplace):
        c = program.lower(*args).compile()
        text = c.as_text()
        assert text.count(" while(") == cfg.n_loops
        mem = c.memory_analysis()
        assert mem.alias_size_in_bytes >= 2 * cache["k"].size * 2
        assert mem.temp_size_in_bytes < 2 ** 28
        assert _moves_of(text, params) == []
    token, _, counters = jax.eval_shape(loop.prefill_inplace, *args)
    assert (token.shape, token.dtype) == ((1,), jnp.int32)
    assert set(counters) == {"loop_prefill_passes"}
    logits, _, _, seen = jax.eval_shape(loop.prefill_last_inplace, *args)
    assert logits.shape == (1, cfg.vocab_size)
    assert seen["gates"].shape == (cfg.n_loops, 1, bucket)
    assert seen["blocks"]["handed"].shape == (cfg.n_entries, 1, 2048)
