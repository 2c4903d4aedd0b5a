"""Does the system still start on the chip?

One process that owns the TPU and drives the two programs that run on
it through the entry points a user calls, at the full width and depth
of Llama-3.2-1B (`llama.LLAMA3_1B`, bf16, weights from ``--seed``):

- *kernels*: every Pallas kernel on the path, executed on the device at
  the model's shapes and compared with its own jnp reference;
- *serve*: ``serve.run(build_llm_deployment(...),
  _local_testing_mode=True)`` answering concurrent requests on the
  default engine (its one decode path: ``ops/`` picks the kernel),
  prefill logits compared with plain ``llama.forward``;
- *train*: ``spmd.sharded_init`` + ``spmd.make_train_step`` on a
  one-device mesh, a few steps on one repeated batch, plain and with
  ``fused_ops=True``.

``--chips 4`` runs ONLY the ``fsdp=2 x tp=2`` sharded train step and
the same steps on a one-device mesh it is compared with.

Each phase prints one JSON line of observations (seconds, peak device
bytes, errors against the references). The LAST line of stdout is the
verdict: ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": 1}}``. Any failed phase ends the run: exit code 1 and
``"ok": false``. Without a TPU the script fails at once.

``--rehearse`` is the CPU rehearsal: tiny sizes, kernels under the
Pallas interpreter. Its last line names the platform it really ran on
(``cpu``), so a rehearsal can never read as a chip pass.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback


def _emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def _device_row() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _require(ok, what) -> None:
    """The smoke's checks are its purpose: raise (``assert`` would
    vanish under ``python -O``)."""
    if not ok:
        raise SmokeFailure(str(what))


def _device_bytes(stat: str) -> list:
    """One `memory_stats` entry per device, where the backend reports
    it (the CPU backend of the rehearsal does not)."""
    import jax

    return [(d.memory_stats() or {}).get(stat) for d in jax.devices()]


class Recorder:
    """One JSON line of observations per phase: what the device still
    held when the phase began, what the compile cache served during it,
    and the device's peak after it (a running maximum over the process:
    the backend cannot reset it)."""

    def __init__(self, cache):
        self.cache = cache

    def begin(self) -> dict:
        gc.collect()
        return {"requests": self.cache.requests, "hits": self.cache.hits,
                "bytes": _device_bytes("bytes_in_use")}

    def emit(self, phase: str, began: dict, **observed) -> None:
        _emit({"phase": phase, **observed,
               "compile_requests": self.cache.requests - began["requests"],
               "cache_hits": self.cache.hits - began["hits"],
               "bytes_in_use_at_start": began["bytes"],
               "peak_bytes_in_use": _device_bytes("peak_bytes_in_use")})


def _rel_err(got, ref) -> float:
    """max |got - ref| / max |ref|, in fp32."""
    import jax.numpy as jnp

    g = jnp.asarray(got, jnp.float32)
    r = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(g - r))) / max(
        float(jnp.max(jnp.abs(r))), 1e-30)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""

    cfg: object
    interpret: bool          # Pallas kernels under the interpreter
    batch: int               # serve slots == train batch == kernel batch
    seq: int                 # serve max_len == train sequence
    buckets: tuple           # serve prompt buckets
    prompt_lens: tuple
    new_tokens: tuple
    prefix_block: int = 16   # the engine's KV block


def _sizes(rehearse: bool) -> Sizes:
    from ray_tpu.models import llama

    if rehearse:
        import jax.numpy as jnp

        cfg = llama.tiny_config(max_seq_len=256, dtype=jnp.float32,
                                interpret_kernels=True)
        return Sizes(cfg, True, 4, 256, (32, 64), (9, 20, 33, 50),
                     (6, 9, 12, 7))
    # The one-device train step holds the whole model and its Adam state
    # (8.4 GB of arguments) on one chip: that is "at the memory's limit"
    # (llama.LlamaConfig.remat_policy), where the default "attention"
    # needs 16.01 GB of the chip's 15.75 and the compile is refused.
    cfg = dataclasses.replace(llama.LLAMA3_1B, max_seq_len=2048,
                              remat_policy="nothing")
    return Sizes(cfg, False, 8, 2048, (128, 512),
                 (37, 120, 200, 333, 450, 64),
                 (32, 48, 64, 40, 56, 32))


# ------------------------------------------------------------- kernels

# Written tolerances, as max |kernel - reference| / max |reference| in
# fp32. Kernel and reference both compute in fp32 and round once to the
# storage dtype, so the elementwise kernels may differ by a unit or two
# in bf16's last place (2^-8); the attention kernels also differ in the
# order of their bf16 matmul accumulations.
_TOL_ELEMENTWISE = 2.0 ** -6
_TOL_ATTENTION = 4e-2


def phase_kernels(sz: Sizes, seed: int, rec: Recorder) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu import ops

    cfg, it = sz.cfg, sz.interpret
    b, s, d, f = sz.batch, sz.seq, cfg.d_model, cfg.d_ff
    h, kh, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    key = jax.random.PRNGKey(seed)
    began = rec.begin()
    t0 = time.perf_counter()

    def rnd(i, shape, dtype=dt):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32).astype(dtype)

    errors = {}

    def check(name, got, ref, tol):
        got, ref = jax.block_until_ready((got, ref))
        worst = 0.0
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            _require(g.shape == r.shape and g.dtype == r.dtype,
                     (name, g.shape, r.shape, g.dtype, r.dtype))
            _require(jnp.all(jnp.isfinite(g.astype(jnp.float32))),
                     f"{name}: not finite")
            worst = max(worst, _rel_err(g, r))
        errors[name] = worst
        _require(worst <= tol, f"{name}: error {worst:.3e} over {tol:.3e}")

    # Glue kernels at the train step's activation shapes.
    x, res = rnd(0, (b, s, d)), rnd(1, (b, s, d))
    scale = rnd(2, (d,)) * 0.1
    check("fused_rms_norm",
          jax.jit(lambda x: ops.fused_rms_norm(x, scale, interpret=it))(x),
          jax.jit(lambda x: ops.rms_norm(x, scale))(x), _TOL_ELEMENTWISE)
    check("fused_rms_norm_residual",
          jax.jit(lambda x, r: ops.fused_rms_norm_residual(
              x, r, scale, interpret=it))(x, res),
          jax.jit(lambda x, r: (ops.rms_norm(x + r, scale), x + r))(x, res),
          _TOL_ELEMENTWISE)
    gate, up = rnd(3, (b, s, f)), rnd(4, (b, s, f))
    check("fused_swiglu",
          jax.jit(lambda g, u: ops.fused_swiglu(g, u, interpret=it))(gate, up),
          jax.jit(ops.swiglu_reference)(gate, up), _TOL_ELEMENTWISE)

    q, k, v = rnd(5, (b, s, h, hd)), rnd(6, (b, s, kh, hd)), \
        rnd(7, (b, s, kh, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    theta = cfg.rope_theta

    def rope_fused(q, k):
        return ops.fused_qk_rope(q, k, pos, theta, interpret=it)

    def rope_ref(q, k):
        return (ops.apply_rope(q, pos, theta), ops.apply_rope(k, pos, theta))

    check("fused_qk_rope", jax.jit(rope_fused)(q, k),
          jax.jit(rope_ref)(q, k), _TOL_ELEMENTWISE)
    # One decode-shaped call too: T=1 rows at a cache offset.
    pos1 = jnp.full((b, 1), s - 3, jnp.int32)
    check("fused_qk_rope_decode",
          jax.jit(lambda q, k: ops.fused_qk_rope(
              q, k, pos1, theta, interpret=it))(q[:, :1], k[:, :1]),
          jax.jit(lambda q, k: (ops.apply_rope(q, pos1, theta),
                                ops.apply_rope(k, pos1, theta)))(
              q[:, :1], k[:, :1]), _TOL_ELEMENTWISE)
    # Cotangent weights ride as jit ARGUMENTS: a closed-over array is
    # baked into the executable (hundreds of MB here — slow to compile
    # and too big for the persistent cache).
    wq, wk = rnd(8, q.shape, jnp.float32), rnd(9, k.shape, jnp.float32)

    def rope_grads(fn):
        def loss(q, k, wq, wk):
            oq, ok = fn(q, k)
            return jnp.sum(oq * wq) + jnp.sum(ok * wk)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    check("fused_qk_rope_bwd", rope_grads(rope_fused)(q, k, wq, wk),
          rope_grads(rope_ref)(q, k, wq, wk), _TOL_ELEMENTWISE)

    # Flash path of full_causal_attention, forward and backward,
    # against the portable online-softmax scan: at the model's geometry,
    # and at the train cell's device shard (`smollm2.sft.fsdp2tp2`: 16
    # sequences of 2,048, 16 heads of 64 with a KV head each), whose
    # blocks `ops.attention.flash_block_sizes` chose from a sweep.
    def attention_case(name, q, k, v):
        s, hd = q.shape[1], q.shape[3]
        pos = jnp.broadcast_to(jnp.arange(s), q.shape[:2])

        def attn_ref(q, k, v):
            return ops.blockwise_attention(q, k, v, q_positions=pos,
                                           kv_positions=pos,
                                           block_k=min(512, s))

        _require(it or ops.use_fused_kernel(True, True, s, hd),
                 f"no flash kernel at seq {s}, head_dim {hd}")
        wo = rnd(10, q.shape, jnp.float32)

        def attn_grads(fn):
            return jax.jit(jax.grad(
                lambda q, k, v, wo: jnp.sum(fn(q, k, v) * wo),
                argnums=(0, 1, 2)))

        check(name, jax.jit(ops.full_causal_attention)(q, k, v),
              jax.jit(attn_ref)(q, k, v), _TOL_ATTENTION)
        check(name + "_bwd",
              attn_grads(ops.full_causal_attention)(q, k, v, wo),
              attn_grads(attn_ref)(q, k, v, wo), _TOL_ATTENTION)

    attention_case("flash_attention", q, k, v)
    shard = (b, s, 4, hd) if it else (16, 2048, 16, 64)
    attention_case("flash_attention_hd64_shard",
                   *(rnd(20 + i, shard) for i in range(3)))

    # The decode kernel on the engine-native [B, KH, S, D] cache.
    qd = rnd(11, (b, h, hd))
    ck, cv = rnd(12, (b, kh, s, hd)), rnd(13, (b, kh, s, hd))
    lengths = jax.random.randint(jax.random.fold_in(key, 14), (b,), 1, s + 1)
    lengths = lengths.at[0].set(s).at[-1].set(1)
    check("decode_attention",
          ops.decode_attention(qd, ck, cv, lengths, layout="bksd",
                               interpret=it),
          ops.decode_attention_reference(
              qd, ck.swapaxes(1, 2), cv.swapaxes(1, 2), lengths),
          _TOL_ATTENTION)

    # The grouped expert products of a prefill (`rtpu_grouped_swiglu`,
    # `rtpu_grouped_matmul`) against the ``lax.ragged_dot`` path the
    # decode step keeps, at GLM's widths and middle bucket (2,048 tokens
    # x 4 of 64 experts, a quarter of the bucket padding, the second
    # layer of a stack) and as a chip's SHARE (8 of 256 experts a token,
    # 32 held: seven rows of eight in no group). Both round each
    # product once to bf16; they differ in the order of the float32
    # accumulations, a unit of bf16's last place at the output's top.
    # What is compared is the layer's sum under gates of one (`gated_sum`
    # selects the rows of no group out: no kernel wrote them).
    from ray_tpu.ops import grouped_experts as ge

    t, d_e, f_e = (256, d, f) if it else (2048, 2048, 1536)
    n_exp, n_all = (8, 32) if it else (64, 256)
    stacks = {"w_gate": rnd(30, (2 * n_exp, d_e, f_e)) * d_e ** -0.5,
              "w_up": rnd(31, (2 * n_exp, d_e, f_e)) * d_e ** -0.5,
              "w_down": rnd(32, (2 * n_exp, f_e, d_e)) * f_e ** -0.5}
    stacks = {k: w.astype(dt) for k, w in stacks.items()}
    xe = rnd(33, (t, d_e))
    liking = 0.8 * rnd(34, (n_all,), jnp.float32)
    chosen = lambda i, k, n: jax.lax.top_k(
        liking[:n] + jax.random.gumbel(jax.random.fold_in(key, i), (t, n)),
        k)[1].astype(jnp.int32)

    def grouped(name, experts, n, takes_kernels, **kw):
        rule = ge._takes_kernels
        _require(rule(experts.size, kw["held"][1] if kw.get("held") else n,
                      it), f"{name}: the rule sends a prefill to ragged_dot")
        if not takes_kernels:           # the reference: steered HERE
            ge._takes_kernels = lambda *_: False
        try:
            def layer(x, e, w, i):
                pairs, load = ge.grouped_swiglu(x, e, w, i, n,
                                                interpret=it or None, **kw)
                return ge.gated_sum(pairs, jnp.ones(e.shape, jnp.float32)), load

            return jax.jit(layer)(xe, experts, stacks, jnp.int32(1))
        finally:
            ge._takes_kernels = rule

    for name, experts, n, kw in (
            ("grouped_experts", chosen(35, 4, n_exp), n_exp,
             {"valid": jnp.arange(t) < t * 3 // 4}),
            ("grouped_experts_held", chosen(36, 8, n_all), n_all,
             {"held": (n_all // 4, n_exp // 2)})):
        (y, load), (y_ref, load_ref) = (grouped(name, experts, n, on, **kw)
                                        for on in (True, False))
        _require(bool(jnp.all(load == load_ref)), f"{name}: load differs")
        check(name, y, y_ref, _TOL_ELEMENTWISE)

    rec.emit("kernels", began, seconds=round(time.perf_counter() - t0, 2),
             interpret=it, max_rel_err=errors)


# --------------------------------------------------------------- serve

# bf16 logits of two independent implementations of a 16-layer model:
# relative L2 error of the whole vocabulary row, and how far below the
# reference's best logit the engine's first token may sit.
_TOL_LOGITS_REL_L2 = 3e-2
_TOL_FIRST_TOKEN_MARGIN = 0.1


def phase_serve(sz: Sizes, seed: int, rec: Recorder) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import build_llm_deployment

    began = rec.begin()
    t0 = time.perf_counter()
    handle = serve.run(
        build_llm_deployment(engine_kwargs=dict(
            cfg=sz.cfg, max_batch=sz.batch, max_len=sz.seq,
            prompt_buckets=list(sz.buckets), decode_chunk=8,
            prefix_block=sz.prefix_block, seed=seed)),
        _local_testing_mode=True)
    engine = handle._instance.engine
    try:
        rng = np.random.default_rng(seed)
        vocab = sz.cfg.vocab_size
        prompts = [[int(t) for t in rng.integers(1, vocab, n)]
                   for n in sz.prompt_lens]

        def ask(i):
            return handle.remote({"prompt_ids": prompts[i],
                                  "max_new_tokens": sz.new_tokens[i]})

        # One request per prompt bucket first: these pay the compiles.
        bucket_of = [min(bk for bk in sz.buckets if bk >= n)
                     for n in sz.prompt_lens]
        for bk in sz.buckets:
            ask(bucket_of.index(bk)).result(timeout=900)
        compile_s = time.perf_counter() - t0

        t1 = time.perf_counter()
        outs = [r.result(timeout=900)
                for r in [ask(i) for i in range(len(prompts))]]
        run_s = time.perf_counter() - t1
        for i, out in enumerate(outs):
            ids = out["token_ids"]
            _require(len(ids) == sz.new_tokens[i],
                     f"request {i}: {len(ids)} tokens")
            _require(all(0 <= t < vocab for t in ids),
                     f"request {i}: id out of range in {ids}")
        stats = handle.stats.remote().result(timeout=60)
        _require(stats["active"] == 0 and stats["waiting"] == 0, stats)

        # Reference: plain llama.forward on the engine's own params,
        # prompts padded to their bucket (causal: padding after the
        # last real token cannot reach it).
        ref_fwd = jax.jit(lambda p, t: llama.forward(p, t, sz.cfg))
        margins = []
        for i, (prompt, out) in enumerate(zip(prompts, outs)):
            padded = np.zeros((1, bucket_of[i]), np.int32)
            padded[0, :len(prompt)] = prompt
            ref = ref_fwd(engine.params, jnp.asarray(padded))[
                0, len(prompt) - 1].astype(jnp.float32)
            _require(jnp.all(jnp.isfinite(ref)), f"reference {i}: not finite")
            spread = float(jnp.max(jnp.abs(ref)))
            margin = float(jnp.max(ref) - ref[out["token_ids"][0]]) / spread
            margins.append(margin)
            _require(margin <= _TOL_FIRST_TOKEN_MARGIN,
                     f"request {i}: first token {margin:.3f} below the "
                     f"reference's best logit")
            if i == len(prompts) - 2:
                # The engine's own prefill program on the same prompt
                # (functional: the returned cache is dropped).
                got, _ = engine.loop.prefill(
                    engine.params, engine.cache, jnp.asarray(padded),
                    jnp.int32(0), jnp.int32(0))
                got = got[0, len(prompt) - 1].astype(jnp.float32)
                rel_l2 = float(jnp.linalg.norm(got - ref)
                               / jnp.linalg.norm(ref))
                max_abs = float(jnp.max(jnp.abs(got - ref)))
                _require(rel_l2 <= _TOL_LOGITS_REL_L2,
                         f"prefill logits off llama.forward: {rel_l2:.3e}")
    finally:
        engine.close()
    rec.emit("serve", began, compile_seconds=round(compile_s, 2),
             run_seconds=round(run_s, 2), requests=len(outs),
             new_tokens=sum(sz.new_tokens), logits_rel_l2_vs_forward=rel_l2,
             logits_max_abs_err=max_abs, first_token_margin_max=max(margins))


# --------------------------------------------------------------- train

def _train_steps(cfg, mesh, key, tokens_np, steps: int):
    """(losses, seconds of the first step, seconds of the rest, final
    TrainState) through the repo's own sharded init + train step."""
    import jax

    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import mesh_context

    # warmup=1: the schedule's first update has rate 0, every later one
    # the full rate, so a handful of steps on one repeated batch must
    # bring the loss down. 1e-4 moves bf16 weights by a unit or two in
    # their last place; the default 100-step warm-up would round away.
    tx = spmd.default_optimizer(lr=1e-4, warmup=1)
    with mesh_context(mesh):
        state = spmd.sharded_init(cfg, mesh, key, tx)
        step = spmd.make_train_step(cfg, mesh, tx)
        tokens = jax.device_put(tokens_np, spmd.data_sharding(mesh))
        losses, times = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, metrics = step(state, tokens)
            jax.block_until_ready((state, metrics))
            times.append(time.perf_counter() - t0)
            losses.append(float(metrics["loss"]))
    return losses, times[0], sum(times[1:]), state


def _check_losses(losses) -> None:
    import math

    _require(all(math.isfinite(x) for x in losses), f"loss: {losses}")
    _require(losses[-1] < losses[0], f"loss did not fall: {losses}")


def _batch(sz: Sizes, seed: int):
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, sz.cfg.vocab_size, (sz.batch, sz.seq)).astype(np.int32)


def phase_train(sz: Sizes, seed: int, rec: Recorder) -> None:
    import jax

    from ray_tpu.parallel.mesh import single_device_mesh

    key = jax.random.PRNGKey(seed)
    tokens = _batch(sz, seed)
    first = {}
    for name, fused in (("train", False),
                        ("train_fused", "interpret" if sz.interpret
                         else True)):
        cfg = dataclasses.replace(sz.cfg, fused_ops=fused)
        began = rec.begin()
        steps = 4 if not fused else 3
        losses, first_s, rest_s, state = _train_steps(
            cfg, single_device_mesh(), key, tokens, steps)
        _check_losses(losses)
        first[name] = losses[0]
        del state
        rec.emit(name, began, batch=list(tokens.shape), losses=losses,
                 first_step_seconds=round(first_s, 2),
                 later_steps_seconds=round(rest_s, 2))
    # Same params, same batch: the fused kernels change rounding only.
    gap = abs(first["train_fused"] - first["train"])
    _require(gap <= 2e-2 * abs(first["train"]), f"fused vs plain: {first}")


def phase_train_sharded(sz: Sizes, seed: int, rec: Recorder) -> None:
    """``--chips 4``: the fsdp=2 x tp=2 step against the one-device
    step, same key and batch, in this one process."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import mesh_2d, single_device_mesh

    devices = jax.devices()
    key = jax.random.PRNGKey(seed)
    tokens = _batch(sz, seed)
    steps = 3

    began = rec.begin()
    ref_losses, ref_first, ref_rest, state = _train_steps(
        sz.cfg, single_device_mesh(devices[0]), key, tokens, steps)
    _check_losses(ref_losses)
    del state
    rec.emit("train_one_device", began, losses=ref_losses,
             first_step_seconds=round(ref_first, 2),
             later_steps_seconds=round(ref_rest, 2))

    began = rec.begin()
    mesh = mesh_2d(4, tp=2, devices=devices)
    _require(mesh.shape["fsdp"] == 2 and mesh.shape["tp"] == 2, mesh.shape)
    losses, first_s, rest_s, state = _train_steps(
        sz.cfg, mesh, key, tokens, steps)
    _check_losses(losses)
    spmd.assert_params_sharded(state.params, mesh,
                               llama.param_logical_axes(sz.cfg))
    # Every device holds shards and none holds the whole model —
    # weights AND optimizer state.
    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    _require(all(0 < n <= 0.5 * total for n in held.values()),
             f"state bytes per device {held} of {total}")
    # bf16 tolerance: the sharded program reduces in another order.
    gaps = [abs(a - r) / abs(r) for a, r in zip(losses, ref_losses)]
    _require(max(gaps) <= 1e-2, f"{losses} vs one device {ref_losses}")
    del state
    rec.emit("train_fsdp2_tp2", began, losses=losses,
             first_step_seconds=round(first_s, 2),
             later_steps_seconds=round(rest_s, 2),
             state_bytes_total=total, state_bytes_per_device=held,
             max_rel_loss_gap_vs_one_device=max(gaps))


# ------------------------------------------------------ chip detection

def phase_detect() -> None:
    """The node daemon counts chips from device nodes (it must never
    load the TPU runtime). Hold that count to what JAX sees."""
    import glob

    import jax

    from ray_tpu.core.resources import _detect_tpu

    seen = {pat: sorted(glob.glob(pat))
            for pat in ("/dev/accel*", "/dev/vfio/*")}
    n = _detect_tpu()[0]
    _emit({"phase": "detect", "device_nodes": seen, "detected_chips": n,
           "jax_device_count": jax.device_count(),
           "RTPU_TPU_CHIPS": os.environ.get("RTPU_TPU_CHIPS")})
    _require(n == jax.device_count(),
             f"device nodes say {n} chips, JAX {jax.device_count()}: {seen}")


# ---------------------------------------------------------------- main

def run(args) -> dict:
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    import jax
    import jaxlib

    from ray_tpu.util import compile_cache

    device = _device_row()
    _require(device["platform"] == "tpu" or args.rehearse,
             f"chip_smoke needs a TPU; JAX found {device['platform']} "
             f"({device['kind']})")
    _require(device["count"] == args.chips,
             f"chip_smoke --chips {args.chips} found {device['count']} "
             f"devices")
    cache = compile_cache.configure()
    rec = Recorder(cache)
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", None)
    except ImportError:
        libtpu_version = None
    _emit({"phase": "start", "device": device, "rehearsal": args.rehearse,
           "jax": jax.__version__, "jaxlib": jaxlib.__version__,
           "libtpu": libtpu_version, "compile_cache_dir": cache.path,
           "compile_cache_dir_from_env":
               bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
           "compile_cache_entries_at_start":
               len(os.listdir(cache.path)) if os.path.isdir(cache.path) else 0,
           "seed": args.seed})
    sz = _sizes(args.rehearse)
    if args.chips == 4:
        phase_train_sharded(sz, args.seed, rec)
        return device
    # Each phase drops what it kept on the device before the next
    # begins (Recorder.begin collects): an engine and an Adam state
    # do not fit 16 GB together.
    phase_kernels(sz, args.seed, rec)
    phase_serve(sz, args.seed, rec)
    phase_train(sz, args.seed, rec)
    if not args.rehearse:
        phase_detect()
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny sizes, interpreted kernels")
    args = ap.parse_args()
    t0 = time.perf_counter()
    try:
        device = run(args)
    except Exception as e:  # noqa: BLE001 — the run ends here, failed
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": repr(e)[:300]}), flush=True)
        return 1
    _emit({"phase": "done", "seconds": round(time.perf_counter() - t0, 1)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
