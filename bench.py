"""Benchmark: BOTH north-star metrics (BASELINE.md) on the available chip.

Prints one JSON line per row, then ONE final merged line (the driver
records the tail line):

  {"metric": "train_mfu_llama8b_proxy", "value": <MFU>, "unit": "mfu",
   "vs_baseline": <MFU/0.40>, "train_mfu_llama1b": ...,
   "llm_decode_tokens_per_s": ..., "serve_llm_requests_per_s": ...,
   "serve_llm_p50_ttft_ms": ..., "serve_llm_p99_ttft_ms": ..., ...}

Rows:
- train_mfu_llama1b — full Llama-3-1B pretrain step, measured directly.
- train_mfu_llama8b_proxy — 8B-class MFU via a two-depth layer scan:
  one v5e chip (16 GB HBM) cannot hold 8B params + optimizer state, so
  the step is measured at two depths of the TRUE 8B layer geometry
  (d=4096, d_ff=14336, GQA 32/8, vocab 128k, seq 2048, full remat,
  chunked CE, SGD) and the per-layer time from the depth differential is
  extrapolated to 32 layers. The differential cancels the embed/head/CE
  cost shared by both runs; method fields are recorded in the row.
- llm_decode_tokens_per_s — the native continuous-batching engine
  (serve/engine/) decoding with Llama-1B weights on the chip.
- llm_engine — the engine suite (``--engine`` runs it standalone):
  decode tok/s, engine-side TTFT/TPOT p50, and prefix-cache hit rate
  under a shared-prefix workload; rows are labelled ``config:
  "tiny-cpu"`` when not measured on hardware.
- llm_engine_spec / llm_engine_spec_off — speculative decoding
  (prompt-lookup drafting + multi-token verify) on a repetitive
  workload, measured against the identical engine with speculation
  disabled: tok/s both ways, ``llm_spec_accept_rate``, and the
  ``spec_speedup`` ratio (greedy outputs are token-identical, so both
  rows count the same tokens).
- ops_microbench / decode_matmul_gbps — per-kernel rows (``--ops`` runs
  them standalone): fused-vs-unfused step time for the model-path glue
  (RMSNorm / rope / SwiGLU, ops/fused.py) and the decode-shaped matmul's
  weight-streaming GB/s at the working dtype vs weight-only int8
  (``baseline_dtype`` names the precision) — so a kernel
  regression is visible without a full train run.
- llm_decode_tokens_per_s_int8 — the decode bench re-run with
  ``quantize="int8"`` (weight-only int8, models/quant.py) on an
  otherwise identical engine; carries ``speedup_vs_f32``.
- serve_llm_* — req/s + p50/p99 TTFT through the FULL serve stack
  (controller/router/replica, tiny engine) in a CPU child process; the
  reference publishes no serve numbers (it delegates to vLLM), so these
  are absolute, tracked round-over-round.
- locality_scheduling — locality-aware scheduling suite (``--locality``
  runs it standalone) on a 4-node in-process CPU cluster:
  ``locality_hit_rate`` and ``object_bytes_pulled_per_task`` for the
  default scheduler vs a forced-random-placement baseline of the same
  workload.
- chaos_recovery — fault-recovery suite (``--chaos`` runs it
  standalone) on a real subprocess cluster: ``head_recovery_s`` (the
  head is SIGKILLed mid-workload; time until a NEW head-dependent
  submission — an actor creation — completes against the respawned
  head), ``object_reconstruction_s`` (the only holder of a task output
  is SIGKILLed; time for ``get()`` to complete via lineage
  re-execution), ``leaked_leases`` (the post-drain open-lease census
  over every node, which must be 0), and ``leaked_resources`` (the
  RTPU_DEBUG_RES cluster-wide acquire/release balance — BufferLease
  pins, node lease-table entries, KV reservations — aggregated over
  dump_flight, which must also be 0; the child always runs under
  RTPU_DEBUG_RPC=1 + RTPU_DEBUG_RES=1). Needs a loadable native store
  lib like the dataplane suite.
- dataplane — multi-writer object-plane suite (``--dataplane`` runs it
  standalone): K-process concurrent large puts through one sharded shm
  store (``single_put_gbps``, ``multi_put_gbps``, ``put_scaling_ratio``
  = multi/single — concurrent writers must not fall below one), node-to-
  node pull bandwidth over the scatter-gather transfer path
  (``pull_gbps``), and n-callers x n-actors calls with array args
  (``actor_args_nn_per_s``). Needs the native store lib (built from
  source on first use).
- data — streaming Dataset executor suite (``--data`` standalone):
  same-window alternating A/B of ``random_shuffle`` with the exchange
  on the channel mesh vs per-task RPC (``data_shuffle_gbps_channel`` /
  ``_task`` / ``data_shuffle_channel_speedup``), and a synthetic train
  loop over ``iter_batches(device_put=...)`` with the double-buffered
  loader vs inline transfers (``data_ingest_steps_per_s_buffered`` /
  ``_inline`` / ``data_ingest_overlap_speedup``) plus a pre-staged
  roofline (``data_ingest_efficiency``; ``cpu_cores`` on the row —
  overlap > 1 needs host cores for the loader thread). Needs the
  native store lib, like dataplane.

Structure: measurements run in CHILD subprocesses; the parent stays off
JAX (a parent that touched JAX would hold the chip, and the child that
needs it would fail or hang). ONE child owns the chip, once: it fails
without a TPU (no CPU stand-in for a device metric) and any failed phase
fails it. The parent then emits a structured failure record (still one
JSON line) instead of a traceback.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
import uuid

# Peak dense bf16 FLOP/s per chip by device kind substring.
PEAK_FLOPS = [
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6 lite", 918e12),
    ("v6e", 918e12),
]

CHILD_TIMEOUT_S = 2100     # first TPU compiles (4 programs) can take minutes
SERVE_TIMEOUT_S = 900
SERVE_ROUTED_TIMEOUT_S = 600  # whole 8-phase sweep child (2 replicas, CPU)
LOCALITY_TIMEOUT_S = 420   # per locality child (boots a 4-node cluster)
DATAPLANE_TIMEOUT_S = 420  # dataplane child (store bench + 2-node cluster)
CHAOS_TIMEOUT_S = 600      # chaos child (kill head/node + upgrade + recover)
SCALE_TIMEOUT_S = 300      # scale child (100 simulated nodes, head hot paths)
DAG_TIMEOUT_S = 420        # dag child (2-actor cluster, channel vs RPC hops)
DATA_TIMEOUT_S = 420       # data child (channel-vs-task shuffle + ingest A/B)
DISAGG_TIMEOUT_S = 900     # disagg serve sweep (colocated vs disagg TTFT)
KV_FLEET_TIMEOUT_S = 600   # fleet KV tier A/B (spill/pull vs recompute)
SERVE_SCALE_TIMEOUT_S = 900  # serve-scale suite (router sim + QoS flood
#                              + streaming disagg A/B cluster)


def peak_flops_for(device_kind: str) -> float:
    dk = device_kind.lower()
    for key, val in PEAK_FLOPS:
        if key in dk:
            return val
    raise ValueError(f"no peak FLOP/s known for device kind "
                     f"{device_kind!r}; add it to PEAK_FLOPS")


def _chip_devices() -> list:
    """The chip children measure the device: without a TPU they fail
    (a CPU timing is never written under a device metric's name). Also
    places JAX's persistent compile cache before the first compile."""
    import jax

    from ray_tpu.util import compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py: this child measures the chip; JAX found "
            f"{devices[0].platform} ({devices[0].device_kind})")
    print(f"compile cache: {compile_cache.configure().path}", file=sys.stderr,
          flush=True)
    return devices


# --------------------------------------------------------------------------
# train + decode child (owns the TPU)
# --------------------------------------------------------------------------

def _timed_steps(step, state, tokens, warmup: int, iters: int):
    """Returns (seconds_per_step, last_loss, state). JAX returns before
    the device finishes: each timed region ends in a host fetch of the
    loss scalar, which waits for every step before it."""
    for _ in range(warmup):
        state, metrics = step(state, tokens)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, tokens)
    loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    return dt / iters, loss, state


def _bench_train(cfg, batch, seq, warmup, iters, devices, tx=None):
    import numpy as np

    import jax

    from ray_tpu.parallel import spmd
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh, mesh_context

    # FSDP over every chip of the host (fsdp=1 on one chip); the
    # attention kernel is shard_mapped over the mesh's data axes.
    mesh = make_mesh(MeshSpec(fsdp=len(devices)), devices)
    tx = tx or spmd.default_optimizer(lr=1e-4)
    # ONE host key, created outside any mesh context (jax-lint
    # rng-reinit-per-mesh).
    key = jax.random.PRNGKey(0)
    with mesh_context(mesh):
        state = spmd.sharded_init(cfg, mesh, key, tx)
        step = spmd.make_train_step(cfg, mesh, tx)
        rng = np.random.default_rng(0)
        tokens = jax.device_put(
            rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
            spmd.data_sharding(mesh))
        step_s, loss, state = _timed_steps(step, state, tokens, warmup, iters)
    assert np.isfinite(loss), f"non-finite loss {loss}"
    del state
    return step_s


def _bench_8b_proxy(devices, kind: str) -> dict:
    """Two-depth layer scan of the true 8B layer geometry; projects MFU
    at n_layers=32 from the per-layer time differential."""
    import optax

    from ray_tpu.models import llama

    import jax

    base = dataclasses.replace(llama.LLAMA3_8B, max_seq_len=2048,
                               fused_ops=True)
    batch, seq, warmup, iters = 4, 2048, 2, 6
    depth_pairs = [(2, 6), (2, 4)]  # fallback shrinks HBM footprint
    # SGD: adamw's moment buffers alone would not fit next to 8B-geometry
    # params at depth 6 on a 16 GB chip; optimizer choice does not move
    # the matmul-bound step time materially (method recorded in the row).
    tx = optax.sgd(1e-4)
    last_err = None
    for d_lo, d_hi in depth_pairs:
        try:
            t_lo = _bench_train(dataclasses.replace(base, n_layers=d_lo),
                                batch, seq, warmup, iters, devices, tx)
            t_hi = _bench_train(dataclasses.replace(base, n_layers=d_hi),
                                batch, seq, warmup, iters, devices, tx)
        except jax.errors.JaxRuntimeError as e:
            # Out of HBM at this depth: shrink. Anything else (a kernel
            # the compiler refuses, a wrong result) fails the run.
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            last_err = e
            continue
        per_layer = (t_hi - t_lo) / (d_hi - d_lo)
        full_layers = llama.LLAMA3_8B.n_layers
        t_full = t_lo + (full_layers - d_lo) * per_layer
        tokens_per_s = batch * seq / t_full
        full_cfg = dataclasses.replace(base, n_layers=full_layers)
        mfu = (tokens_per_s * full_cfg.flops_per_token(seq)
               / peak_flops_for(kind) / len(devices))
        return {
            "metric": "train_mfu_llama8b_proxy",
            "value": round(mfu, 4),
            "unit": "mfu",
            "vs_baseline": round(mfu / 0.40, 4),
            "tokens_per_s_per_chip": round(tokens_per_s / len(devices), 1),
            "projected_step_time_s": round(t_full, 4),
            "method": (f"layer-scan: measured depths {d_lo},{d_hi} of 8B "
                       f"geometry (d4096/ff14336/GQA32-8/vocab128k), "
                       f"extrapolated to {full_layers} layers; SGD; full "
                       f"remat; chunked CE"),
            "measured_step_s": {str(d_lo): round(t_lo, 4),
                                str(d_hi): round(t_hi, 4)},
            "batch": batch, "seq": seq,
        }
    raise RuntimeError(f"8B proxy: every depth pair ran out of HBM: "
                       f"{last_err!r:.300}")


def _bench_decode(quantize: str = None) -> dict:
    """Steady-state decode throughput of the native LLM engine
    (``quantize="int8"`` measures the weight-only-quantized engine on
    the identical workload — the decode path is weight-bandwidth bound,
    so halving the weight bytes is the headline lever)."""
    import threading

    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = dataclasses.replace(llama.LLAMA3_1B, max_seq_len=512)
    max_batch, new_tokens, seconds = 8, 48, 8.0
    # decode_chunk=8: one host sync per 8 tokens.
    engine = LLMEngine(cfg, max_batch=max_batch, max_len=256,
                       prompt_buckets=[32], decode_chunk=8,
                       quantize=quantize)
    rng = np.random.default_rng(0)

    hi = min(1000, cfg.vocab_size - 1)

    def prompt():
        return [int(t) for t in rng.integers(1, hi, 16)]

    engine.generate(prompt(), max_new_tokens=2)  # compile prefill+decode
    stop_at = time.perf_counter() + seconds
    counts = [0] * max_batch
    client_errors = []

    def client(i):
        try:
            while time.perf_counter() < stop_at:
                out = engine.generate(prompt(), max_new_tokens=new_tokens,
                                      timeout=300)
                counts[i] += len(out["token_ids"])
        except Exception as e:  # noqa: BLE001 — recorded, never silent
            client_errors.append(repr(e)[:200])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(max_batch)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    engine.close()
    if client_errors and not sum(counts):
        raise RuntimeError(f"all decode clients failed: {client_errors[0]}")
    tps = sum(counts) / elapsed
    metric = ("llm_decode_tokens_per_s_int8" if quantize == "int8"
              else "llm_decode_tokens_per_s")
    row = {"metric": metric, "value": round(tps, 1),
           "unit": "tokens/s",
           "config": "llama3-1b",
           "max_batch": max_batch}
    if quantize:
        row["quantize"] = quantize
    if client_errors:
        # Dead clients deflate throughput: a plausible-but-wrong number
        # must carry the evidence (module invariant).
        row["client_errors"] = len(client_errors)
        row["client_error_sample"] = client_errors[0]
    return row


def _bench_engine() -> dict:
    """Engine suite: decode throughput + TTFT + prefix-cache hit rate
    measured directly on the serve/engine subsystem (no serve stack).

    Clients share a common prompt prefix, so slot recycling exercises
    the prefix cache the way a chat workload (shared system prompt)
    would; TTFT comes from the engine's own metrics (prefill + queue
    wait), not a client-side stopwatch."""
    import threading

    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = dataclasses.replace(llama.LLAMA3_1B, max_seq_len=512)
    max_batch, new_tokens, seconds = 8, 48, 8.0
    # Build THIS engine under the RTPU_DEBUG_JAX witness: the row
    # records the steady-state compiled-program counts (program creep =
    # silent retraces = the slowest possible regression). The WARM-UP
    # also runs under jax.transfer_guard("disallow") to prove the tick
    # is free of implicit transfers on this backend's real path — but
    # the guard (and the flag) comes OFF before the timed region, so a
    # guard-unclean path degrades to guard_clean:false instead of
    # destroying the headline row, and the timed numbers stay
    # comparable with pre-witness rounds. Program counting lives in the
    # wrappers installed at construction and keeps working after the
    # env restore; the other bench engines stay unwitnessed.
    prev_env = {k: os.environ.get(k)
                for k in ("RTPU_DEBUG_JAX",
                          "RTPU_DEBUG_JAX_TRANSFER_GUARD")}

    def restore_env():
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    os.environ["RTPU_DEBUG_JAX"] = "1"
    os.environ["RTPU_DEBUG_JAX_TRANSFER_GUARD"] = "disallow"
    engine = None
    guard_clean = True
    try:
        engine = LLMEngine(cfg, max_batch=max_batch, max_len=256,
                           prompt_buckets=[32], decode_chunk=8,
                           prefix_block=8, name="bench-engine")
        rng = np.random.default_rng(0)
        hi = min(1000, cfg.vocab_size - 1)
        shared = [int(t) for t in rng.integers(1, hi, 16)]  # prefix

        def prompt():
            return shared + [int(t) for t in rng.integers(1, hi, 8)]

        try:
            engine.generate(prompt(), max_new_tokens=2)  # guarded warm
        except Exception as e:  # noqa: BLE001 — guard violation: an
            # implicit transfer on THIS backend's tick path. Record it,
            # drop the guard, and re-warm so the row still measures.
            guard_clean = False
            guard_error = repr(e)[:200]
            os.environ.pop("RTPU_DEBUG_JAX_TRANSFER_GUARD", None)
            engine.generate(prompt(), max_new_tokens=2)
        restore_env()
        stop_at = time.perf_counter() + seconds
        counts = [0] * max_batch
        client_errors = []

        def client(i):
            try:
                while time.perf_counter() < stop_at:
                    out = engine.generate(prompt(),
                                          max_new_tokens=new_tokens,
                                          timeout=300)
                    counts[i] += len(out["token_ids"])
            except Exception as e:  # noqa: BLE001 — recorded below
                client_errors.append(repr(e)[:200])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(max_batch)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        stats = engine.stats()
    finally:
        # Restore on EVERY path (idempotent): a leaked flag would
        # witness-wrap (and transfer-guard) the spec engines built
        # later in this process.
        restore_env()
        if engine is not None:
            engine.close()
    if client_errors and not sum(counts):
        raise RuntimeError(f"all engine clients failed: {client_errors[0]}")
    row = {"metric": "llm_engine",
           "llm_decode_tokens_per_s": round(sum(counts) / elapsed, 1),
           "ttft_ms": stats["ttft_ms_p50"],
           "tpot_ms": stats["tpot_ms_p50"],
           "prefix_hit_rate": stats["prefix_hit_rate"],
           "decode_host_syncs": stats["decode_host_syncs"],
           # Recompile-witness program counts: steady-state should be
           # decode_chunk=1, prefill=1 (one bucket here) — growth
           # round-over-round means something started retracing.
           "compiled_programs": stats.get("compiled_programs"),
           # Was the GUARDED warm-up tick free of implicit transfers on
           # this backend's real path? (The timed region runs
           # unguarded either way.)
           "transfer_guard_clean": guard_clean,
           "config": "llama3-1b",
           "max_batch": max_batch, "decode_chunk": 8}
    if not guard_clean:
        row["transfer_guard_error"] = guard_error
    if client_errors:
        row["client_errors"] = len(client_errors)
        row["client_error_sample"] = client_errors[0]
    return row


def _bench_engine_spec() -> list:
    """Speculative-decoding suite: a repetitive/code-like workload —
    where prompt-lookup drafting bites — measured back-to-back with
    speculation ON and OFF on otherwise identical engines, so the
    speedup is a measured ratio from one process, not an assertion.

    The repetitive prompt drives the generation into the repetition
    loops real serving sees in code edits / templated output; greedy
    outputs are token-identical between the two runs (the engine's
    equivalence invariant), so both rows count the same tokens."""
    import threading

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = dataclasses.replace(llama.LLAMA3_1B, max_seq_len=512)
    max_batch, new_tokens, seconds = 8, 160, 8.0
    # A constant-token prompt is the distilled repetitive workload: the
    # generation locks into repetition loops the drafter tracks.
    prompt = [16] * 24
    spec_kw = dict(spec_draft_len=12, spec_chunk=2, spec_ngram_max=8)

    def run(spec: bool) -> dict:
        engine = LLMEngine(cfg, max_batch=max_batch, max_len=256,
                           prompt_buckets=[32], decode_chunk=8,
                           name=f"bench-spec-{'on' if spec else 'off'}",
                           **(spec_kw if spec else {}))
        for _ in range(2):  # compile prefill+decode(+verify), warm ctrl
            engine.generate(prompt, max_new_tokens=120)
        stop_at = time.perf_counter() + seconds
        counts = [0] * max_batch
        errors: list = []

        def client(i):
            try:
                while time.perf_counter() < stop_at:
                    out = engine.generate(prompt,
                                          max_new_tokens=new_tokens,
                                          timeout=300)
                    counts[i] += len(out["token_ids"])
            except Exception as e:  # noqa: BLE001 — recorded below
                errors.append(repr(e)[:200])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(max_batch)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        stats = engine.stats()
        engine.close()
        if errors and not sum(counts):
            raise RuntimeError(f"all spec-bench clients failed: "
                               f"{errors[0]}")
        out = {"tokens_per_s": round(sum(counts) / elapsed, 1),
               "stats": stats, "errors": errors}
        return out

    on = run(spec=True)
    off = run(spec=False)
    common = {"workload": "repetitive", "prompt_len": len(prompt),
              "max_batch": max_batch, "decode_chunk": 8,
              "config": "llama3-1b"}
    row_on = {"metric": "llm_engine_spec",
              "llm_decode_tokens_per_s": on["tokens_per_s"],
              "llm_spec_accept_rate": on["stats"]["spec_accept_rate"],
              "spec_drafted": on["stats"]["spec_drafted"],
              "spec_accepted": on["stats"]["spec_accepted"],
              "decode_utilization": on["stats"]["decode_utilization"],
              "spec_speedup": round(
                  on["tokens_per_s"] / off["tokens_per_s"], 2)
              if off["tokens_per_s"] else None,
              **spec_kw, **common}
    row_off = {"metric": "llm_engine_spec_off",
               "llm_decode_tokens_per_s": off["tokens_per_s"],
               "decode_utilization": off["stats"]["decode_utilization"],
               **common}
    for row, r in ((row_on, on), (row_off, off)):
        if r["errors"]:
            row["client_errors"] = len(r["errors"])
            row["client_error_sample"] = r["errors"][0]
    return [row_on, row_off]


def _bench_engine_mixed() -> list:
    """Mixed long-prompt + long-decode sweep: streaming decode clients'
    p99 TPOT while long prompts keep arriving, chunked prefill ON vs
    OFF on otherwise identical engines.

    Unchunked, every long-prompt admission prefills its whole bucket in
    one dispatch between the roster's decode chunks — the in-flight
    streams stall for the full prefill and the stall lands in their
    inter-token p99. Chunked, the same prompt materializes
    ``prefill_chunk`` tokens per tick, bounding any single stall (this
    is also what keeps the PR 9 SLO admission gate from shedding on a
    single long prompt). Greedy outputs are identical in both phases —
    only the interleaving changes."""
    import threading

    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = dataclasses.replace(llama.LLAMA3_1B, max_seq_len=512)
    seconds = 8.0
    long_prompt_len, decode_new = 200, 48
    rng = np.random.default_rng(3)
    hi = min(1000, cfg.vocab_size - 1)
    long_prompts = [[int(t) for t in rng.integers(1, hi,
                                                  long_prompt_len)]
                    for _ in range(4)]
    decode_prompts = [[int(t) for t in rng.integers(1, hi, 16)]
                      for _ in range(2)]

    def run(prefill_chunk: int) -> dict:
        engine = LLMEngine(cfg, max_batch=4, max_len=256,
                           prompt_buckets=[32, 224], decode_chunk=8,
                           prefill_chunk=prefill_chunk,
                           name=f"bench-mixed-{prefill_chunk}")
        # Warm every program: both prefill buckets + decode.
        engine.generate(long_prompts[0], max_new_tokens=2)
        engine.generate(decode_prompts[0], max_new_tokens=2)
        stop_at = time.perf_counter() + seconds
        gaps: list = []
        gaps_lock = threading.Lock()
        errors: list = []
        decoded = [0, 0]  # per-thread counts (no shared-counter race)

        def decode_client(i):
            try:
                while time.perf_counter() < stop_at:
                    last = None
                    local = []
                    for _ in engine.generate_stream(
                            decode_prompts[i], max_new_tokens=decode_new,
                            timeout=300):
                        now = time.perf_counter()
                        if last is not None:
                            local.append(now - last)  # TPOT, not TTFT
                        last = now
                        decoded[i] += 1
                    with gaps_lock:
                        gaps.extend(local)
            except Exception as e:  # noqa: BLE001 — recorded below
                errors.append(repr(e)[:200])

        def prompt_client(i):
            try:
                while time.perf_counter() < stop_at:
                    engine.generate(long_prompts[i % len(long_prompts)],
                                    max_new_tokens=2, timeout=300)
            except Exception as e:  # noqa: BLE001 — recorded below
                errors.append(repr(e)[:200])

        threads = ([threading.Thread(target=decode_client, args=(i,))
                    for i in range(2)]
                   + [threading.Thread(target=prompt_client, args=(i,))
                      for i in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        engine.close()
        if errors and not gaps:
            raise RuntimeError(f"mixed-bench clients failed: {errors[0]}")
        gaps.sort()
        p = {q: round(gaps[min(int(q / 100 * len(gaps)),
                               len(gaps) - 1)] * 1e3, 3)
             for q in (50, 99)} if gaps else {50: None, 99: None}
        return {"p50_tpot_ms": p[50], "p99_tpot_ms": p[99],
                "decode_tokens": sum(decoded),
                "tpot_samples": len(gaps), "errors": errors}

    chunk = 32
    on = run(prefill_chunk=chunk)
    off = run(prefill_chunk=0)
    common = {"workload": "mixed-long-prompt",
              "long_prompt_len": long_prompt_len,
              "decode_new_tokens": decode_new, "max_batch": 4,
              "config": "llama3-1b"}
    rows = []
    for tag, r, pc in (("chunked", on, chunk), ("unchunked", off, 0)):
        row = {"metric": f"llm_engine_mixed_{tag}",
               "prefill_chunk": pc, **{k: v for k, v in r.items()
                                       if k != "errors"}, **common}
        if r["errors"]:
            row["client_errors"] = len(r["errors"])
            row["client_error_sample"] = r["errors"][0]
        rows.append(row)
    if on["p99_tpot_ms"] and off["p99_tpot_ms"]:
        # >1 means chunked prefill flattened the decode tail.
        rows[0]["p99_tpot_flatness_vs_unchunked"] = round(
            off["p99_tpot_ms"] / on["p99_tpot_ms"], 2)
    return rows


def engine_child_main() -> None:
    """Standalone engine suite (``bench.py --engine``): engine row, the
    speculative-decoding on/off pair, and the mixed long-prompt sweep
    (chunked prefill on/off), one JSON row each."""
    _chip_devices()
    print(json.dumps(_bench_engine()), flush=True)
    for row in _bench_engine_spec():
        print(json.dumps(row), flush=True)
    for row in _bench_engine_mixed():
        print(json.dumps(row), flush=True)


# --------------------------------------------------------------------------
# ops microbench suite (--ops): per-kernel fused-vs-unfused + int8 matmul
# --------------------------------------------------------------------------

def _timed_chain(fn, state, iters: int, warmup: int = 3):
    """Seconds per call for a shape-preserving jitted fn, chained
    state -> state so XLA cannot hoist the work; each timed region
    ends in one host fetch, which waits for every call before it."""
    import jax

    for _ in range(warmup):
        state = fn(state)
    float(jax.tree.leaves(state)[0].ravel()[0])  # drain warmup work
    t0 = time.perf_counter()
    for _ in range(iters):
        state = fn(state)
    leaves = jax.tree.leaves(jax.tree.map(lambda a: a.ravel()[0], state))
    float(leaves[0])
    return (time.perf_counter() - t0) / iters


def _bench_ops() -> list:
    """Per-kernel microbenches: fused vs unfused step time for the
    model-path glue, and the decode matmul's weight GB/s at the
    working dtype vs
    weight-only int8. Small and self-contained so a kernel regression
    shows up in every BENCH round."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import ops

    b, s, d, f, h, kh, hd = 8, 2048, 2048, 8192, 32, 8, 64
    fm, iters, dt = 8192, 30, jnp.bfloat16
    config = "llama1b-shapes"
    key = jax.random.PRNGKey(0)
    rows = []

    def row(op, fused_fn, plain_fn, state, shape):
        t_plain = _timed_chain(jax.jit(plain_fn), state, iters)
        t_fused = _timed_chain(jax.jit(fused_fn), state, iters)
        rows.append({
            "metric": "ops_microbench", "op": op,
            "fused_us": round(t_fused * 1e6, 1),
            "unfused_us": round(t_plain * 1e6, 1),
            "speedup": round(t_plain / t_fused, 3) if t_fused else None,
            "shape": shape, "config": config})

    x = jax.random.normal(key, (b, s, d), dt)
    scale = jax.random.normal(jax.random.fold_in(key, 1), (d,),
                              jnp.float32) * 0.1
    row("rms_norm",
        lambda x: ops.fused_rms_norm(x, scale),
        lambda x: ops.rms_norm(x, scale),
        x, [b, s, d])

    q = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, hd),
                          dt)
    k = jax.random.normal(jax.random.fold_in(key, 3), (b, s, kh, hd),
                          dt)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    row("rope_qk",
        lambda qk: ops.fused_qk_rope(qk[0], qk[1], pos),
        lambda qk: (ops.apply_rope(qk[0], pos),
                    ops.apply_rope(qk[1], pos)),
        (q, k), [b, s, h, hd])

    gate = jax.random.normal(jax.random.fold_in(key, 4), (b, s, f),
                             dt)
    up = jax.random.normal(jax.random.fold_in(key, 5), (b, s, f), dt)
    row("swiglu",
        lambda g: ops.fused_swiglu(g, up),
        lambda g: (jax.nn.silu(g) * up).astype(g.dtype),
        gate, [b, s, f])

    # Decode-shaped matmul: tiny activation against a big square weight
    # — pure weight streaming, the thing int8 halves. GB/s counts the
    # WEIGHT bytes actually read per step. The weights ride the chained
    # STATE (jit arguments), never a closure: a closed-over int8 weight
    # gets constant-folded to full width at trace time and the "int8"
    # timing silently streams full-precision bytes (verified in HLO).
    w = jax.random.normal(jax.random.fold_in(key, 6), (fm, fm), dt)
    wq = jnp.clip(jnp.round(w.astype(jnp.float32) * 127), -127,
                  127).astype(jnp.int8)
    wscale = jnp.full((fm,), 1.0 / 127, jnp.float32)
    xa = jax.random.normal(jax.random.fold_in(key, 7), (8, fm), dt)
    t_base = _timed_chain(
        jax.jit(lambda s: ((s[0] @ s[1]).astype(dt), s[1])),
        (xa, w), iters)
    t_int8 = _timed_chain(
        jax.jit(lambda s: (((s[0] @ s[1].astype(s[0].dtype))
                            * s[2]).astype(dt), s[1], s[2])),
        (xa, wq, wscale), iters)
    rows.append({
        "metric": "decode_matmul_gbps",
        # "baseline" = the model's working dtype (bf16 on TPU, f32 on
        # CPU) — named by the dtype field, not mislabelled f32.
        "baseline_gbps": round(fm * fm * w.dtype.itemsize / t_base / 1e9,
                               2),
        "int8_gbps": round(fm * fm * 1 / t_int8 / 1e9, 2),
        "baseline_dtype": jnp.dtype(dt).name,
        "speedup": round(t_base / t_int8, 3) if t_int8 else None,
        "weight_shape": [fm, fm], "batch": 8, "config": config})
    return rows


def ops_main() -> int:
    """Standalone ``--ops``: per-kernel rows + one merged tail line."""
    _chip_devices()
    rows = _bench_ops()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_ops_rows(rows)))
    return 0


def _merge_ops_rows(rows: list) -> dict:
    merged = {"metric": "ops"}
    for r in rows:
        if r.get("metric") == "ops_microbench" and "error" not in r:
            merged[f"ops_fused_{r['op']}_speedup"] = r.get("speedup")
        elif r.get("metric") == "decode_matmul_gbps" and "error" not in r:
            merged["decode_matmul_baseline_gbps"] = r.get("baseline_gbps")
            merged["decode_matmul_baseline_dtype"] = \
                r.get("baseline_dtype")
            merged["decode_matmul_int8_gbps"] = r.get("int8_gbps")
            merged["decode_matmul_int8_speedup"] = r.get("speedup")
        elif "error" in r:
            merged.setdefault("error", r["error"])
    return merged


def child_main() -> None:
    """The chip child: every row below is a device measurement. A phase
    that fails raises — the child exits non-zero and the run fails (an
    error row beside an exit code of 0 reads as a finished bench)."""
    from ray_tpu.models import llama

    devices = _chip_devices()
    kind = devices[0].device_kind

    # --- row 1: Llama-1B full-model MFU (round-over-round continuity) ---
    # fused_ops=True: Pallas-fused RMSNorm/rope/SwiGLU (ops/fused.py).
    # Equivalence vs the unfused path is tier-1-tested under the
    # interpreter (tests/test_fused_ops.py), compiled for the chip in
    # tests/test_chip_compile.py and run on it by chip_smoke.py.
    cfg = dataclasses.replace(llama.LLAMA3_1B, max_seq_len=2048,
                              fused_ops=True)
    batch, seq, warmup, iters = 8, 2048, 2, 10
    step_s = _bench_train(cfg, batch, seq, warmup, iters, devices)
    tokens_per_s_chip = batch * seq / step_s / len(devices)
    mfu1b = tokens_per_s_chip * cfg.flops_per_token(seq) / peak_flops_for(kind)
    row_1b = {
        "metric": "train_mfu_llama1b",
        "value": round(mfu1b, 4),
        "unit": "mfu",
        "vs_baseline": round(mfu1b / 0.40, 4),
        "tokens_per_s_per_chip": round(tokens_per_s_chip, 1),
        "step_time_s": round(step_s, 4),
        "device": kind,
        "n_chips": len(devices),
        "config": "llama3-1b",
        "fused_ops": bool(cfg.fused_ops),
        "batch": batch, "seq": seq,
    }
    print(json.dumps(row_1b), flush=True)

    # --- row 2: 8B-class projected MFU (north star) ---------------------
    print(json.dumps(_bench_8b_proxy(devices, kind)), flush=True)

    # --- row 3: engine decode throughput on the chip --------------------
    row_dec = _bench_decode()
    print(json.dumps(row_dec), flush=True)

    # --- row 3b: same decode workload, weight-only int8 engine ----------
    row_q = _bench_decode(quantize="int8")
    if row_dec.get("value") and row_q.get("value"):
        row_q["speedup_vs_f32"] = round(
            row_q["value"] / row_dec["value"], 3)
    print(json.dumps(row_q), flush=True)

    # --- row 4: engine suite (decode + TTFT + prefix-cache) -------------
    print(json.dumps(_bench_engine()), flush=True)

    # --- rows 5+6: speculative decoding on/off (repetitive workload) ----
    # --- rows 6b: mixed long-prompt sweep, chunked prefill on/off -------
    # --- rows 7+: per-kernel ops microbench (fused glue + int8 matmul) --
    for r in _bench_engine_spec() + _bench_engine_mixed() + _bench_ops():
        print(json.dumps(r), flush=True)


def serve_child_main() -> None:
    """Full-stack serve bench; runs on CPU (the TPU child owns the chip)."""
    from ray_tpu.serve.benchmark import run_benchmark

    rows = run_benchmark(seconds=6.0, concurrency=4)
    print(json.dumps({"metric": "serve_llm", **rows}), flush=True)


# --------------------------------------------------------------------------
# routed-serve sweep (--serve): routing policies under skewed-prefix load
# --------------------------------------------------------------------------

def serve_routed_child_main() -> int:
    """One full routing-policy pass: ONE cluster, a sequence of
    measurement phases (policy list from RTPU_SERVE_SWEEP_ORDER,
    default alternating random/scored x3 then one pow2 phase) —
    adjacent phases share the host-noise window, and alternating the
    two headline policies several times means a noise burst corrupts
    at most one phase per side; the parent takes per-policy medians.
    Each phase deploys a FRESH 2-replica tiny-cpu engine deployment
    (fresh KV: no residency carry-over between policies), drives
    closed-loop skewed-prefix traffic, tears the deployment down, and
    prints one JSON row.

    Workload: 8 prefix groups of 224 tokens (14 cache blocks) + 8
    fresh suffix tokens, mildly skewed popularity. The full group set
    (112 blocks) overcommits one replica's 80-block KV pool — blind
    routing churns eviction — while a 4-group affinity partition (56
    blocks) stays resident. A prefix HIT prefills only the suffix
    (16-bucket); a miss pays the full 232-bucket prefill. Decode is
    held to ONE 1-step dispatch (prefill itself yields the first
    token) so the policy-neutral decode floor doesn't drown the
    prefill asymmetry on 2 CPU cores. Streams every request to
    measure true TTFT."""
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.serve._private.controller import CONTROLLER_NAME
    from ray_tpu.serve.llm import build_llm_deployment

    order = [p.strip() for p in os.environ.get(
        "RTPU_SERVE_SWEEP_ORDER",
        "random,scored,random,scored,random,scored,pow2").split(",")
        if p.strip()]
    seconds, n_replicas, concurrency = 8.0, 2, 6
    prefix_len, suffix_len, new_tokens = 224, 8, 2

    # Tracing ON for the whole sweep (both policies pay the same cost):
    # the TTFT-breakdown keys (queue/route/prefill) are derived from the
    # head's span ring, so routing/SLO changes are judged on decomposed
    # TTFT instead of noisy end-to-end medians.
    rt = ray_tpu.init(num_cpus=max(8, os.cpu_count() or 8),
                      _system_config={"tracing_enabled": True})
    rng = np.random.default_rng(11)
    groups = [[int(t) for t in rng.integers(1, 200, prefix_len)]
              for _ in range(8)]
    pop = 1.0 / (np.arange(8) + 4.0)
    pop = pop / pop.sum()

    def make_payload(r):
        g = int(r.choice(len(groups), p=pop))
        suffix = [int(t) for t in r.integers(1, 200, suffix_len)]
        return {"prompt_ids": groups[g] + suffix,
                "max_new_tokens": new_tokens}

    for phase_i, policy in enumerate(order):
        GLOBAL_CONFIG.set("serve_router_policy", policy)
        name = f"routed-{phase_i}-{policy}"
        handle = serve.run(build_llm_deployment(
            name=name, num_replicas=n_replicas,
            engine_kwargs={"max_batch": 4, "max_len": 320,
                           "prompt_buckets": [16, 232],
                           "prefix_block": 16, "decode_chunk": 1}),
            name=name)
        # Warm every replica's programs off the measured path with a
        # NEUTRAL prompt (not a group prefix: warmup must not pre-seed
        # affinity for any policy).
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        replicas = ray_tpu.get(controller.get_replicas.remote(name),
                               timeout=60)
        warm_full = {"prompt_ids": [210] * (prefix_len + suffix_len),
                     "max_new_tokens": new_tokens}
        warm_small = {"prompt_ids": [210] * 12,
                      "max_new_tokens": new_tokens}
        ray_tpu.get([r.handle_request.remote("__call__", (w,), {})
                     for r in replicas for w in (warm_full, warm_small)],
                    timeout=900)
        # Let one snapshot sweep land so scored routing starts informed.
        time.sleep(1.5)

        phase_t0_wall = time.time()
        stop_at = time.perf_counter() + seconds
        ttfts: list = []
        tokens = [0] * concurrency
        reqs = [0] * concurrency
        errs = [0] * concurrency
        last_err: list = [None]
        lock = threading.Lock()

        def client(i: int) -> None:
            r = np.random.default_rng(1000 + i)
            while time.perf_counter() < stop_at:
                # One failed request must not kill the whole closed-loop
                # client: a phase quietly running 5 clients instead of 6
                # would bias exactly the policy comparison the
                # alternating-median design protects.
                try:
                    gen = handle.options("stream", stream=True).remote(
                        make_payload(r))
                    t0 = time.perf_counter()
                    n = 0
                    for _tok in gen:
                        if n == 0:
                            with lock:
                                ttfts.append(
                                    (time.perf_counter() - t0) * 1e3)
                        n += 1
                    tokens[i] += n
                    reqs[i] += 1
                except Exception as e:
                    errs[i] += 1
                    with lock:
                        last_err[0] = repr(e)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0

        stats = ray_tpu.get([r.handle_request.remote("stats", (), {})
                             for r in replicas], timeout=60)
        hits = sum(s["prefix_hits"] for s in stats)
        misses = sum(s["prefix_misses"] for s in stats)
        ttfts.sort()

        # TTFT decomposition from the head's span ring: median duration
        # of this phase's serve.route / engine.queued / engine.prefill
        # spans (spans started during the measurement window only).
        def _span_breakdown() -> dict:
            want = {"serve.route": "ttft_route_ms",
                    "engine.queued": "ttft_queue_ms",
                    "engine.prefill": "ttft_prefill_ms"}
            buckets: dict = {k: [] for k in want.values()}
            try:
                # Driver-side spans (serve.route) buffer locally until
                # the 64-span high-water mark: flush before reading the
                # head ring or the newest routes are always missing.
                from ray_tpu.util import tracing as _tr

                _tr.flush()
                spans = rt.head.retrying_call("trace_tail", 50000,
                                              timeout=10)
            except Exception as e:
                print(f"breakdown span fetch failed: {e!r}",
                      file=sys.stderr, flush=True)
                return {}
            for s in spans:
                key = want.get(s.get("name"))
                if key is None or s.get("end") is None:
                    continue
                if s["start"] < phase_t0_wall:
                    continue
                buckets[key].append((s["end"] - s["start"]) * 1e3)
            out = {}
            for key, vals in buckets.items():
                if vals:
                    vals.sort()
                    out[key] = round(vals[len(vals) // 2], 3)
            return out

        row = {
            "metric": "serve_routed",
            "config": "tiny-cpu-2rep",
            "policy": policy,
            "requests_per_s": round(sum(reqs) / elapsed, 2),
            "tokens_per_s": round(sum(tokens) / elapsed, 2),
            "p50_ttft_ms": round(ttfts[len(ttfts) // 2], 2)
                if ttfts else None,
            "p99_ttft_ms": round(
                ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 2)
                if ttfts else None,
            "prefix_hit_rate": round(hits / (hits + misses), 4)
                if hits + misses else 0.0,
            "client_errors": sum(errs),
            "client_last_error": last_err[0],
            "router": handle._router.stats(),
        }
        row.update(_span_breakdown())
        print(json.dumps(row), flush=True)
        # Tear the phase's deployment down so the next policy starts
        # from cold KV on an idle cluster.
        ray_tpu.get(controller.delete.remote(name), timeout=60)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                if not ray_tpu.get(controller.get_replicas.remote(name),
                                   timeout=10):
                    break
            except Exception:  # rtpu-lint: disable=swallowed-exception
                break  # deployment record gone entirely == torn down
            time.sleep(0.5)
    return 0


def _serve_routed_rows(rounds: int = 1) -> list:
    """Run ``rounds`` sweep children. Each child measures the two
    headline policies (random, scored) as ALTERNATING adjacent phases
    on one cluster plus a trailing pow2 phase, so every phase pair
    shares a host-noise window and a burst corrupts at most one phase
    per side. Odd rounds lead with scored so neither policy always
    gets the freshest cluster. Per policy, every metric reduces by
    MEDIAN across all phases of all rounds — robust to a corrupted
    minority of phases and symmetric across policies. Error rows never
    kill the bench."""
    collected: dict = {}
    errors: dict = {}
    policies = ("random", "pow2", "scored")
    for rnd in range(rounds):
        pair = (["random", "scored"] if rnd % 2 == 0
                else ["scored", "random"])
        order = pair * 3 + ["pow2", "pow2"]
        env = {"JAX_PLATFORMS": "cpu",
               "RTPU_SERVE_SWEEP_ORDER": ",".join(order)}
        try:
            proc = _run(["--serve-routed-child"],
                        SERVE_ROUTED_TIMEOUT_S, env_extra=env)
        except subprocess.TimeoutExpired as te:
            # Phases stream one JSON row each as they finish: salvage
            # what the child measured before the hang instead of
            # discarding minutes of completed phases with it.
            partial = te.stdout or ""
            if isinstance(partial, bytes):
                partial = partial.decode(errors="replace")
            rows = [ln for ln in _json_lines(partial)
                    if ln.get("metric") == "serve_routed"
                    and ln.get("policy")]
            for row in rows:
                collected.setdefault(row["policy"], []).append(row)
            for policy in policies:
                if not any(r["policy"] == policy for r in rows):
                    errors.setdefault(policy, {
                        "metric": "serve_routed", "policy": policy,
                        "error": f"timeout {SERVE_ROUTED_TIMEOUT_S}s"})
            continue
        lines = _json_lines(proc.stdout)
        rows = [ln for ln in lines
                if ln.get("metric") == "serve_routed"
                and ln.get("policy")]
        for row in rows:
            collected.setdefault(row["policy"], []).append(row)
        if proc.returncode != 0 or len(rows) < len(order):
            tail = (proc.stderr or proc.stdout).strip() \
                .splitlines()[-3:]
            for policy in policies:
                if not any(r["policy"] == policy for r in rows):
                    errors.setdefault(policy, {
                        "metric": "serve_routed", "policy": policy,
                        "error": "rc=%d: %s" % (proc.returncode,
                                                " | ".join(tail))})

    def _median(vals: list) -> float:
        vals = sorted(vals)
        n = len(vals)
        mid = vals[n // 2] if n % 2 else (vals[n // 2 - 1]
                                          + vals[n // 2]) / 2
        return round(mid, 4)

    out = []
    for p in policies:
        rows = collected.get(p)
        if not rows:
            if p in errors:
                out.append(errors[p])
            continue
        merged = dict(rows[len(rows) // 2])
        merged["phases"] = len(rows)
        for key in ("requests_per_s", "tokens_per_s", "p50_ttft_ms",
                    "p99_ttft_ms", "prefix_hit_rate", "ttft_queue_ms",
                    "ttft_route_ms", "ttft_prefill_ms"):
            vals = [r[key] for r in rows if r.get(key) is not None]
            if vals:
                merged[key] = _median(vals)
        # Router path counters accumulate over every phase: the scored
        # row must prove the affinity path actually ran.
        merged["router"] = {
            k: sum(r.get("router", {}).get(k, 0) for r in rows)
            for k in ("scored_routes", "pow2_routes",
                      "affinity_routes")}
        out.append(merged)
    return out


def _merge_serve_routed_rows(rows: list) -> dict:
    by = {r.get("policy"): r for r in rows}
    merged = {"metric": "serve_routed"}
    sc = by.get("scored", {})
    if "error" in sc or not sc:
        merged["error"] = sc.get("error", "scored row missing")
    else:
        merged["serve_routed_tokens_per_s"] = sc.get("tokens_per_s")
        merged["serve_routed_p99_ttft_ms"] = sc.get("p99_ttft_ms")
        merged["serve_prefix_affinity_hit_rate"] = sc.get("prefix_hit_rate")
        # Span-derived TTFT decomposition (scored phases): future
        # routing/SLO PRs are judged on the component that moved, not
        # on the noisy end-to-end median alone.
        merged["serve_ttft_queue_ms"] = sc.get("ttft_queue_ms")
        merged["serve_ttft_route_ms"] = sc.get("ttft_route_ms")
        merged["serve_ttft_prefill_ms"] = sc.get("ttft_prefill_ms")
    rnd = by.get("random", {})
    if rnd and "error" not in rnd:
        merged["serve_routed_tokens_per_s_random"] = rnd.get("tokens_per_s")
        merged["serve_routed_p99_ttft_ms_random"] = rnd.get("p99_ttft_ms")
        merged["serve_prefix_hit_rate_random"] = rnd.get("prefix_hit_rate")
        if sc.get("tokens_per_s") and rnd.get("tokens_per_s"):
            merged["serve_routed_speedup_vs_random"] = round(
                sc["tokens_per_s"] / rnd["tokens_per_s"], 3)
    p2 = by.get("pow2", {})
    if p2 and "error" not in p2:
        merged["serve_routed_tokens_per_s_pow2"] = p2.get("tokens_per_s")
        merged["serve_routed_p99_ttft_ms_pow2"] = p2.get("p99_ttft_ms")
    return merged


def serve_routed_main() -> int:
    """Standalone ``--serve``: all three policies + one merged tail line."""
    rows = _serve_routed_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_serve_routed_rows(rows)))
    return 0 if all("error" not in r for r in rows) else 1


# --------------------------------------------------------------------------
# locality suite (--locality): locality-aware scheduling vs forced-random
# --------------------------------------------------------------------------

def locality_child_main() -> None:
    """One locality-workload measurement on a 4-node in-process cluster:
    blocks are produced pinned round-robin across the nodes, then one
    consumer task per block reads its block. With locality scheduling on
    (RTPU_SCHEDULER_LOCALITY_ENABLED=1, the default) consumers land on
    their block's holder node and pull nothing; the ``--random`` child
    (flag off + SPREAD placement) is the forced-random-placement
    baseline whose consumers pull their input over the simulated DCN.
    Prints one JSON row."""
    mode = "random" if "--random" in sys.argv else "locality"
    import ray_tpu as rt
    from ray_tpu.core.runtime_context import require_runtime
    from ray_tpu.util import metrics as _m
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    rt.init(num_cpus=2)
    runtime = require_runtime()
    extra = [runtime.add_node(num_cpus=2) for _ in range(3)]
    node_ids = [runtime._nodes[0].node_id] + [n.node_id for n in extra]

    n_blocks = 24
    block_bytes = 4 << 20

    @rt.remote
    def produce(i: int, nbytes: int):
        import numpy as _np

        return _np.full(nbytes, i % 251, dtype=_np.uint8)

    @rt.remote
    def consume(arr) -> int:
        time.sleep(0.1)  # stand-in compute: keeps one task per lease
        return int(arr[0]) + len(arr)

    blocks = [
        produce.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=node_ids[i % len(node_ids)])
        ).remote(i, block_bytes)
        for i in range(n_blocks)]
    ready, _ = rt.wait(blocks, num_returns=n_blocks, timeout=180)
    assert len(ready) == n_blocks, "block production timed out"

    def pull_totals() -> int:
        pulled = 0
        for n in runtime.nodes():
            try:
                st = runtime._pool.get(n["address"]).call(
                    "pull_stats", timeout=5)
                pulled += int(st.get("bytes_pulled", 0))
            except Exception:
                pass
        return pulled

    opts = {"scheduling_strategy": "SPREAD"} if mode == "random" else {}
    h0 = _m.SCHEDULER_LOCALITY_HITS.get()
    m0 = _m.SCHEDULER_LOCALITY_MISSES.get()
    p0 = pull_totals()
    t0 = time.perf_counter()
    futs = [consume.options(**opts).remote(ref) for ref in blocks]
    out = rt.get(futs, timeout=300)
    wall_s = time.perf_counter() - t0
    assert len(out) == n_blocks
    pulled = pull_totals() - p0
    hits = _m.SCHEDULER_LOCALITY_HITS.get() - h0
    misses = _m.SCHEDULER_LOCALITY_MISSES.get() - m0
    row = {
        "metric": "locality_scheduling", "mode": mode,
        "locality_hit_rate": round(hits / max(1, hits + misses), 3),
        "object_bytes_pulled_per_task": round(pulled / n_blocks, 1),
        "bytes_pulled_total": pulled,
        "locality_hits": hits, "locality_misses": misses,
        "n_tasks": n_blocks, "block_bytes": block_bytes,
        "nodes": len(node_ids), "wall_s": round(wall_s, 2)}
    print(json.dumps(row), flush=True)
    rt.shutdown()


def _locality_suite_rows() -> list:
    """Run both locality children; returns their rows (error rows on
    failure — the suite must never take down the whole bench)."""
    rows = []
    for mode in ("locality", "random"):
        args = ["--locality-child"] + (["--random"] if mode == "random"
                                       else [])
        env = {"JAX_PLATFORMS": "cpu",
               "RTPU_SCHEDULER_LOCALITY_ENABLED":
                   "1" if mode == "locality" else "0"}
        try:
            proc = _run(args, LOCALITY_TIMEOUT_S, env_extra=env)
        except subprocess.TimeoutExpired:
            rows.append({"metric": "locality_scheduling", "mode": mode,
                         "error": f"timeout {LOCALITY_TIMEOUT_S}s"})
            continue
        lines = _json_lines(proc.stdout)
        if proc.returncode == 0 and lines:
            rows.append(lines[-1])
        else:
            tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
            rows.append({"metric": "locality_scheduling", "mode": mode,
                         "error": "rc=%d: %s" % (proc.returncode,
                                                 " | ".join(tail))})
    return rows


def locality_main() -> int:
    """Standalone ``--locality``: both modes + one merged tail line."""
    rows = _locality_suite_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_locality_rows(rows)))
    return 0 if all("error" not in r for r in rows) else 1


def _merge_locality_rows(rows: list) -> dict:
    by = {r.get("mode"): r for r in rows}
    loc, rnd = by.get("locality", {}), by.get("random", {})
    merged = {"metric": "locality_scheduling"}
    if "error" in loc:
        merged["error"] = loc["error"]
    else:
        merged["locality_hit_rate"] = loc.get("locality_hit_rate")
        merged["object_bytes_pulled_per_task"] = \
            loc.get("object_bytes_pulled_per_task")
    if "error" not in rnd:
        merged["object_bytes_pulled_per_task_random"] = \
            rnd.get("object_bytes_pulled_per_task")
    return merged


# --------------------------------------------------------------------------
# dataplane suite (--dataplane): multi-writer store + pull + actor args
# --------------------------------------------------------------------------

_DP_STORE = "/rtpu_bench_dp"
_DP_OBJ = 8 << 20
_DP_SECONDS = 3.0


def _dp_writer(idx: int, barrier, q) -> None:
    """One put+delete writer process over the shared bench store (spawned
    via multiprocessing; must be module-level for pickling)."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.shm_store import ShmStore

    store = ShmStore.open(_DP_STORE)
    payload = bytearray(_DP_OBJ)

    def oid(i):
        return ObjectID(bytes([idx]) + i.to_bytes(8, "little") + b"\0" * 19)

    for i in range(2):  # warm the affine block (first-touch faults)
        store.put_bytes(oid(1000000 + i), payload)
        store.delete(oid(1000000 + i))
    barrier.wait(timeout=60)
    n = 0
    t0 = time.perf_counter()
    stop = t0 + _DP_SECONDS
    while time.perf_counter() < stop:
        store.put_bytes(oid(n), payload)
        store.delete(oid(n))
        n += 1
    q.put((n, time.perf_counter() - t0))


def _dp_put_gbps(k: int) -> float:
    """Aggregate put bandwidth of k concurrent writer PROCESSES (each in
    its own interpreter and page tables — the real multi-client shape)."""
    import multiprocessing as mp

    from ray_tpu.core.shm_store import ShmStore

    store = ShmStore.create(_DP_STORE, 768 << 20, prefault=False)
    try:
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        barrier = ctx.Barrier(k)
        procs = [ctx.Process(target=_dp_writer, args=(i, barrier, q))
                 for i in range(k)]
        for p in procs:
            p.start()
        res = [q.get(timeout=120) for _ in range(k)]
        for p in procs:
            p.join(timeout=30)
        return sum(n * _DP_OBJ / dt for n, dt in res) / 1e9
    finally:
        store.close()


def dataplane_child_main() -> None:
    """Store put scaling, then a 2-node cluster for pull bandwidth and
    n x n actor calls with array args. One JSON row per metric."""
    rows = []

    single = _dp_put_gbps(1)
    multi = _dp_put_gbps(4)
    ratio = round(multi / single, 3) if single else None
    rows.append({"metric": "single_put_gbps", "value": round(single, 2),
                 "unit": "GB/s", "object_mib": _DP_OBJ >> 20, "writers": 1})
    rows.append({"metric": "multi_put_gbps", "value": round(multi, 2),
                 "unit": "GB/s", "object_mib": _DP_OBJ >> 20, "writers": 4})
    rows.append({"metric": "put_scaling_ratio", "value": ratio,
                 "unit": "multi/single"})
    for r in rows:
        print(json.dumps(r), flush=True)

    import numpy as np

    import ray_tpu as rt
    from ray_tpu.core.runtime_context import require_runtime
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    rt.init(num_cpus=2)
    try:
        runtime = require_runtime()
        extra = runtime.add_node(num_cpus=2)

        # --- pull bandwidth: object sealed on the extra node, pulled by
        # the driver's node manager over the scatter-gather chunk path.
        @rt.remote
        def produce(nbytes: int):
            import numpy as _np

            return _np.full(nbytes, 7, dtype=_np.uint8)

        pull_mib = 64
        ref = produce.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=extra.node_id)).remote(pull_mib << 20)
        rt.wait([ref], timeout=120)
        home_addr = runtime.nodes()[0]["address"]
        from ray_tpu.core.config import GLOBAL_CONFIG as _cfg

        t0 = time.perf_counter()
        ok = runtime._pool.get(home_addr).call(
            "pull_object", ref.id().binary(), 60_000, timeout=90)
        dt = time.perf_counter() - t0
        rows.append({
            "metric": "pull_gbps",
            "value": round((pull_mib << 20) / dt / 1e9, 2) if ok else 0.0,
            "unit": "GB/s", "object_mib": pull_mib,
            "chunk_bytes": int(_cfg.object_transfer_chunk_bytes)})
        print(json.dumps(rows[-1]), flush=True)

        # --- n x n actor calls with a numpy array argument (the
        # actor_calls_with_arg_async_n_n shape).
        import threading

        @rt.remote
        class Sink:
            def take(self, arr):
                return arr.nbytes

        n_actors = 4
        actors = [Sink.remote() for _ in range(n_actors)]
        rt.get([a.take.remote(np.zeros(8, np.uint8)) for a in actors],
               timeout=120)  # boot + compile path
        arg = np.zeros(32 << 10, np.uint8)
        counts = [0] * n_actors
        stop_at = time.perf_counter() + 3.0

        def caller(i):
            a = actors[i]
            while time.perf_counter() < stop_at:
                futs = [a.take.remote(arg) for _ in range(32)]
                rt.get(futs, timeout=60)
                counts[i] += len(futs)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(n_actors)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        rows.append({"metric": "actor_args_nn_per_s",
                     "value": round(sum(counts) / elapsed, 1),
                     "unit": "calls/s", "actors": n_actors,
                     "arg_bytes": int(arg.nbytes)})
        print(json.dumps(rows[-1]), flush=True)
    finally:
        rt.shutdown()


def _dataplane_rows() -> list:
    """Run the dataplane child; returns its rows (or one error row)."""
    try:
        proc = _run(["--dataplane-child"], DATAPLANE_TIMEOUT_S,
                    env_extra={"JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        return [{"metric": "dataplane",
                 "error": f"timeout {DATAPLANE_TIMEOUT_S}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "dataplane",
                "error": "rc=%d: %s" % (proc.returncode, " | ".join(tail))})
    return out


def dataplane_main() -> int:
    """Standalone ``--dataplane``: rows + one merged tail line."""
    rows = _dataplane_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_dataplane_rows(rows)))
    return 0 if all("error" not in r for r in rows) else 1


def _merge_dataplane_rows(rows: list) -> dict:
    by = {r.get("metric"): r for r in rows}
    merged = {"metric": "dataplane"}
    for k in ("single_put_gbps", "multi_put_gbps", "put_scaling_ratio",
              "pull_gbps", "actor_args_nn_per_s"):
        if k in by and "error" not in by[k]:
            merged[k] = by[k].get("value")
    errs = [r["error"] for r in rows if "error" in r]
    if errs:
        merged["error"] = errs[0]
    return merged


# --------------------------------------------------------------------------
# chaos suite (--chaos): fault-recovery times on a real subprocess cluster
# --------------------------------------------------------------------------

def chaos_child_main() -> None:
    """Kill the head mid-workload and the only holder of an object, and
    time the recovery paths (supervisor respawn + durable-table reload +
    node re-registration/holder republish; lineage re-execution). Prints
    one JSON row. No chaos PLAN here — the faults are real SIGKILLs from
    the bench driver, so the row measures the recovery machinery
    end-to-end exactly as a production fault would exercise it."""
    import os as _os
    import signal as _signal

    import numpy as _np

    import ray_tpu as rt
    from ray_tpu.core.runtime_context import require_runtime
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    rt.init(num_cpus=2)
    runtime = require_runtime()

    @rt.remote
    def ping(i):
        return i

    # Warm: pool + leases exist, a background workload is in flight.
    assert rt.get([ping.remote(i) for i in range(4)],
                  timeout=120) == list(range(4))

    @rt.remote
    class Probe:
        def ok(self):
            return "ok"

    # --- head_recovery_s: SIGKILL the head, then time a NEW
    # head-dependent submission (actor creation must traverse
    # register_actor -> pick -> lease -> create on the RESPAWNED head).
    background = [ping.remote(i) for i in range(8)]  # mid-workload
    _os.kill(runtime._head_proc.pid, _signal.SIGKILL)
    t0 = time.perf_counter()
    probe = Probe.remote()
    assert rt.get(probe.ok.remote(), timeout=180) == "ok"
    head_recovery_s = time.perf_counter() - t0
    assert rt.get(background, timeout=180) == list(range(8))
    rt.kill(probe)

    # --- object_reconstruction_s: the ONLY holder of a task output is
    # SIGKILLed; get() must complete via lineage re-execution.
    node_b = runtime.add_node(num_cpus=2)
    time.sleep(1.5)
    n = 1_000_000

    @rt.remote(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=node_b.node_id, soft=True))
    def produce():
        return _np.arange(n)

    ref = produce.remote()
    ready, _ = rt.wait([ref], num_returns=1, timeout=120,
                       fetch_local=False)
    assert ready, "produce timed out"
    runtime.kill_node(node_b)
    t0 = time.perf_counter()
    got = rt.get(ref, timeout=180)
    object_reconstruction_s = time.perf_counter() - t0
    assert got[0] == 0 and got[-1] == n - 1

    # --- head_upgrade_s: rolling head upgrade (drain -> sqlite
    # checkpoint -> port handover to a NEW incarnation) under continuous
    # task + actor-call load. Acceptance is ZERO failed client requests
    # (latency may spike while requests ride retries across the gap) —
    # asserted here, so a row with head_upgrade_s implies it held.
    from ray_tpu.devtools import chaos as _chaos_mod

    @rt.remote(max_restarts=1, max_task_retries=-1)
    class UpgradeEcho:
        def hit(self, i):
            return i

    echo = UpgradeEcho.remote()
    assert rt.get(echo.hit.remote(-1), timeout=60) == -1

    def _upgrade_request(i):
        if i % 2:
            assert rt.get(ping.remote(i), timeout=120) == i
        else:
            assert rt.get(echo.hit.remote(i), timeout=120) == i

    up = _chaos_mod.run_rolling_upgrade(runtime, _upgrade_request,
                                        clients=2)
    assert up["request_failures"] == [], \
        f"requests failed during rolling upgrade: {up['request_failures']}"
    assert up["new_incarnation"] != up["old_incarnation"]
    head_upgrade_s = up["upgrade_s"]
    upgrade_requests_ok = up["requests_ok"]
    rt.kill(echo)

    # --- leak check: after the workload drains, the cluster-wide lease
    # census must be empty (every fault path returned its lease). A
    # census with an unreachable node is NOT leak-free — it is
    # incomplete; keep polling until every alive node answered (the
    # health sweep removes the killed node from the census set).
    leaked = None
    census_errors = None
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        census = runtime.head.retrying_call("cluster_leases", timeout=15)
        entries = [v for v in census.values() if isinstance(v, dict)]
        census_errors = [v["error"] for v in entries if "error" in v]
        leaked = [l for v in entries for l in v.get("leases", ())]
        if not leaked and not census_errors:
            break
        time.sleep(0.5)
    row = {
        "metric": "chaos_recovery",
        "head_recovery_s": round(head_recovery_s, 2),
        "object_reconstruction_s": round(object_reconstruction_s, 2),
        "head_upgrade_s": round(head_upgrade_s, 2),
        "upgrade_requests_ok": upgrade_requests_ok,
        "leaked_leases": len(leaked) if leaked is not None else -1,
        "object_bytes": n * 8, "nodes": 2,
    }
    if census_errors:
        row["census_error"] = census_errors[0]
    _witness_log_hits: dict = {}

    def _log_witness_hits(marker: bytes, fresh: bool = False) -> int:
        """Count witness lines across this session's process logs (read
        BEFORE shutdown — the session log dir is restored after it).
        Both witness markers are counted in ONE pass over the logs and
        memoized — the chaos child always runs with both flags on, and
        re-reading every worker log per marker doubles teardown I/O for
        nothing. ``fresh=True`` re-scans: the res verdict runs AFTER an
        up-to-20s settle window, and a late imbalance line (a worker's
        engine-close report — workers are not in the dump_flight poll
        set) must not hide behind a pre-settle snapshot."""
        from ray_tpu.core.config import GLOBAL_CONFIG as _gcfg

        markers = (b"RTPU_DEBUG_RPC:", b"RTPU_DEBUG_RES:",
                   b"RTPU_CHAN:")
        if fresh or not _witness_log_hits:
            _witness_log_hits.clear()
            _witness_log_hits.update({m: 0 for m in markers})
            try:
                for fn in _os.listdir(_gcfg.log_dir):
                    p = _os.path.join(_gcfg.log_dir, fn)
                    if _os.path.isfile(p):
                        with open(p, "rb") as fh:
                            data = fh.read()
                        for m in markers:
                            _witness_log_hits[m] += data.count(m)
            except OSError:
                pass
        return _witness_log_hits.get(marker, 0)

    def _poll_flight_payloads() -> list:
        """dump_flight payloads from the head + every alive node (the
        one RPC every process serves — both witnesses ride it)."""
        peers = [runtime.head.call("dump_flight", timeout=10)]
        for nv in runtime.head.call("list_nodes", timeout=10):
            if nv.get("alive"):
                peers.append(runtime._pool.get(nv["address"]).call(
                    "dump_flight", timeout=10))
        return peers

    if _os.environ.get("RTPU_DEBUG_RPC") == "1":
        # RPC-contract witness status: the whole recovery run executed
        # with duplicate delivery injected on every idempotent request
        # and per-(sender,receiver) outbox sequence checks. "Clean"
        # means zero violations in the driver's registry AND zero
        # RTPU_DEBUG_RPC: lines across this session's head/node/worker
        # logs (read BEFORE shutdown — the session log dir is restored
        # after it).
        from ray_tpu.devtools import rpc_debug as _rpcdbg

        log_hits = _log_witness_hits(b"RTPU_DEBUG_RPC:")
        # Cluster-wide witness stats ride the flight-dump payloads (the
        # one RPC every process serves): aggregate the driver's own
        # registry with the head's and every alive node's, so the row
        # proves duplicate injection actually COVERED the server side.
        viol = len(_rpcdbg.violations())
        dups = sum(_rpcdbg.dup_audit_counts().values())
        try:
            for payload in _poll_flight_payloads():
                rd = (payload or {}).get("rpc_debug") or {}
                viol += int(rd.get("violations", 0))
                dups += int(rd.get("dup_audits", 0))
        except Exception as e:
            row["rpc_witness_poll_error"] = repr(e)[:120]
        # Registry aggregate and log scan are overlapping evidence (a
        # live server's violation appears in BOTH): report them as
        # separate fields rather than a double-counting sum. Clean
        # requires both zero — the log scan also covers processes that
        # died before they could be polled.
        row["rpc_witness_clean"] = bool(viol == 0 and log_hits == 0)
        row["rpc_witness_violations"] = viol
        row["rpc_witness_log_lines"] = log_hits
        row["rpc_dup_audits"] = dups
    if _os.environ.get("RTPU_DEBUG_RES") == "1":
        # Resource-lifetime witness verdict: after the workload drains,
        # the CLUSTER-WIDE balance registries (driver + head + every
        # alive node, over the same dump_flight channel) must show zero
        # outstanding leak-kind resources — BufferLease pins, node
        # lease-table entries, KV speculation reservations. Transient
        # in-flight acquisitions settle within the retry window; a real
        # leak (the PR 2/PR 8 shapes) never does.
        from ray_tpu.devtools import res_debug as _resdbg

        leaked = None
        res_acquires = 0
        peer_viol = 0
        poll_error = None
        res_deadline = time.monotonic() + 20
        while time.monotonic() < res_deadline:
            own = _resdbg.dump_payload()
            leaked = own["leaked"]
            res_acquires = sum(own["acquired"].values())
            peer_viol = 0
            poll_error = None
            try:
                for payload in _poll_flight_payloads():
                    rd = (payload or {}).get("res_debug") or {}
                    leaked += int(rd.get("leaked", 0))
                    res_acquires += sum(
                        (rd.get("acquired") or {}).values())
                    # Peer violation counts ride the same payload: a
                    # node/head check_balanced failure (e.g. a "thread"
                    # imbalance, which is not a LEAK_KIND and never
                    # shows in `leaked`) must not pass the verdict —
                    # and the head's stdout is a PIPE, so its
                    # RTPU_DEBUG_RES: lines never reach the log scan.
                    peer_viol += int(rd.get("violations", 0))
            except Exception as e:
                # A transient poll failure (a node mid-respawn) is
                # RETRIED until the deadline, like a nonzero leak; it
                # neither passes a verdict built from partial data nor
                # fails the run off one dropped frame. Only the LAST
                # lap's outcome stands — incomplete = not clean, the
                # same rule the lease census applies.
                poll_error = repr(e)[:120]
                leaked = None
            if leaked == 0:
                break
            time.sleep(0.5)
        if poll_error is not None:
            row["res_witness_poll_error"] = poll_error
        res_viol = len(_resdbg.violations()) + peer_viol
        # Fresh scan AFTER the settle window: a worker's late
        # RTPU_DEBUG_RES line is this verdict's only evidence channel.
        res_log_hits = _log_witness_hits(b"RTPU_DEBUG_RES:",
                                         fresh=True)
        row["leaked_resources"] = leaked if leaked is not None else -1
        # Coverage evidence, like rpc_dup_audits: a leaked_resources=0
        # verdict over zero observed acquires would be vacuous.
        row["res_acquires_audited"] = res_acquires
        row["res_witness_clean"] = bool(leaked == 0 and res_viol == 0
                                        and res_log_hits == 0)
        row["res_witness_violations"] = res_viol
        row["res_witness_log_lines"] = res_log_hits
    if _os.environ.get("RTPU_DEBUG_CHAN") == "1":
        # Channel-protocol witness verdict: every ring/peer frame the
        # recovery run moved was checked online (seq/credit/cursor
        # invariants, sampled payload checksums, Lamport clocks).
        # Cluster-wide aggregation rides dump_flight like the other two
        # witnesses; the RTPU_CHAN: log scan covers processes that died
        # before the poll. frames_witnessed is the coverage evidence —
        # a 0-violation verdict over 0 frames is vacuous.
        from ray_tpu.devtools import chan_debug as _chandbg

        chan_frames = _chandbg.frames_witnessed()
        chan_viol = len(_chandbg.violations())
        try:
            for payload in _poll_flight_payloads():
                cd = (payload or {}).get("chan_debug") or {}
                chan_frames += int(cd.get("frames", 0))
                chan_viol += int(cd.get("violations", 0))
        except Exception as e:
            row["chan_witness_poll_error"] = repr(e)[:120]
        chan_log_hits = _log_witness_hits(b"RTPU_CHAN:")
        row["chan_frames_witnessed"] = chan_frames
        row["chan_violations"] = chan_viol
        row["chan_witness_log_lines"] = chan_log_hits
        row["chan_witness_clean"] = bool(chan_viol == 0
                                         and chan_log_hits == 0)
    print(json.dumps(row), flush=True)
    rt.shutdown()


def _chaos_rows() -> list:
    try:
        # RTPU_DEBUG_RPC=1: the recovery suite doubles as the RPC
        # contract audit — duplicate delivery on idempotent methods,
        # outbox sequence checks, classification-hole refusal — and the
        # row records witness-clean status alongside the timings.
        # RTPU_DEBUG_RES=1 alongside: the same run also audits resource
        # lifetimes — every BufferLease pin, node lease grant, and KV
        # reservation must settle (cluster-wide leaked_resources == 0).
        # RTPU_DEBUG_CHAN=1 completes the triple: every channel frame
        # the run moves is protocol-checked online (chan_violations==0).
        proc = _run(["--chaos-child"], CHAOS_TIMEOUT_S,
                    env_extra={"JAX_PLATFORMS": "cpu",
                               "RTPU_DEBUG_RPC": "1",
                               "RTPU_DEBUG_RES": "1",
                               "RTPU_DEBUG_CHAN": "1"})
    except subprocess.TimeoutExpired:
        return [{"metric": "chaos_recovery",
                 "error": f"timeout {CHAOS_TIMEOUT_S}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "chaos_recovery",
                "error": "rc=%d: %s" % (proc.returncode,
                                        " | ".join(tail))})
    return out


def chaos_main() -> int:
    """Standalone ``--chaos``: recovery rows + one merged tail line.
    Exit 1 on any error, a non-zero lease leak, or an incomplete
    census — the verify gate's 'leaked_leases: 0' must not pass at the
    exit-code level on a leaking or unverifiable run."""
    rows = _chaos_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_chaos_rows(rows)))
    clean = all("error" not in r and "census_error" not in r
                and r.get("leaked_leases", 0) == 0
                and r.get("rpc_witness_clean", True)
                and r.get("leaked_resources", 0) == 0
                and r.get("res_witness_clean", True)
                and r.get("chan_violations", 0) == 0
                and r.get("chan_witness_clean", True)
                for r in rows)
    return 0 if clean else 1


def _merge_chaos_rows(rows: list) -> dict:
    by = {r.get("metric"): r for r in rows}
    merged = {"metric": "chaos_recovery"}
    row = by.get("chaos_recovery", {})
    if "error" in row:
        merged["error"] = row["error"]
    else:
        for k in ("head_recovery_s", "object_reconstruction_s",
                  "head_upgrade_s", "upgrade_requests_ok",
                  "leaked_leases", "census_error", "rpc_witness_clean",
                  "rpc_witness_violations", "rpc_witness_log_lines",
                  "rpc_dup_audits", "leaked_resources",
                  "res_witness_clean", "res_witness_violations",
                  "res_witness_log_lines", "res_acquires_audited",
                  "chan_witness_clean", "chan_violations",
                  "chan_witness_log_lines", "chan_frames_witnessed"):
            if row.get(k) is not None:
                merged[k] = row[k]
    return merged


# --------------------------------------------------------------------------
# scale suite (--scale): head hot paths at 100 simulated nodes
# --------------------------------------------------------------------------

def scale_child_main() -> int:
    """Boot ONE head + N simulated in-process node managers (stubbed
    stores, real control plane: registration, versioned heartbeat sync,
    directory mirrors, lease census) and measure the head's hot paths at
    production node counts: RPC dispatch (pick_node with locality
    hints), object-directory lookups, the node-death/drain directory
    scrub, and the cluster-wide lease census. Prints one JSON row."""
    import hashlib
    import random as _random

    from ray_tpu.cluster import protocol as _protocol
    from ray_tpu.core.cluster_runtime import SimulatedCluster
    from ray_tpu.core.config import GLOBAL_CONFIG as _cfg

    n = int(os.environ.get("RTPU_SCALE_NODES", "100"))
    n_objects = int(os.environ.get("RTPU_SCALE_OBJECTS", "20000"))
    if n >= 500:
        # 1000 nodes at one beat/s would make the run a heartbeat fan-in
        # bench; stretch the beat (and the death threshold with it) so
        # the storm below measures dispatch, not backpressure.
        _cfg.set("health_check_period_ms", 5000)
    t0 = time.perf_counter()
    sim = SimulatedCluster(n)
    sim.wait_registered(60)
    boot_s = time.perf_counter() - t0
    rng = _random.Random(0)
    node_ids = [nd.node_id for nd in sim.nodes]

    def pctl(vals: list, p: float) -> float:
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(len(vals) * p))]

    # Seed the object directory: n_objects objects, 1-3 holders each,
    # shipped as one object_batch frame per node (the production wire
    # shape). Gives directory lookups + the drain scrub real work.
    oids = [hashlib.sha224(b"scale-obj-%d" % i).digest()
            for i in range(n_objects)]
    per_node: dict = {nid: [] for nid in node_ids}
    for oid in oids:
        for nid in rng.sample(node_ids, rng.randint(1, 3)):
            per_node[nid].append(("add", oid, 1 << 20))
    for nid, entries in per_node.items():
        sim.client.call("object_batch", nid, entries, timeout=30)

    # Head RPC dispatch: pick_node, alternating bare and locality-hinted
    # picks (the dispatch shape owners send), p99 over 2000 calls.
    lat_pick = []
    for i in range(2000):
        hints = ([oids[rng.randrange(n_objects)] for _ in range(4)]
                 if i % 2 else None)
        t = time.perf_counter()
        picked = sim.client.call("pick_node", {"CPU": 1.0}, None, None,
                                 f"scale-k{i % 64}", hints, timeout=30)
        lat_pick.append((time.perf_counter() - t) * 1e6)
        assert picked is not None
    # Directory lookups: object_locations p99 over 2000 random objects.
    lat_loc = []
    for i in range(2000):
        t = time.perf_counter()
        sim.client.call("object_locations",
                        oids[rng.randrange(n_objects)],
                        node_ids[rng.randrange(n)], timeout=30)
        lat_loc.append((time.perf_counter() - t) * 1e6)
    # Task storm: sustained owner-side dispatch against a few hot
    # scheduling keys, A/B in the same window — per-task head pick +
    # lease (the pre-block path) vs owner-routed lease blocks (grant
    # once per block, then node-direct request_lease until exhaustion).
    from ray_tpu.cluster.protocol import ClientPool as _ClientPool

    storm_tasks = int(os.environ.get("RTPU_SCALE_STORM_TASKS", "2000"))
    storm_keys = int(os.environ.get("RTPU_SCALE_STORM_KEYS", "8"))
    pool = _ClientPool()
    res = {"CPU": 1.0}

    def storm(use_blocks: bool) -> dict:
        head_rpcs = 0
        direct = 0
        done = 0
        blocks: dict = {}
        t0 = time.perf_counter()
        for i in range(storm_tasks):
            key = f"storm-k{i % storm_keys}"
            granted = None
            addr = None
            used_head = False
            if use_blocks:
                blk = blocks.get(key)
                if blk is not None and blk[2] > 0:
                    bid, addr, remaining = blk
                    granted = pool.get(addr).call(
                        "request_lease", res, True, None,
                        uuid.uuid4().hex, "bench-owner", None, None,
                        bid, timeout=30)
                    if granted is None or isinstance(granted, dict):
                        granted = None
                        blocks.pop(key, None)
                    else:
                        blocks[key] = (bid, addr, remaining - 1)
                if granted is None:
                    # First touch / exhausted: one head grant renews a
                    # whole block of node-direct admissions.
                    used_head = True
                    head_rpcs += 1
                    bid = uuid.uuid4().hex
                    got = sim.client.call("lease_block_grant", bid,
                                          "bench-owner", res, None,
                                          None, timeout=30)
                    if got is None:
                        continue
                    _nid, addr, size, _ttl = got
                    granted = pool.get(addr).call(
                        "request_lease", res, True, None,
                        uuid.uuid4().hex, "bench-owner", None, None,
                        bid, timeout=30)
                    if granted is None or isinstance(granted, dict):
                        continue
                    blocks[key] = (bid, addr, size - 1)
            else:
                used_head = True
                head_rpcs += 1
                picked = sim.client.call("pick_node", res, None, None,
                                         key, None, timeout=30)
                if picked is None:
                    continue
                addr = picked[1]
                granted = pool.get(addr).call(
                    "request_lease", res, True, None, uuid.uuid4().hex,
                    "bench-owner", None, None, None, timeout=30)
                if granted is None or isinstance(granted, dict):
                    continue
            pool.get(addr).call("return_lease", granted[1], timeout=30)
            done += 1
            if not used_head:
                direct += 1
        dt = time.perf_counter() - t0
        for bid, _addr, _rem in blocks.values():
            sim.client.call("lease_block_revoke", bid, timeout=30)
        return {"tasks_per_s": round(done / dt, 1) if dt else None,
                "bypass_rate": round(direct / done, 4) if done else None,
                "head_rpcs_per_task": round(head_rpcs / done, 4)
                if done else None,
                "completed": done}

    head_path = storm(use_blocks=False)
    block_path = storm(use_blocks=True)
    pool.close_all()

    # Cluster-wide lease census (fan-out to all N nodes).
    t = time.perf_counter()
    census = sim.client.call("cluster_leases", timeout=60)
    census_ms = (time.perf_counter() - t) * 1e3
    census_errors = sum(1 for v in census.values()
                        if isinstance(v, dict) and "error" in v)
    # Node drain: the directory scrub that also runs per dead node.
    t = time.perf_counter()
    sim.client.call("drain_node", node_ids[-1], timeout=60)
    drain_ms = (time.perf_counter() - t) * 1e3
    # Heartbeat fan-in: the in-process head's per-handler stats cover
    # every beat the N nodes sent since boot.
    hb = _protocol.get_event_stats().get("heartbeat", {})
    hb_count = int(hb.get("count", 0))
    row = {
        "metric": "head_scale",
        "nodes": n,
        "objects": n_objects,
        "boot_s": round(boot_s, 2),
        "head_dispatch_us_p50": round(pctl(lat_pick, 0.50), 1),
        "head_dispatch_us_p99": round(pctl(lat_pick, 0.99), 1),
        "head_object_locations_us_p99": round(pctl(lat_loc, 0.99), 1),
        "head_census_ms": round(census_ms, 1),
        "head_census_errors": census_errors,
        "head_drain_scrub_ms": round(drain_ms, 1),
        "storm_tasks_per_s": block_path["tasks_per_s"],
        "storm_tasks_per_s_headpath": head_path["tasks_per_s"],
        "head_dispatch_bypass_rate": block_path["bypass_rate"],
        "head_rpcs_per_task": block_path["head_rpcs_per_task"],
        "head_rpcs_per_task_headpath": head_path["head_rpcs_per_task"],
        "storm_tasks_completed": block_path["completed"],
        "heartbeats_processed": hb_count,
        "head_heartbeat_handler_us_avg": round(
            hb.get("total_s", 0.0) / hb_count * 1e6, 1) if hb_count else None,
        "head_heartbeat_handler_ms_max": round(
            hb.get("max_s", 0.0) * 1e3, 2) if hb_count else None,
    }
    print(json.dumps(row), flush=True)
    sim.shutdown()
    return 0


def _scale_rows() -> list:
    # 1000-node runs (RTPU_SCALE_NODES=1000) boot 10x the node threads
    # and heartbeat fan-in: give the child a proportionally wider window.
    timeout_s = SCALE_TIMEOUT_S
    if int(os.environ.get("RTPU_SCALE_NODES", "100")) >= 500:
        timeout_s = SCALE_TIMEOUT_S * 4
    try:
        proc = _run(["--scale-child"], timeout_s,
                    env_extra={"JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        return [{"metric": "head_scale",
                 "error": f"timeout {timeout_s}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "head_scale",
                "error": "rc=%d: %s" % (proc.returncode,
                                        " | ".join(tail))})
    return out


def scale_main() -> int:
    rows = _scale_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0 if all("error" not in r for r in rows) else 1


# --------------------------------------------------------------------------
# dag suite (--dag): per-hop channel latency vs task-RPC round trip
# --------------------------------------------------------------------------

def dag_child_main() -> int:
    """Compiled-DAG channel hop latency vs the equivalent task-RPC
    round trip, same payload sizes, same node. Three measurements:

    - ``dag_hop_us_p50_*``: a raw one-way shm-ring hop (ping-pong over
      two rings / 2) — the steady-state per-edge cost the compiled DAG
      pays per message.
    - ``dag_exec_us_p50_*``: a full ``compiled.execute().get()`` round
      (driver→actor→driver: 2 channel hops + the actor loop).
    - ``task_rpc_us_p50_*``: ``actor.echo.remote(payload)`` + ``get``
      — the path a non-compiled call takes through lease/RPC/store.

    The ROADMAP acceptance is hop ≥10x under the task-RPC round trip."""
    import multiprocessing as _mp
    import uuid as _uuid

    import ray_tpu
    from ray_tpu.dag import InputNode
    from ray_tpu.dag.ring import RingChannel

    def _p50_us(samples: list) -> float:
        return round(sorted(samples)[len(samples) // 2] * 1e6, 1)

    row = {"metric": "dag_channel", "config": "same-node"}

    # Raw ring hop (no cluster needed): a CHILD PROCESS echoes ring A
    # onto ring B; p50 round-trip / 2 = one-way hop. Cross-process is
    # the honest measurement — a same-process thread pair serializes on
    # the GIL and reads ~10x slower than the real two-process hop.
    def _echo_proc(ca_, cb_, n_):
        ra_ = RingChannel(ca_, capacity=8)
        wb_ = RingChannel(cb_, capacity=8)
        for i in range(n_):
            wb_.write(ra_.read(i, timeout=30), i)
        ra_.close(unlink=True)
        wb_.close()

    def _ring_hop_p50(nbytes: int, n: int = 300) -> float:
        payload = b"x" * nbytes
        ca, cb = _uuid.uuid4().bytes, _uuid.uuid4().bytes
        proc = _mp.get_context("fork").Process(
            target=_echo_proc, args=(ca, cb, n), daemon=True)
        proc.start()
        wa = RingChannel(ca, capacity=8)
        rb = RingChannel(cb, capacity=8)
        samples = []
        for i in range(n):
            t0 = time.perf_counter()
            wa.write(payload, i)
            rb.read(i, timeout=30)
            samples.append((time.perf_counter() - t0) / 2)
        proc.join(timeout=30)
        wa.close()
        rb.close(unlink=True)
        return _p50_us(samples[n // 4:])

    for name, nbytes in (("4KB", 4096), ("256KB", 256 * 1024)):
        row[f"dag_hop_us_p50_{name}"] = _ring_hop_p50(nbytes)

    # RTPU_DEBUG_CHAN arm, 4KB hop: the witness must stay a debug tool,
    # not a tax — the row records its on-vs-off overhead (target <5%)
    # and gates on zero protocol violations over the witnessed frames.
    # The env flag is set before the fork so BOTH endpoints (parent
    # writer/reader and the echo child) run their hooks; the verdict
    # below covers the parent-side registry (the child's violations
    # print RTPU_CHAN: lines on the shared stdout).
    from ray_tpu.devtools import chan_debug as _chandbg

    os.environ["RTPU_DEBUG_CHAN"] = "1"
    _chandbg.reset()
    try:
        witness_us = _ring_hop_p50(4096)
    finally:
        os.environ.pop("RTPU_DEBUG_CHAN", None)
    row["dag_hop_us_p50_4KB_witness"] = witness_us
    base_us = row["dag_hop_us_p50_4KB"]
    row["dag_witness_overhead_pct"] = round(
        100.0 * (witness_us - base_us) / base_us, 1)
    row["chan_frames_witnessed"] = _chandbg.frames_witnessed()
    row["chan_violations"] = len(_chandbg.violations())

    rt = ray_tpu.init(num_cpus=8)
    try:
        @ray_tpu.remote
        class Echo:
            def echo(self, x):
                return x

        a = Echo.remote()
        ray_tpu.get(a.echo.remote(b"warm"), timeout=120)
        for name, nbytes in (("4KB", 4096), ("256KB", 256 * 1024)):
            payload = b"x" * nbytes
            samples = []
            for _ in range(40):
                t0 = time.perf_counter()
                ray_tpu.get(a.echo.remote(payload), timeout=60)
                samples.append(time.perf_counter() - t0)
            row[f"task_rpc_us_p50_{name}"] = _p50_us(samples[10:])
            with InputNode() as inp:
                dag = a.echo.bind(inp)
            compiled = dag.experimental_compile()
            try:
                for _ in range(8):
                    compiled.execute(payload).get(timeout=60)
                samples = []
                for _ in range(60):
                    t0 = time.perf_counter()
                    compiled.execute(payload).get(timeout=60)
                    samples.append(time.perf_counter() - t0)
            finally:
                compiled.teardown()
            row[f"dag_exec_us_p50_{name}"] = _p50_us(samples[15:])
            hop = row[f"dag_hop_us_p50_{name}"]
            rpc = row[f"task_rpc_us_p50_{name}"]
            row[f"dag_hop_speedup_vs_rpc_{name}"] = round(rpc / hop, 1)
            row[f"dag_exec_speedup_vs_rpc_{name}"] = round(
                rpc / row[f"dag_exec_us_p50_{name}"], 2)
    finally:
        ray_tpu.shutdown()
    print(json.dumps(row), flush=True)
    return 0


def _dag_rows() -> list:
    try:
        proc = _run(["--dag-child"], DAG_TIMEOUT_S,
                    env_extra={"JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        return [{"metric": "dag_channel",
                 "error": f"timeout {DAG_TIMEOUT_S}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "dag_channel",
                "error": "rc=%d: %s" % (proc.returncode,
                                        " | ".join(tail))})
    return out


def dag_bench_main() -> int:
    """Standalone ``--dag``: exit 1 on any error OR a channel-protocol
    violation from the witness arm — the hop numbers don't count if the
    frames that produced them broke the protocol."""
    rows = _dag_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0 if all("error" not in r and r.get("chan_violations", 0) == 0
                    for r in rows) else 1


# --------------------------------------------------------------------------
# data suite (--data): channel-vs-task shuffle GB/s + ingest overlap A/B
# --------------------------------------------------------------------------

def data_child_main() -> int:
    """Streaming Dataset executor A/Bs, same window, alternating arms:

    - shuffle: ``random_shuffle`` of the same dataset with the exchange
      on the channel mesh vs the per-task-RPC pipeline (both transports
      share the partition/merge kernels, so the work per row is
      identical — the delta is pure transport).
    - ingest: a synthetic train loop over ``iter_batches(device_put=)``
      with the double-buffered background loader vs inline per-batch
      ``device_put`` on the consumer thread (the pre-executor path).
    """
    import numpy as np

    import ray_tpu
    import ray_tpu.data as rdata
    from ray_tpu.core.config import GLOBAL_CONFIG as cfg

    row = {"metric": "data_executor", "config": "same-node"}
    ray_tpu.init(num_cpus=4)
    try:
        # ---------------- shuffle GB/s, alternating A/B -----------------
        import ray_tpu.data._exchange as _ex

        # Many small blocks: steady-state per-piece cost is what the
        # transports differ on (the partition/merge kernels are shared),
        # and a 48-block exchange moves 48x48 pieces per pass.
        n_rows, width = 96_000, 16  # ~13 MB of float64 per pass
        ds = rdata.range(n_rows, parallelism=48).map_batches(
            lambda b: {"id": b["id"],
                       "x": np.tile(b["id"][:, None].astype(np.float64),
                                    (1, width))})
        nbytes = n_rows * (width + 1) * 8
        counts = {"channel": 0}
        orig = _ex._channel_exchange

        def counting(*a, **k):
            counts["channel"] += 1
            return orig(*a, **k)

        _ex._channel_exchange = counting
        ds.materialize()  # warm read path + compile nothing later
        times = {"channel": [], "task": []}
        reps = 3
        for rep in range(reps):
            for arm in ("channel", "task"):  # alternate inside the window
                cfg.data_exchange_transport = arm
                t0 = time.perf_counter()
                out = ds.random_shuffle(seed=rep).materialize()
                assert out.count() == n_rows
                times[arm].append(time.perf_counter() - t0)
        cfg.data_exchange_transport = "channel"
        gbps = {arm: round(nbytes / min(ts) / 1e9, 3)
                for arm, ts in times.items()}
        row["data_shuffle_gbps_channel"] = gbps["channel"]
        row["data_shuffle_gbps_task"] = gbps["task"]
        row["data_shuffle_channel_speedup"] = round(
            gbps["channel"] / gbps["task"], 2)
        # Honesty check: 0 here means every "channel" arm silently fell
        # back to tasks and the A/B measured nothing.
        row["data_channel_exchanges"] = counts["channel"]

        # ---------------- ingest overlap A/B ----------------------------
        import jax
        import jax.numpy as jnp

        dev = jax.devices()[0]
        d = 256
        bs = 4096
        ing = rdata.range(65_536, parallelism=16).map_batches(
            lambda b: {"x": np.tile(b["id"][:, None].astype(np.float32),
                                    (1, d))})
        w = jnp.ones((d, d), jnp.float32)

        @jax.jit
        def step(x, w_):
            y = x @ w_
            y = jnp.tanh(y) @ w_
            return (y @ w_).sum()

        step(jnp.ones((bs, d), jnp.float32), w).block_until_ready()

        def run_buffered():
            n = 0
            for b in ing.iter_batches(batch_size=bs, device_put=dev):
                step(b["x"], w).block_until_ready()
                n += 1
            return n

        def run_inline():
            n = 0
            for hb in ing.iter_batches(batch_size=bs):
                b = {k: jax.device_put(v, dev) for k, v in hb.items()}
                step(b["x"], w).block_until_ready()
                n += 1
            return n

        run_buffered()  # warm both pipelines once
        t_buf, t_inl = [], []
        for _ in range(2):
            t0 = time.perf_counter()
            n_steps = run_buffered()
            t_buf.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            assert run_inline() == n_steps
            t_inl.append(time.perf_counter() - t0)
        # Roofline: the same step count on a pre-staged device batch —
        # what steps/s looks like with ZERO ingest cost. buffered/
        # roofline is the "ingest stopped bottlenecking" ratio (needs
        # host cores for the loader thread to overlap into; on a 1-core
        # container both A/B arms are core-bound and converge instead).
        xb = jax.device_put(np.ones((bs, d), np.float32), dev)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            step(xb, w).block_until_ready()
        t_roof = time.perf_counter() - t0
        row["data_ingest_steps_per_s_buffered"] = round(
            n_steps / min(t_buf), 2)
        row["data_ingest_steps_per_s_inline"] = round(
            n_steps / min(t_inl), 2)
        row["data_ingest_steps_per_s_roofline"] = round(
            n_steps / t_roof, 2)
        row["data_ingest_overlap_speedup"] = round(
            min(t_inl) / min(t_buf), 2)
        row["data_ingest_efficiency"] = round(
            t_roof / min(t_buf), 2)
        row["cpu_cores"] = len(os.sched_getaffinity(0))
    finally:
        ray_tpu.shutdown()
    print(json.dumps(row), flush=True)
    return 0


def _data_rows() -> list:
    try:
        proc = _run(["--data-child"], DATA_TIMEOUT_S,
                    env_extra={"JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        return [{"metric": "data_executor",
                 "error": f"timeout {DATA_TIMEOUT_S}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "data_executor",
                "error": "rc=%d: %s" % (proc.returncode,
                                        " | ".join(tail))})
    return out


def data_bench_main() -> int:
    rows = _data_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0 if all("error" not in r for r in rows) else 1


# --------------------------------------------------------------------------
# disagg serve sweep: colocated vs disaggregated p99 TTFT, mixed load
# --------------------------------------------------------------------------

def serve_disagg_child_main() -> int:
    """Mixed long-prompt + long-decode workload, equal replica budget:
    colocated (2 full replicas) vs disaggregated (1 prefill + 1
    decode). TTFT is measured with PROBE requests (max_new_tokens=1 —
    the request completes at its first token on both topologies), fired
    steadily while background threads keep long decodes and long
    prompts in flight. Disaggregation isolates the probe path from the
    decode load, which is what flattens p99."""
    import threading

    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.serve.llm import build_llm_deployment

    ek = dict(max_batch=4, max_len=288,
              prompt_buckets=[16, 32, 64, 128, 256], decode_chunk=4,
              prefill_chunk=32, seed=0)
    measure_s = 12.0
    ray_tpu.init(num_cpus=24)
    rows = []
    try:
        for mode in ("colocated", "disagg"):
            if mode == "colocated":
                dep = build_llm_deployment(
                    name=f"sw{mode}", num_replicas=2, engine_kwargs=ek)
            else:
                dep = build_llm_deployment(
                    name=f"sw{mode}", disaggregated=True,
                    num_prefill_replicas=1, num_decode_replicas=1,
                    engine_kwargs=ek)
            h = serve.run(dep)
            # Warm both paths (compiles prefill buckets + decode).
            h.remote({"prompt_ids": [7] * 16,
                      "max_new_tokens": 4}).result(timeout=600)
            h.remote({"prompt_ids": list(range(1, 225)),
                      "max_new_tokens": 2}).result(timeout=600)
            stop = threading.Event()
            errors = []

            def _bg(fn):
                def run():
                    i = 0
                    while not stop.is_set():
                        try:
                            fn(i)
                        except Exception as e:  # noqa: BLE001 — recorded
                            errors.append(repr(e))
                            if len(errors) > 20:
                                return
                        i += 1
                t = threading.Thread(target=run, daemon=True)
                t.start()
                return t

            def long_decode(i):
                # Decode-dominated stream: a cheap 16-token prefill
                # then 96 decode steps. In the colocated topology these
                # keep BOTH replicas' engines decoding (probe prefills
                # queue behind decode ticks); disaggregated, they live
                # on the decode replica and the probe path stays clear.
                h.remote({"prompt_ids": [(i * 7 + j) % 251 + 1
                                         for j in range(16)],
                          "max_new_tokens": 96}).result(timeout=300)

            def long_prompt(i):
                # Bursty long prompts (throttled to a fixed rate so
                # both topologies see the same long-prompt load — an
                # unthrottled stream just saturates whatever prefill
                # capacity exists and measures replica COUNT, not
                # topology).
                h.remote({"prompt_ids": [(i * 13 + j) % 251 + 1
                                         for j in range(224)],
                          "max_new_tokens": 2}).result(timeout=300)
                time.sleep(0.6)

            bgs = [_bg(long_decode), _bg(long_decode), _bg(long_decode),
                   _bg(long_prompt)]
            time.sleep(2.0)  # let the background load saturate
            probes = []
            t_end = time.monotonic() + measure_s
            while time.monotonic() < t_end:
                t0 = time.perf_counter()
                h.remote({"prompt_ids": [3] * 16,
                          "max_new_tokens": 1}).result(timeout=300)
                probes.append((time.perf_counter() - t0) * 1e3)
                time.sleep(0.05)
            stop.set()
            for t in bgs:
                t.join(timeout=60)
            probes.sort()
            rows.append({
                "metric": f"serve_disagg_{mode}",
                "config": "tiny-cpu",
                "probes": len(probes),
                "p50_ttft_ms": round(probes[len(probes) // 2], 2),
                "p99_ttft_ms": round(
                    probes[min(len(probes) - 1,
                               int(len(probes) * 0.99))], 2),
                "bg_errors": len(errors),
            })
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0


def _serve_disagg_rows() -> list:
    try:
        proc = _run(["--serve-disagg-child"], DISAGG_TIMEOUT_S,
                    env_extra={"JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        return [{"metric": "serve_disagg",
                 "error": f"timeout {DISAGG_TIMEOUT_S}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "serve_disagg",
                "error": "rc=%d: %s" % (proc.returncode,
                                        " | ".join(tail))})
    return out


def _merge_serve_disagg_rows(rows: list) -> dict:
    by = {r.get("metric"): r for r in rows}
    merged: dict = {"metric": "serve_disagg"}
    err = next((r["error"] for r in rows if "error" in r), None)
    colo = by.get("serve_disagg_colocated", {})
    dis = by.get("serve_disagg_disagg", {})
    if err:
        merged["error"] = err
        return merged
    if colo.get("p99_ttft_ms") and dis.get("p99_ttft_ms"):
        merged["serve_colo_p99_ttft_ms"] = colo["p99_ttft_ms"]
        merged["serve_disagg_p99_ttft_ms"] = dis["p99_ttft_ms"]
        merged["serve_colo_p50_ttft_ms"] = colo.get("p50_ttft_ms")
        merged["serve_disagg_p50_ttft_ms"] = dis.get("p50_ttft_ms")
        merged["serve_disagg_ttft_flatness"] = round(
            colo["p99_ttft_ms"] / dis["p99_ttft_ms"], 2)
    return merged


def serve_disagg_main() -> int:
    rows = _serve_disagg_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_serve_disagg_rows(rows)))
    return 0 if all("error" not in r for r in rows) else 1


# --------------------------------------------------------------------------
# fleet KV tier: spill/pull vs recompute, same-window A/B with churn
# --------------------------------------------------------------------------

def kv_fleet_child_main() -> int:
    """PR 9's prefix sweep extended to the fleet KV tier (PR 18): two
    single-slot engines share a page store, and round-robin group
    traffic makes every admission evict the previous group — so the
    fleet tier (spill on evict, pull on re-admission) is the ONLY
    prefix reuse available. Mid-sweep one engine is killed and
    replaced: its HBM cache dies, its spilled pages don't. The A/B
    alternates fleet off/on twice in the same window so drift can't
    masquerade as a win; post-kill TTFTs are reported separately
    (``p50_ttft_ms_churn`` — the metric the tier exists to flatten)."""
    from ray_tpu.models import llama
    from ray_tpu.serve.engine.kv_fleet import LocalKVPageStore
    from ray_tpu.serve.llm import LLMEngine

    BLOCK = 8
    GROUPS, TURNS = 6, 3
    # 80-token shared prefix (10 blocks) + 8-token per-turn suffix:
    # fleet-on re-admissions pull 10 pages + prefill an 8-bucket tail,
    # fleet-off recomputes the whole 88 tokens in the 96 bucket.
    prefixes = [[(g * 97 + j) % 251 + 1 for j in range(80)]
                for g in range(GROUPS)]

    def prompt_for(g, turn):
        return prefixes[g] + [(g * 31 + turn * 7 + j) % 251 + 1
                              for j in range(8)]

    # Wider than tiny_config on purpose: recompute FLOPs grow with
    # d_model^2 while page bytes grow linearly, and the tier only pays
    # off when a block costs more to recompute than to copy. The
    # default tiny model is in the opposite (recompute-is-free) regime
    # — which the measured crossover on the "on" rows makes visible.
    cfg = llama.tiny_config(d_model=384, n_layers=6, n_heads=8,
                            n_kv_heads=2, d_ff=1536, max_seq_len=96)
    ek = dict(max_batch=1, max_len=96,
              prompt_buckets=[8, 16, 32, 64, 96], decode_chunk=4,
              seed=0, prefix_block=BLOCK)

    def new_engine(mode, store):
        if mode == "on":
            # Gate 0 = always pull; the MEASURED crossover is reported
            # alongside so the merged line shows what "auto" would do.
            return LLMEngine(cfg, kv_fleet_min_prefix_blocks=0,
                             kv_fleet_store=store, **ek)
        return LLMEngine(cfg, **ek)

    def warm(e):
        # Compile every program the sweep uses, off the clock.
        e.generate([5] * 88, max_new_tokens=1)
        e.generate([6] * 8, max_new_tokens=1)

    def eng_reused(e):
        st = e.stats()
        return (st.get("prefix_tokens_reused", 0)
                + st.get("kv_fleet_tokens_reused", 0))

    # Round-robin turns across groups (group -> engine by g % 2): the
    # slot is always evicted between a group's consecutive turns.
    sched = [(g, t) for t in range(TURNS) for g in range(GROUPS)]
    kill_at = len(sched) // 2

    rows = []
    for mode in ("off", "on", "off", "on"):  # same-window alternating
        store = LocalKVPageStore(capacity_bytes=256 << 20)
        engines = [new_engine(mode, store), new_engine(mode, store)]
        try:
            for e in engines:
                warm(e)
            baseline = [eng_reused(e) for e in engines]
            reused_total = 0
            prompt_tokens = 0
            ttfts, churn_ttfts = [], []
            for i, (g, t) in enumerate(sched):
                if i == kill_at:
                    # "Replica kill": engine 0's HBM cache dies with
                    # it. Bank its measured reuse, then rebuild and
                    # re-warm (restart compiles are off the clock —
                    # churn TTFT measures the CACHE loss, not XLA).
                    reused_total += eng_reused(engines[0]) - baseline[0]
                    engines[0].close()
                    engines[0] = new_engine(mode, store)
                    warm(engines[0])
                    baseline[0] = eng_reused(engines[0])
                e = engines[g % 2]
                p = prompt_for(g, t)
                t0 = time.perf_counter()
                e.generate(p, max_new_tokens=1)
                dt_ms = (time.perf_counter() - t0) * 1e3
                prompt_tokens += len(p)
                ttfts.append(dt_ms)
                if i >= kill_at:
                    churn_ttfts.append(dt_ms)
            reused_total += sum(eng_reused(e) - baseline[j]
                                for j, e in enumerate(engines))
            ttfts.sort()
            churn_ttfts.sort()
            row = {
                "metric": "kv_fleet_sweep",
                "config": "small-cpu",
                "mode": mode,
                "requests": len(sched),
                "hit_rate": round(reused_total / max(1, prompt_tokens),
                                  3),
                "p50_ttft_ms": round(ttfts[len(ttfts) // 2], 2),
                "p50_ttft_ms_churn": round(
                    churn_ttfts[len(churn_ttfts) // 2], 2),
            }
            if mode == "on":
                st = engines[1].stats()
                # The measured crossover table: store-side costs from
                # the start-of-engine probe, recompute side from real
                # prefill EWMAs accumulated during this sweep.
                for k in ("kv_fleet_pull_ms_per_page",
                          "kv_fleet_lookup_ms",
                          "kv_fleet_prefill_ms_per_block",
                          "kv_pull_vs_recompute_crossover_blocks",
                          "kv_fleet_spilled_blocks",
                          "kv_fleet_pulled_blocks",
                          "kv_fleet_rejects"):
                    row[k] = st.get(k)
            rows.append(row)
        finally:
            for e in engines:
                try:
                    e.close()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0


def _kv_fleet_rows() -> list:
    try:
        proc = _run(["--kv-fleet-child"], KV_FLEET_TIMEOUT_S,
                    env_extra={"JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        return [{"metric": "kv_fleet",
                 "error": f"timeout {KV_FLEET_TIMEOUT_S}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "kv_fleet",
                "error": "rc=%d: %s" % (proc.returncode,
                                        " | ".join(tail))})
    return out


def _merge_kv_fleet_rows(rows: list) -> dict:
    """Median across the repeated off/on phases (2 each): one headline
    pair per metric, so a single noisy phase can't flip the A/B."""
    merged: dict = {"metric": "kv_fleet"}
    err = next((r["error"] for r in rows if "error" in r), None)
    if err:
        merged["error"] = err
        return merged

    def med(vals):
        vals = sorted(v for v in vals if v is not None)
        return vals[len(vals) // 2] if vals else None

    on = [r for r in rows if r.get("mode") == "on"]
    off = [r for r in rows if r.get("mode") == "off"]
    if not on or not off:
        merged["error"] = "missing off/on phase rows"
        return merged
    merged["kv_fleet_hit_rate"] = med([r.get("hit_rate") for r in on])
    merged["kv_fleet_hit_rate_off"] = med(
        [r.get("hit_rate") for r in off])
    merged["kv_fleet_p50_ttft_ms_churn"] = med(
        [r.get("p50_ttft_ms_churn") for r in on])
    merged["kv_fleet_p50_ttft_ms_churn_off"] = med(
        [r.get("p50_ttft_ms_churn") for r in off])
    merged["kv_fleet_p50_ttft_ms"] = med(
        [r.get("p50_ttft_ms") for r in on])
    merged["kv_fleet_p50_ttft_ms_off"] = med(
        [r.get("p50_ttft_ms") for r in off])
    co = [r.get("kv_pull_vs_recompute_crossover_blocks") for r in on
          if r.get("kv_pull_vs_recompute_crossover_blocks") is not None]
    if co:
        merged["kv_pull_vs_recompute_crossover_blocks"] = co[-1]
    return merged


def kv_fleet_bench_main() -> int:
    rows = _kv_fleet_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_kv_fleet_rows(rows)))
    return 0 if all("error" not in r for r in rows) else 1


# --------------------------------------------------------------------------
# serve-scale suite: million-session router sim + QoS flood + streaming A/B
# --------------------------------------------------------------------------

def _scale_session_deck(n: int = 1_000_000,
                        space: int = 1_000_000) -> list:
    """A heavy-tailed (Pareto) deck of session ids: a handful of hot
    multi-turn sessions dominate while the tail spans ~1M distinct
    users — the popularity shape the session-affinity LRU and the
    prefix index are built for."""
    import random

    rng = random.Random(1234)
    return [min(int((rng.paretovariate(1.1) - 1.0) * 4000.0), space - 1)
            for _ in range(n)]


def _router_scale_sim(n_replicas: int, deck: list, templates: list,
                      chains: list, measure_s: float = 3.0) -> dict:
    """Route the session deck against ``n_replicas`` simulated load
    snapshots with NO cluster: the router's choose() hot path is what
    scales (candidate subsets + incremental rank + delta'd snapshot
    fan-in), so driving it directly measures decisions/s at fleet
    sizes the box can't boot. A ~1% delta sweep lands every ~0.5s —
    the controller-journal cadence — so freshness never lapses into
    the pow-2 fallback and the rank keeps absorbing O(touched)
    updates mid-measure."""
    import random

    from ray_tpu.devtools.lock_debug import make_lock
    from ray_tpu.serve._private.router import Router

    rng = random.Random(n_replicas)
    # Equal candidate pressure at every scale: ~20 replicas hold each
    # prompt-template chain (the affinity-candidate cap saturates), so
    # per-decision work is identical and the flatness ratio measures
    # the fleet-size dependence alone.
    P = max(8, min(len(templates), n_replicas // 20))

    def _snap(i, now):
        return {"ts": now, "queue_depth": (i * 7) % 5, "waiting": 0,
                "slots": 4, "kv_free_blocks": (i * 3) % 9,
                "kv_total_blocks": 8, "prefix_block_size": 4,
                "prefix_hashes": chains[i % P]}

    now = time.time()
    replicas = [f"r{i}" for i in range(n_replicas)]
    r = Router.__new__(Router)
    r._controller = None
    r._deployment = "scale-sim"
    r._lock = make_lock("serve.router._lock")
    r._replicas = []
    r._version = -1
    r._load_gen = -1
    r._loads = {}
    r._inflight = {}
    r._model_affinity = {}
    r._scored_routes = 0
    r._pow2_routes = 0
    r._affinity_routes = 0
    r._poller_started = True  # sim mode: never spawn the long-poller
    r._poll_thread = None
    r._stopped = False
    t0 = time.perf_counter()
    r._apply(1, replicas, 1, [_snap(i, now) for i in range(n_replicas)])
    apply_ms = (time.perf_counter() - t0) * 1e3
    sweep = max(1, n_replicas // 100)
    gen = 1
    decisions = 0
    sessions = set()
    di = rng.randrange(len(deck))
    # Warm the route path (first choose touches lazy state), then
    # measure a fixed wall window.
    r.done(r.choose(prefix_tokens=templates[0], session_key=deck[di]))
    t_next_delta = time.monotonic() + 0.5
    t_end = time.monotonic() + measure_s
    t_start = time.monotonic()
    while True:
        now_m = time.monotonic()
        if now_m >= t_end:
            break
        if now_m >= t_next_delta:
            gen += 1
            ups = {}
            for _ in range(sweep):
                i = rng.randrange(n_replicas)
                ups[i] = _snap(i, time.time())
            assert r._apply_delta(1, ups, load_gen=gen)
            t_next_delta = now_m + 0.5
            continue
        s = deck[di]
        di = (di + 1) % len(deck)
        sessions.add(s)
        choice = r.choose(prefix_tokens=templates[s % P], session_key=s)
        r.done(choice)
        decisions += 1
    span = time.monotonic() - t_start
    st = r.stats()
    scored = max(1, st["scored_routes"])
    return {
        "metric": f"serve_scale_router_{n_replicas}",
        "replicas": n_replicas,
        "decisions": decisions,
        "decisions_per_s": round(decisions / span, 1),
        "apply_full_ms": round(apply_ms, 2),
        "avg_candidates_scored": round(
            st["candidates_scored"] / scored, 2),
        "scored_frac": round(st["scored_routes"]
                             / max(1, decisions + 1), 4),
        "session_affinity_routes": st["session_affinity_routes"],
        "distinct_sessions": len(sessions),
        "deck_sessions": len(deck),
        "delta_sweeps": gen - 1,
    }


def _qos_flood_sim(measure_s: float = 3.0) -> dict:
    """Hostile-tenant flood against the WFQ admission gate, no
    cluster: 4 well-behaved tenants and one flooder firing ~50x its
    token budget. The contract is per-tenant isolation — the flooder
    sheds on ITS OWN bucket + queue while the good tenants' p99
    acquire latency stays flat."""
    import threading

    from ray_tpu.serve._private.slo import (AdmissionController,
                                            DeploymentOverloadedError)

    ac = AdmissionController(budget_ms=0.0, queue_depth=64,
                             queue_timeout_s=0.25, window=256,
                             min_samples=1, probe_inflight=4)
    ac.configure_tenant("flood", weight=1.0, tokens_per_s=20.0,
                        burst_tokens=10.0)
    good = [f"good{i}" for i in range(4)]
    stop = threading.Event()
    lat = {t: [] for t in good + ["flood"]}
    shed_local = {"flood": 0}
    lock = threading.Lock()

    def tenant_loop(t, cost, rate_hz):
        period = 1.0 / rate_hz
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                ac.acquire("d", tenant=t, cost=cost)
            except DeploymentOverloadedError:
                with lock:
                    shed_local[t] = shed_local.get(t, 0) + 1
                continue
            wait_ms = (time.perf_counter() - t0) * 1e3
            time.sleep(0.002)  # simulated service time
            ac.record_ttft("d", wait_ms + 2.0, tenant=t)
            ac.release("d", tenant=t)
            with lock:
                lat[t].append(wait_ms + 2.0)
            time.sleep(max(0.0, period - 0.002))

    threads = [threading.Thread(target=tenant_loop, args=(t, 5.0, 40.0),
                                daemon=True) for t in good]
    threads += [threading.Thread(target=tenant_loop,
                                 args=("flood", 5.0, 50.0), daemon=True)
                for _ in range(4)]  # ~200 req/s vs a 4 req/s budget
    for t in threads:
        t.start()
    time.sleep(measure_s)
    stop.set()
    for t in threads:
        t.join(timeout=10)

    def _p99(vals):
        if not vals:
            return None
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1, int(len(vals) * 0.99))], 2)

    snap = ac.snapshot()["d"]["tenants"]
    good_p99 = max(_p99(lat[t]) or 0.0 for t in good)
    return {
        "metric": "serve_scale_qos",
        "good_p99_ttft_ms": good_p99,
        "good_admitted": sum(len(lat[t]) for t in good),
        "good_shed": sum(snap.get(t, {}).get("shed", 0) for t in good),
        "flood_p99_ttft_ms": _p99(lat["flood"]),
        "flood_admitted": len(lat["flood"]),
        "flood_shed": snap.get("flood", {}).get("shed", 0),
    }


def serve_scale_child_main() -> int:
    """Simulated-serve scale suite: (1) router decisions/s against
    100 -> 10k replica load snapshots under a ~1M-session heavy-tailed
    deck (flatness is the O(touched) acceptance bar), (2) WFQ flood
    isolation, (3) a REAL mini-cluster streaming-disagg A/B — p50
    TTFT of the token stream vs the non-streaming probe in the same
    window — then (4) the RTPU_DEBUG_RES leak census over all of it."""
    from ray_tpu.core.config import GLOBAL_CONFIG as cfg
    from ray_tpu.serve.engine.kv_manager import chain_hashes

    rows = []
    cfg.set("serve_router_policy", "scored")
    templates = [[(t * 7 + j) % 251 + 1 for j in range(12)]
                 for t in range(512)]
    chains = [chain_hashes(p, 4) for p in templates]
    deck = _scale_session_deck()
    for n in (100, 1000, 10000):
        rows.append(_router_scale_sim(n, deck, templates, chains))
    rows.append(_qos_flood_sim())
    rows.append(_stream_ab_row())
    try:
        from ray_tpu.devtools import res_debug

        rows.append({
            "metric": "serve_scale_res",
            "leaked_resources": sum(res_debug.outstanding().values()),
            "res_violations": len(res_debug.violations()),
        })
    except Exception as e:  # noqa: BLE001 — census never blocks rows
        rows.append({"metric": "serve_scale_res", "error": repr(e)[:200]})
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0 if all("error" not in r for r in rows) else 1


def _stream_ab_row() -> dict:
    """Same-window streaming-vs-probe A/B on a real disagg deployment
    (1 prefill + 1 decode) plus a colocated streaming reference: the
    stream's first token leaves at prefill time over the reverse
    channel, so its p50 TTFT must hold the non-streaming probe's line
    — streaming is free, not a second hop."""
    try:
        import ray_tpu
        import ray_tpu.serve as serve
        from ray_tpu.serve.llm import build_llm_deployment
    except Exception as e:  # noqa: BLE001 — import gap -> error row
        return {"metric": "serve_scale_stream", "error": repr(e)[:200]}

    ek = dict(max_batch=4, max_len=288,
              prompt_buckets=[16, 32, 64, 128, 256], decode_chunk=4,
              prefill_chunk=32, seed=0)
    measure_s = 10.0
    row = {"metric": "serve_scale_stream"}
    try:
        ray_tpu.init(num_cpus=24)
        try:
            colo = serve.run(build_llm_deployment(
                name="scstcolo", engine_kwargs=ek))
            dis = serve.run(build_llm_deployment(
                name="scstdis", disaggregated=True,
                num_prefill_replicas=1, num_decode_replicas=1,
                engine_kwargs=ek))
            warm = {"prompt_ids": [7] * 16, "max_new_tokens": 4}
            colo.remote(dict(warm)).result(timeout=600)
            dis.remote(dict(warm)).result(timeout=600)

            def _stream_once(h, i, new_tokens):
                req = {"prompt_ids": [(i * 11 + j) % 251 + 1
                                      for j in range(16)],
                       "max_new_tokens": new_tokens}
                t0 = time.perf_counter()
                first = last = None
                n = 0
                for _ in h.options("stream", stream=True).remote(req):
                    last = time.perf_counter()
                    if first is None:
                        first = last
                    n += 1
                ttft = (first - t0) * 1e3
                tpot = ((last - first) / max(1, n - 1)) * 1e3
                return ttft, tpot, n

            for name, h in (("colo", colo), ("disagg", dis)):
                ttfts, tpots, sprobes, probes = [], [], [], []
                t_end = time.monotonic() + measure_s
                i = 0
                while time.monotonic() < t_end:
                    # Interleave a full stream, a STREAMED probe and a
                    # non-streaming probe: the A/B shares the window and
                    # the replica state, and probe-vs-stream-probe is
                    # the same request shape (completes at token 1), so
                    # any gap is the streaming plumbing itself.
                    ttft, tpot, n = _stream_once(h, i, 24)
                    ttfts.append(ttft)
                    tpots.append(tpot)
                    sprobes.append(_stream_once(h, i, 1)[0])
                    t0 = time.perf_counter()
                    h.remote({"prompt_ids": [3] * 16,
                              "max_new_tokens": 1}).result(timeout=300)
                    probes.append((time.perf_counter() - t0) * 1e3)
                    i += 1
                for k, vals in (("stream_p50_ttft_ms", ttfts),
                                ("stream_p50_tpot_ms", tpots),
                                ("stream_probe_p50_ttft_ms", sprobes),
                                ("probe_p50_ttft_ms", probes)):
                    vals.sort()
                    row[f"{name}_{k}"] = round(vals[len(vals) // 2], 2)
                row[f"{name}_streams"] = len(ttfts)
        finally:
            try:
                serve.shutdown()
            finally:
                ray_tpu.shutdown()
    except Exception as e:  # noqa: BLE001 — cluster gap -> error row
        row["error"] = repr(e)[:200]
    return row


def _serve_scale_rows() -> list:
    try:
        proc = _run(["--serve-scale-child"], SERVE_SCALE_TIMEOUT_S,
                    env_extra={"JAX_PLATFORMS": "cpu",
                               "RTPU_DEBUG_RES": "1"})
    except subprocess.TimeoutExpired:
        return [{"metric": "serve_scale",
                 "error": f"timeout {SERVE_SCALE_TIMEOUT_S}s"}]
    lines = _json_lines(proc.stdout)
    if lines and proc.returncode == 0:
        return lines
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
    out = lines or []
    out.append({"metric": "serve_scale",
                "error": "rc=%d: %s" % (proc.returncode,
                                        " | ".join(tail))})
    return out


def _merge_serve_scale_rows(rows: list) -> dict:
    by = {r.get("metric"): r for r in rows}
    merged: dict = {"metric": "serve_scale"}
    err = next((r["error"] for r in rows if "error" in r), None)
    if err:
        merged["error"] = err
    lo = by.get("serve_scale_router_100", {})
    hi = by.get("serve_scale_router_10000", {})
    if lo.get("decisions_per_s") and hi.get("decisions_per_s"):
        merged["router_decisions_per_s"] = hi["decisions_per_s"]
        merged["router_decisions_per_s_100"] = lo["decisions_per_s"]
        # ~1.0 == flat: choose() cost held while the snapshot set grew
        # 100x (the O(touched) acceptance bar is 0.8+).
        merged["router_scale_flatness"] = round(
            hi["decisions_per_s"] / lo["decisions_per_s"], 3)
        merged["router_avg_candidates_scored_10k"] = \
            hi.get("avg_candidates_scored")
    qos = by.get("serve_scale_qos", {})
    for src, dst in (("good_p99_ttft_ms", "serve_qos_good_p99_ttft_ms"),
                     ("flood_p99_ttft_ms",
                      "serve_qos_flood_p99_ttft_ms"),
                     ("flood_shed", "serve_qos_flood_shed")):
        if qos.get(src) is not None:
            merged[dst] = qos[src]
    st = by.get("serve_scale_stream", {})
    if "error" not in st:
        for src, dst in (
                ("disagg_stream_p50_ttft_ms",
                 "serve_stream_disagg_p50_ttft_ms"),
                ("disagg_stream_p50_tpot_ms",
                 "serve_stream_disagg_p50_tpot_ms"),
                ("disagg_stream_probe_p50_ttft_ms",
                 "serve_stream_disagg_probe_p50_ttft_ms"),
                ("disagg_probe_p50_ttft_ms",
                 "serve_disagg_probe_p50_ttft_ms"),
                ("colo_stream_p50_ttft_ms",
                 "serve_stream_colo_p50_ttft_ms")):
            if st.get(src) is not None:
                merged[dst] = st[src]
    res = by.get("serve_scale_res", {})
    if res.get("leaked_resources") is not None:
        merged["serve_scale_leaked_resources"] = res["leaked_resources"]
    return merged


def serve_scale_main() -> int:
    rows = _serve_scale_rows()
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps(_merge_serve_scale_rows(rows)))
    return 0 if all("error" not in r for r in rows) else 1


# --------------------------------------------------------------------------
# parent supervisor
# --------------------------------------------------------------------------

def accel_holders() -> list:
    """Which processes hold TPU device files open (/dev/accel*, /dev/vfio*).
    A chip belongs to one process at a time: a holder left over from a
    previous run is the usual cause of a backend that fails to set up."""
    holders = []
    try:
        for pid in os.listdir("/proc"):
            if not pid.isdigit():
                continue
            fd_dir = f"/proc/{pid}/fd"
            try:
                for fd in os.listdir(fd_dir):
                    try:
                        tgt = os.readlink(os.path.join(fd_dir, fd))
                    except OSError:
                        continue
                    if "/dev/accel" in tgt or "/dev/vfio" in tgt:
                        try:
                            with open(f"/proc/{pid}/cmdline", "rb") as f:
                                cmd = f.read().replace(b"\0", b" ") \
                                    .decode(errors="replace").strip()[:200]
                        except OSError:
                            cmd = "?"
                        holders.append(
                            {"pid": int(pid), "device": tgt, "cmd": cmd})
                        break
            except OSError:
                continue
    except OSError:
        pass
    return holders


def _run(args: list, timeout_s: int, env_extra: dict = None):
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + args,
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)))


def _json_lines(text: str) -> list:
    out = []
    for ln in text.splitlines():
        if ln.startswith("{"):
            try:
                out.append(json.loads(ln))
            except ValueError:
                pass
    return out


def main() -> int:
    # ONE child owns the chip, once. A failure is a finding, not noise
    # to retry through: the record names it and the run fails.
    error = None
    rows = []
    try:
        proc = _run(["--child"], CHILD_TIMEOUT_S)
        if proc.returncode == 0:
            rows = _json_lines(proc.stdout)
        else:
            tail = (proc.stderr or proc.stdout).strip().splitlines()[-6:]
            error = f"chip child rc={proc.returncode}: " + " | ".join(tail)
    except subprocess.TimeoutExpired:
        error = f"chip child timeout {CHILD_TIMEOUT_S}s"

    if not rows:
        # Structured record, not a traceback. value 0.0 plus an explicit
        # error field — never a silently-plausible number.
        print(json.dumps({
            "metric": "train_mfu_llama8b_proxy",
            "value": 0.0,
            "unit": "mfu",
            "vs_baseline": 0.0,
            "error": error or "chip child printed no rows",
            "accel_holders": accel_holders(),
        }))
        return 1

    for r in rows:  # echo the child's rows for human readers / logs
        print(json.dumps(r), flush=True)

    # Phase 3: serve stack bench on CPU (chip-independent; never blocks
    # the hardware rows).
    serve_row = None
    try:
        sproc = _run(["--serve-child"], SERVE_TIMEOUT_S,
                     env_extra={"JAX_PLATFORMS": "cpu"})
        if sproc.returncode == 0:
            lines = _json_lines(sproc.stdout)
            serve_row = lines[-1] if lines else None
        else:
            serve_row = {"metric": "serve_llm", "error": "rc=%d: %s" % (
                sproc.returncode,
                " | ".join((sproc.stderr or sproc.stdout)
                           .strip().splitlines()[-3:]))}
    except subprocess.TimeoutExpired:
        serve_row = {"metric": "serve_llm",
                     "error": f"timeout {SERVE_TIMEOUT_S}s"}
    if serve_row is not None:
        print(json.dumps(serve_row), flush=True)

    # Phase 3b: routed-serve sweep on CPU (multi-replica skewed-prefix
    # traffic, random vs pow-2 vs scored routing). Tracked from this PR.
    routed_rows: list = []
    try:
        routed_rows = _serve_routed_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        routed_rows = [{"metric": "serve_routed", "error": repr(e)[:200]}]
    for r in routed_rows:
        print(json.dumps(r), flush=True)

    # Phase 4: locality-scheduling suite on CPU (multi-node in-process
    # cluster; chip-independent). Tracked round-over-round from this PR.
    loc_rows: list = []
    try:
        loc_rows = _locality_suite_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        loc_rows = [{"metric": "locality_scheduling",
                     "error": repr(e)[:200]}]
    for r in loc_rows:
        print(json.dumps(r), flush=True)

    # Phase 5: dataplane suite on CPU (multi-writer store + pull + actor
    # args). Tracked round-over-round from this PR.
    dp_rows: list = []
    try:
        dp_rows = _dataplane_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        dp_rows = [{"metric": "dataplane", "error": repr(e)[:200]}]
    for r in dp_rows:
        print(json.dumps(r), flush=True)

    # Phase 6: chaos-recovery suite on CPU (kill head / kill holder,
    # rolling upgrade, recovery times + lease-leak census). Tracked
    # from this PR.
    chaos_rows: list = []
    try:
        chaos_rows = _chaos_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        chaos_rows = [{"metric": "chaos_recovery", "error": repr(e)[:200]}]
    for r in chaos_rows:
        print(json.dumps(r), flush=True)

    # Phase 7: head scale suite on CPU (100 simulated nodes, head
    # dispatch/directory/census hot paths). Tracked from this PR.
    scale_rows: list = []
    try:
        scale_rows = _scale_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        scale_rows = [{"metric": "head_scale", "error": repr(e)[:200]}]
    for r in scale_rows:
        print(json.dumps(r), flush=True)

    # Phase 8: compiled-DAG channel suite on CPU (per-hop ring latency
    # vs task-RPC round trip). Tracked from this PR.
    dag_rows: list = []
    try:
        dag_rows = _dag_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        dag_rows = [{"metric": "dag_channel", "error": repr(e)[:200]}]
    for r in dag_rows:
        print(json.dumps(r), flush=True)

    # Phase 9: disaggregated-serving TTFT sweep on CPU (colocated vs
    # disagg p99 TTFT under mixed long-prompt + long-decode load).
    disagg_rows: list = []
    try:
        disagg_rows = _serve_disagg_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        disagg_rows = [{"metric": "serve_disagg", "error": repr(e)[:200]}]
    for r in disagg_rows:
        print(json.dumps(r), flush=True)

    # Phase 10: streaming-data suite on CPU (channel-vs-task shuffle
    # GB/s + double-buffered ingest A/B). Tracked from this PR.
    data_rows: list = []
    try:
        data_rows = _data_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        data_rows = [{"metric": "data_executor", "error": repr(e)[:200]}]
    for r in data_rows:
        print(json.dumps(r), flush=True)

    # Phase 11: fleet KV tier A/B on CPU (spill/pull vs recompute,
    # replica kill mid-sweep). Tracked from this PR.
    kvf_rows: list = []
    try:
        kvf_rows = _kv_fleet_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        kvf_rows = [{"metric": "kv_fleet", "error": repr(e)[:200]}]
    for r in kvf_rows:
        print(json.dumps(r), flush=True)

    # Phase 12: serve-scale suite on CPU (1M-session router sim at
    # 100 -> 10k snapshots, WFQ flood isolation, streaming disagg
    # TTFT/TPOT A/B). Tracked from this PR.
    svs_rows: list = []
    try:
        svs_rows = _serve_scale_rows()
    except Exception as e:  # noqa: BLE001 — never blocks the bench
        svs_rows = [{"metric": "serve_scale", "error": repr(e)[:200]}]
    for r in svs_rows:
        print(json.dumps(r), flush=True)

    # Final merged line (the driver parses the tail line): headline is the
    # 8B north star when it measured, else the 1B row.
    by_metric = {r.get("metric"): r for r in rows}
    head = by_metric.get("train_mfu_llama8b_proxy")
    if not head or not head.get("value"):
        head = by_metric.get("train_mfu_llama1b", rows[-1])
    merged = dict(head)
    r1b = by_metric.get("train_mfu_llama1b", {})
    merged.setdefault("device", r1b.get("device"))
    merged.setdefault("n_chips", r1b.get("n_chips"))
    merged["train_mfu_llama1b"] = r1b.get("value")
    dec = by_metric.get("llm_decode_tokens_per_s", {})
    merged["llm_decode_tokens_per_s"] = dec.get("value")
    decq = by_metric.get("llm_decode_tokens_per_s_int8", {})
    if "error" not in decq and decq.get("value"):
        merged["llm_decode_tokens_per_s_int8"] = decq.get("value")
        merged["llm_decode_int8_speedup"] = decq.get("speedup_vs_f32")
    ops_merged = _merge_ops_rows(
        [r for r in rows if r.get("metric") in ("ops_microbench",
                                                "decode_matmul_gbps")])
    for k, v in ops_merged.items():
        if k not in ("metric", "error") and v is not None:
            merged[k] = v
    eng = by_metric.get("llm_engine", {})
    if "error" not in eng:
        for k in ("ttft_ms", "prefix_hit_rate"):
            merged[k] = eng.get(k)
        if eng.get("compiled_programs"):
            # Total steady-state programs the witnessed engine built —
            # tracked round-over-round so compile creep is visible in
            # the BENCH_r* tail line.
            merged["llm_engine_programs"] = \
                sum(eng["compiled_programs"].values())
        # The engine suite's decode row supersedes the legacy row when
        # the legacy one errored out.
        if not merged.get("llm_decode_tokens_per_s"):
            merged["llm_decode_tokens_per_s"] = \
                eng.get("llm_decode_tokens_per_s")
    spec = by_metric.get("llm_engine_spec", {})
    if "error" not in spec:
        merged["llm_spec_accept_rate"] = spec.get("llm_spec_accept_rate")
        merged["llm_spec_speedup"] = spec.get("spec_speedup")
        merged["llm_decode_tokens_per_s_spec"] = \
            spec.get("llm_decode_tokens_per_s")
    elif spec:
        merged["spec_error"] = spec["error"]
    mx_on = by_metric.get("llm_engine_mixed_chunked", {})
    mx_off = by_metric.get("llm_engine_mixed_unchunked", {})
    if "error" not in mx_on and mx_on.get("p99_tpot_ms") is not None:
        merged["llm_mixed_p99_tpot_ms_chunked"] = mx_on["p99_tpot_ms"]
        if mx_off.get("p99_tpot_ms") is not None:
            merged["llm_mixed_p99_tpot_ms_unchunked"] = \
                mx_off["p99_tpot_ms"]
        if mx_on.get("p99_tpot_flatness_vs_unchunked") is not None:
            merged["llm_mixed_p99_tpot_flatness"] = \
                mx_on["p99_tpot_flatness_vs_unchunked"]
    elif mx_on:
        merged["mixed_error"] = mx_on["error"]
    if serve_row and "error" not in serve_row:
        for k in ("serve_llm_requests_per_s", "serve_llm_tokens_per_s",
                  "serve_llm_p50_ttft_ms", "serve_llm_p99_ttft_ms"):
            merged[k] = serve_row.get(k)
    elif serve_row:
        merged["serve_error"] = serve_row["error"]
    routed_merged = _merge_serve_routed_rows(routed_rows)
    if "error" not in routed_merged:
        for k in ("serve_routed_tokens_per_s", "serve_routed_p99_ttft_ms",
                  "serve_prefix_affinity_hit_rate",
                  "serve_routed_tokens_per_s_random",
                  "serve_routed_p99_ttft_ms_random",
                  "serve_routed_speedup_vs_random"):
            if routed_merged.get(k) is not None:
                merged[k] = routed_merged[k]
    else:
        merged["serve_routed_error"] = routed_merged["error"]
    loc_merged = _merge_locality_rows(loc_rows)
    if "error" not in loc_merged:
        for k in ("locality_hit_rate", "object_bytes_pulled_per_task",
                  "object_bytes_pulled_per_task_random"):
            if loc_merged.get(k) is not None:
                merged[k] = loc_merged[k]
    else:
        merged["locality_error"] = loc_merged["error"]
    dp_merged = _merge_dataplane_rows(dp_rows)
    for k in ("single_put_gbps", "multi_put_gbps", "put_scaling_ratio",
              "pull_gbps", "actor_args_nn_per_s"):
        if dp_merged.get(k) is not None:
            merged[k] = dp_merged[k]
    if "error" in dp_merged:
        merged["dataplane_error"] = dp_merged["error"]
    ch_merged = _merge_chaos_rows(chaos_rows)
    for k in ("head_recovery_s", "object_reconstruction_s",
              "head_upgrade_s", "leaked_leases", "leaked_resources"):
        if ch_merged.get(k) is not None:
            merged[k] = ch_merged[k]
    if "error" in ch_merged:
        merged["chaos_error"] = ch_merged["error"]
    sc = next((r for r in scale_rows if r.get("metric") == "head_scale"),
              {})
    if "error" not in sc and sc.get("head_dispatch_us_p99") is not None:
        suffix = f"{sc.get('nodes', 0)}node"
        merged[f"head_dispatch_us_p99_{suffix}"] = \
            sc["head_dispatch_us_p99"]
        merged[f"head_census_ms_{suffix}"] = sc.get("head_census_ms")
        for k in ("head_dispatch_bypass_rate", "storm_tasks_per_s",
                  "storm_tasks_per_s_headpath", "head_rpcs_per_task",
                  "head_rpcs_per_task_headpath"):
            if sc.get(k) is not None:
                merged[k] = sc[k]
    elif sc:
        merged["scale_error"] = sc["error"]
    dg = next((r for r in dag_rows if r.get("metric") == "dag_channel"),
              {})
    if "error" not in dg and dg.get("dag_hop_us_p50_4KB") is not None:
        for k in ("dag_hop_us_p50_4KB", "task_rpc_us_p50_4KB",
                  "dag_hop_speedup_vs_rpc_4KB",
                  "dag_exec_speedup_vs_rpc_4KB",
                  "dag_hop_speedup_vs_rpc_256KB"):
            if dg.get(k) is not None:
                merged[k] = dg[k]
    elif dg:
        merged["dag_error"] = dg["error"]
    dis_merged = _merge_serve_disagg_rows(disagg_rows)
    if "error" not in dis_merged:
        for k in ("serve_colo_p99_ttft_ms", "serve_disagg_p99_ttft_ms",
                  "serve_colo_p50_ttft_ms", "serve_disagg_p50_ttft_ms",
                  "serve_disagg_ttft_flatness"):
            if dis_merged.get(k) is not None:
                merged[k] = dis_merged[k]
    else:
        merged["serve_disagg_error"] = dis_merged["error"]
    da = next((r for r in data_rows
               if r.get("metric") == "data_executor"), {})
    if "error" not in da and da.get("data_shuffle_gbps_channel") is not None:
        for k in ("data_shuffle_gbps_channel", "data_shuffle_gbps_task",
                  "data_shuffle_channel_speedup",
                  "data_ingest_steps_per_s_buffered",
                  "data_ingest_steps_per_s_inline",
                  "data_ingest_steps_per_s_roofline",
                  "data_ingest_overlap_speedup",
                  "data_ingest_efficiency"):
            if da.get(k) is not None:
                merged[k] = da[k]
    elif da:
        merged["data_error"] = da["error"]
    kvf_merged = _merge_kv_fleet_rows(kvf_rows)
    if "error" not in kvf_merged:
        for k in ("kv_fleet_hit_rate", "kv_fleet_hit_rate_off",
                  "kv_fleet_p50_ttft_ms_churn",
                  "kv_fleet_p50_ttft_ms_churn_off",
                  "kv_pull_vs_recompute_crossover_blocks"):
            if kvf_merged.get(k) is not None:
                merged[k] = kvf_merged[k]
    else:
        merged["kv_fleet_error"] = kvf_merged["error"]
    svs_merged = _merge_serve_scale_rows(svs_rows)
    for k in ("router_decisions_per_s", "router_decisions_per_s_100",
              "router_scale_flatness",
              "router_avg_candidates_scored_10k",
              "serve_qos_good_p99_ttft_ms",
              "serve_qos_flood_p99_ttft_ms", "serve_qos_flood_shed",
              "serve_stream_disagg_p50_ttft_ms",
              "serve_stream_disagg_p50_tpot_ms",
              "serve_stream_disagg_probe_p50_ttft_ms",
              "serve_disagg_probe_p50_ttft_ms",
              "serve_stream_colo_p50_ttft_ms",
              "serve_scale_leaked_resources"):
        if svs_merged.get(k) is not None:
            merged[k] = svs_merged[k]
    if "error" in svs_merged:
        merged["serve_scale_error"] = svs_merged["error"]
    print(json.dumps(merged))
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(child_main())
    if "--serve-child" in sys.argv:
        sys.exit(serve_child_main())
    if "--serve-routed-child" in sys.argv:
        sys.exit(serve_routed_child_main())
    if "--serve" in sys.argv:
        sys.exit(serve_routed_main())
    if "--engine" in sys.argv:
        sys.exit(engine_child_main())
    if "--ops" in sys.argv:
        sys.exit(ops_main())
    if "--locality-child" in sys.argv:
        sys.exit(locality_child_main())
    if "--locality" in sys.argv:
        sys.exit(locality_main())
    if "--dataplane-child" in sys.argv:
        sys.exit(dataplane_child_main())
    if "--dataplane" in sys.argv:
        sys.exit(dataplane_main())
    if "--chaos-child" in sys.argv:
        sys.exit(chaos_child_main())
    if "--chaos" in sys.argv:
        sys.exit(chaos_main())
    if "--scale-child" in sys.argv:
        sys.exit(scale_child_main())
    if "--scale" in sys.argv:
        sys.exit(scale_main())
    if "--dag-child" in sys.argv:
        sys.exit(dag_child_main())
    if "--dag" in sys.argv:
        sys.exit(dag_bench_main())
    if "--data-child" in sys.argv:
        sys.exit(data_child_main())
    if "--data" in sys.argv:
        sys.exit(data_bench_main())
    if "--serve-disagg-child" in sys.argv:
        sys.exit(serve_disagg_child_main())
    if "--serve-disagg" in sys.argv:
        sys.exit(serve_disagg_main())
    if "--kv-fleet-child" in sys.argv:
        sys.exit(kv_fleet_child_main())
    if "--kv-fleet" in sys.argv:
        sys.exit(kv_fleet_bench_main())
    if "--serve-scale-child" in sys.argv:
        sys.exit(serve_scale_child_main())
    if "--serve-scale" in sys.argv:
        sys.exit(serve_scale_main())
    sys.exit(main())
