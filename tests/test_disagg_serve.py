"""Disaggregated prefill/decode serving: equivalence + failure tests.

Engine tier (store-free, tier-1): a prefill-role engine's KV handoff
installed into a decode-role engine must continue to TOKEN-IDENTICAL
greedy output vs the colocated engine, with the decode side's KV block
chain hashes equal to the prefill side's.

Serve tier (needs the native store lib, like every cluster-booting
test): ``build_llm_deployment(disaggregated=True)`` vs the colocated
deployment over real replicas + DAG channels, including decode-replica
death mid-service (the request re-routes, satellite-6 contract).
"""

import pytest


def _engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    base = dict(max_batch=2, max_len=96, prompt_buckets=[8, 16, 32],
                decode_chunk=4, seed=0)
    base.update(kw)
    return LLMEngine(**base)


PROMPTS = [
    [5, 9, 2, 7, 7, 1],
    [3, 3, 3, 3, 1, 2, 8, 4, 4, 4, 9, 9, 1, 0, 2, 5, 6, 7],
    list(range(1, 33)),  # multi-page prompt (block 16 -> 2 pages)
]


# ------------------------------------------------------------ engine tier


def test_disagg_token_identity_vs_colocated():
    colo = _engine()
    pre = _engine(role="prefill")
    dec = _engine(role="decode")
    try:
        for p in PROMPTS:
            ref = colo.generate(p, max_new_tokens=20)
            h = pre.prefill_remote(p, max_new_tokens=20)
            assert h.get("kv_handoff"), h
            out = dec.install_remote(h)
            assert out["token_ids"] == ref["token_ids"], p
    finally:
        colo.close()
        pre.close()
        dec.close()


def test_disagg_chain_hashes_equal_on_decode_side():
    pre = _engine(role="prefill")
    dec = _engine(role="decode")
    try:
        p = PROMPTS[2]
        h = pre.prefill_remote(p, max_new_tokens=4)
        assert len(h["chain"]) == len(p) // 16  # complete blocks hashed
        req = dec.install_async(h)
        req.future.result(timeout=120)
        # The install asserted chain equality internally; a corrupted
        # chain must be REJECTED (wrong-KV installs can't go silent).
        h2 = pre.prefill_remote(PROMPTS[1], max_new_tokens=4)
        h2["chain"] = [hash("corrupt")]
        with pytest.raises(RuntimeError, match="chain mismatch"):
            dec.install_remote(h2)
        # ...and the failed install released its slot.
        assert dec.kv.free_slots() == dec.max_batch
    finally:
        pre.close()
        dec.close()


def test_disagg_with_chunked_prefill_and_prefix_reuse():
    """Chunked prefill on the prefill engine + a repeat-prefix prompt
    (the prefill-side prefix cache serves the reused blocks) still
    hands off KV that decodes token-identically."""
    colo = _engine()
    pre = _engine(role="prefill", prefill_chunk=16)
    dec = _engine(role="decode")
    try:
        p = PROMPTS[2]
        for trip in range(2):  # second trip hits the prefill prefix cache
            ref = colo.generate(p, max_new_tokens=12)
            h = pre.prefill_remote(p, max_new_tokens=12)
            out = dec.install_remote(h)
            assert out["token_ids"] == ref["token_ids"], trip
        assert pre.kv.hits >= 1  # the reuse actually happened
    finally:
        colo.close()
        pre.close()
        dec.close()


def test_disagg_budget_one_completes_on_prefill_side():
    pre = _engine(role="prefill")
    try:
        out = pre.prefill_remote(PROMPTS[0], max_new_tokens=1)
        assert "kv_handoff" not in out
        assert out["num_generated"] == 1
    finally:
        pre.close()


def test_disagg_concurrent_installs_queue_for_slots():
    """More concurrent handoffs than decode slots: installs wait FIFO
    for recycled slots instead of failing."""
    import threading

    pre = _engine(role="prefill")
    dec = _engine(role="decode", max_batch=2)
    try:
        handoffs = [pre.prefill_remote(PROMPTS[i % 3], max_new_tokens=8)
                    for i in range(5)]
        outs = [None] * 5

        def run(i):
            outs[i] = dec.install_remote(handoffs[i], timeout=180)

        ts = [threading.Thread(target=run, args=(i,)) for i in range(5)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        colo = _engine()
        try:
            for i in range(5):
                ref = colo.generate(PROMPTS[i % 3], max_new_tokens=8)
                assert outs[i]["token_ids"] == ref["token_ids"], i
        finally:
            colo.close()
    finally:
        pre.close()
        dec.close()


def test_disagg_engines_close_balanced(monkeypatch):
    """RTPU_DEBUG_RES: a full prefill→handoff→install→decode round
    leaves no outstanding kv_spec reservations on either engine."""
    monkeypatch.setenv("RTPU_DEBUG_RES", "1")
    from ray_tpu.devtools import res_debug

    res_debug.reset()
    pre = _engine(role="prefill")
    dec = _engine(role="decode")
    h = pre.prefill_remote(PROMPTS[1], max_new_tokens=8)
    dec.install_remote(h)
    pre.close()
    dec.close()
    assert not res_debug.violations(), res_debug.violations()
    assert res_debug.outstanding("kv_spec").get("kv_spec", 0) == 0
    res_debug.reset()


def test_disagg_roles_reject_wrong_entrypoints():
    colo = _engine()
    try:
        with pytest.raises(RuntimeError, match="role='prefill'"):
            colo.prefill_remote(PROMPTS[0])
        with pytest.raises(RuntimeError, match="role='decode'"):
            colo.install_async({"page": 16})
    finally:
        colo.close()


def test_disagg_page_size_mismatch_rejected():
    pre = _engine(role="prefill", prefix_block=16)
    dec = _engine(role="decode", prefix_block=8)
    try:
        h = pre.prefill_remote(PROMPTS[1], max_new_tokens=4)
        with pytest.raises(ValueError, match="page size mismatch"):
            dec.install_async(h)
    finally:
        pre.close()
        dec.close()


# ------------------------------------------------------------- serve tier


@pytest.fixture(scope="module")
def serve_cluster(native_store):
    import ray_tpu
    import ray_tpu.serve as serve

    rt = ray_tpu.init(num_cpus=24)
    yield rt
    serve.shutdown()
    ray_tpu.shutdown()


def _collect_stream(handle, payload, timeout=240.0):
    gen = handle.options("stream", stream=True).remote(payload)
    import time as _t

    deadline = _t.time() + timeout
    toks = []
    for t in gen:
        toks.append(int(t))
        assert _t.time() < deadline, "stream stalled"
    return toks


def test_serve_disagg_stream_token_identity(serve_cluster):
    """Disaggregated streaming (prefill-time first token + decode
    deltas over the reverse result channel) is token-identical to
    colocated streaming AND to the non-streaming result — including
    the multi-page prompt and a mid-stream EOS stop."""
    import ray_tpu.serve as serve
    from ray_tpu.serve.llm import build_llm_deployment

    ek = dict(max_batch=2, max_len=96, prompt_buckets=[8, 16, 32],
              decode_chunk=4, seed=0)
    colo = serve.run(build_llm_deployment(name="stcolo",
                                          engine_kwargs=ek))
    dis = serve.run(build_llm_deployment(
        name="stdis", disaggregated=True, num_decode_replicas=2,
        engine_kwargs=ek))
    for p in PROMPTS:
        req = {"prompt_ids": p, "max_new_tokens": 12}
        ref = colo.remote(dict(req)).result(timeout=120)["token_ids"]
        assert _collect_stream(colo, dict(req)) == ref, p
        assert _collect_stream(dis, dict(req)) == ref, p
    # Mid-stream EOS: pick a token the reference emits mid-generation
    # and make it the stop token — both streams must truncate there,
    # including the EOS token itself, identically.
    p = PROMPTS[2]
    ref = colo.remote({"prompt_ids": p, "max_new_tokens": 12}
                      ).result(timeout=120)["token_ids"]
    eos = ref[4]
    req = {"prompt_ids": p, "max_new_tokens": 12, "eos_id": eos}
    want = colo.remote(dict(req)).result(timeout=120)["token_ids"]
    assert want[-1] == eos and len(want) < len(ref)
    assert _collect_stream(colo, dict(req)) == want
    assert _collect_stream(dis, dict(req)) == want


def test_serve_disagg_stream_reroute_on_decode_death(serve_cluster):
    """SIGKILL the decode replicas after the stream has delivered a
    few tokens: the retained handoff re-routes to a (re-spawned or
    surviving) decode replica and the REPLAYED stream resumes where it
    left off — the consumer sees one token-identical sequence."""
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.serve._private.controller import CONTROLLER_NAME
    from ray_tpu.serve.llm import build_llm_deployment

    ek = dict(max_batch=2, max_len=96, prompt_buckets=[8, 16, 32],
              decode_chunk=4, seed=0)
    colo = serve.run(build_llm_deployment(name="skcolo",
                                          engine_kwargs=ek))
    dis = serve.run(build_llm_deployment(
        name="skdis", disaggregated=True, num_decode_replicas=2,
        engine_kwargs=ek))
    p = PROMPTS[1]
    req = {"prompt_ids": p, "max_new_tokens": 16}
    ref = colo.remote(dict(req)).result(timeout=120)["token_ids"]
    gen = dis.options("stream", stream=True).remote(dict(req))
    got = []
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    for t in gen:
        got.append(int(t))
        if len(got) == 3:
            _, replicas = ray_tpu.get(
                controller.get_replica_set.remote("skdis-decode"),
                timeout=30)
            for rep in replicas:
                ray_tpu.kill(rep)
    assert got == ref


def test_serve_disagg_equivalence_and_reroute_on_death(serve_cluster):
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.serve.llm import build_llm_deployment

    ek = dict(max_batch=2, max_len=96, prompt_buckets=[8, 16, 32],
              decode_chunk=4, seed=0)
    colo = serve.run(build_llm_deployment(name="eqcolo",
                                          engine_kwargs=ek))
    dis = serve.run(build_llm_deployment(
        name="eqdis", disaggregated=True, num_decode_replicas=2,
        engine_kwargs=ek))
    refs = {}
    for p in PROMPTS:
        refs[tuple(p)] = colo.remote(
            {"prompt_ids": p, "max_new_tokens": 12}).result(timeout=120)
        out = dis.remote(
            {"prompt_ids": p, "max_new_tokens": 12}).result(timeout=120)
        assert out["token_ids"] == refs[tuple(p)]["token_ids"], p

    # Kill ONE decode replica: channel edges to it die; in-flight and
    # later requests must re-route to the surviving replica and still
    # return token-identical results.
    from ray_tpu.serve._private.controller import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    version, replicas = ray_tpu.get(
        controller.get_replica_set.remote("eqdis-decode"), timeout=30)
    assert len(replicas) == 2
    ray_tpu.kill(replicas[0])
    for trip in range(3):
        for p in PROMPTS:
            out = dis.remote({"prompt_ids": p, "max_new_tokens": 12}
                             ).result(timeout=180)
            assert out["token_ids"] == refs[tuple(p)]["token_ids"], \
                (trip, p)
