"""The dots3-note family (``models/dots3_note.py``) as one chip's share
against its plain reference on seeded float32 weights at a tiny size:
the selection of single rows across the ``index_topk`` boundary and
across chunk edges, the sliding layers' ring once it has wrapped, a
slot's new owner, the held experts (the shares add up to the uncut
layer), the exact sort-free top-k, the latent kernel under a mask, and
the engine's seam end to end."""

import dataclasses
import json
import os

import jax
from jax import lax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import dots3_note as builder
from benchmark.harness import manifest
from benchmark.reference import dsa_swa_moe_decoder as reference
from ray_tpu.models import dots3_note
from ray_tpu.ops import mla_decode_attention, mla_decode_attention_reference
from ray_tpu.ops import row_select
from ray_tpu.ops.grouped_experts import grouped_swiglu, split_expert_stacks

# Float32 on both sides, the same numbers in another order of
# operations; 2e-4 is the dense families' tolerance
# (tests/benchmark/test_reference.py).
TOL = dict(rtol=2e-4, atol=2e-4)
FILE = manifest.BENCH_DIR / "configs" / "dots3-note-prev-l5-ep8.json"


def _file(rehearse=True):
    with open(FILE) as f:
        c = json.load(f)
    return {**c, **c["rehearse"]} if rehearse else c


@pytest.fixture(scope="module")
def tiny():
    """(config dict, program configuration, params): the configuration
    file's own rehearsal sizes (index_topk 8, window 5, 4 of 16 experts
    held), norm gains and the indexer's LayerNorm moved
    off their starting values so that both conventions are compared."""
    c = _file()
    cfg = builder.config(c)
    params = builder.init_params(cfg, 5)
    bump = lambda k, a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(k),
                                                    a.shape, a.dtype)
    for i, (stack, name) in enumerate((
            ("full", "ln_attn"), ("full", "ln_q"), ("full", "ln_kv"),
            ("full", "ik_gain"), ("full", "ik_bias"), ("sliding", "ln_attn"),
            ("sliding", "ln_q"), ("sliding", "ln_kv"), ("dense", "ln_mlp"),
            ("moe", "ln_mlp"))):
        params[stack][name] = bump(i, params[stack][name])
    params["ln_out"] = bump(19, params["ln_out"])
    return c, cfg, params


_STATIC = dict(static_argnames=("cfg",))
_prefill = jax.jit(dots3_note.forward_with_cache, **_STATIC)
_prefill_last = jax.jit(dots3_note.forward_last_with_cache, **_STATIC)
_step = jax.jit(dots3_note.decode_step_with_cache, **_STATIC)
_forward = jax.jit(dots3_note.forward, **_STATIC)


def _tokens(seed, shape, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 1, vocab)


def _slot(cache, i):
    return {k: v[:, i:i + 1] for k, v in cache.items()}


def test_full_forward_agrees_with_the_reference(tiny):
    """One pass over 37 tokens: rows under, at and past ``index_topk``
    (positions 7, 8, 9: dense, the first that selects, the next) and
    past the window of 5."""
    c, cfg, params = tiny
    tokens = _tokens(1, (2, 37))
    rows = [(b, t) for b in range(2) for t in (0, 3, 4, 5, 7, 8, 9, 36)]
    want = reference.logits_at(params, tokens, rows, c)
    got = _forward(params, tokens, cfg=cfg)
    np.testing.assert_allclose(np.stack([got[b, t] for b, t in rows]), want,
                               **TOL)


def test_the_reference_follows_a_sequence_shorter_than_index_topk(tiny):
    """The check's short request: fewer rows than ``index_topk``, the
    system's rows handed over all the same."""
    c, cfg, params = tiny
    tokens = _tokens(6, (1, 6))
    rows = [(0, 5)]
    want = reference.logits_at(params, tokens, rows, c)
    visible = np.tril(np.ones((6, 6), bool))
    got, routing, selection = reference.followed_logits_at(
        params, tokens, rows, c, None, [lambda layer, a, b: visible[a:b]])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not selection["excess"].any() and not selection["differs"].any()


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_prefill_then_decode_through_the_three_entries(tiny, interpret):
    """Prefill 120 tokens into slot 1 of a two-slot cache whose entries
    start full of another owner's numbers, then 16 decode steps: latent
    rows, index keys and the ring (128 rows for a window of 5: whole
    tiles) that WRAPS at step 128; every step's logits against the
    reference's full forward pass, slot 0 not live and left as it was."""
    c, cfg, params = tiny
    cfg = dataclasses.replace(cfg, interpret_kernels=interpret)
    n, total, rows = 120, 136, 192
    tokens = _tokens(2, (1, total))
    want = np.asarray(reference.logits_at(
        params, tokens, [(0, t) for t in range(n - 1, total)], c))
    cache = jax.tree.map(lambda a: a + 1.0,
                         dots3_note.init_kv_cache(cfg, 2, rows))
    assert cache["kv"].shape == (2, 2, rows, 128)
    assert cache["ik"].shape == (2, 2, rows, 8)
    assert cache["win"].shape == (3, 2, 128, 128)
    logits, row, counters, seen = _prefill(
        params, tokens[:, :n], _slot(cache, 1), 0, cfg=cfg)
    np.testing.assert_allclose(logits[0, n - 1], want[0], **TOL)
    assert int(counters["dsa_queries_selected"]) == 2 * (n - 8)
    assert int(counters["moe_pairs_routed"]) == 4 * 2 * n
    assert seen["rows"].shape == (2, 1, n, rows)
    assert seen["experts"].shape == (4, 1, n, 2)
    chosen = np.asarray(seen["rows"]).sum(-1)[0, 0]
    assert list(chosen[:10]) == [1, 2, 3, 4, 5, 6, 7, 8, 8, 8]
    cache = {k: cache[k].at[:, 1:2].set(row[k]) for k in cache}
    before = jax.tree.map(lambda a: np.asarray(a[:, 0]), cache)
    live = jnp.asarray([False, True])
    for i in range(n, total):
        toks = jnp.stack([jnp.zeros((1,), tokens.dtype), tokens[0, i:i + 1]])
        logits, cache, counters, seen = _step(
            params, toks, cache, jnp.asarray([rows - 1, i], jnp.int32),
            cfg=cfg, live=live)
        np.testing.assert_allclose(logits[1], want[i - n + 1], **TOL)
        assert int(np.asarray(seen["rows"])[0, 1, 0].sum()) == 8
    assert int(counters["dsa_rows_visible"]) == 2 * total
    assert int(counters["dsa_rows_selected"]) == 2 * 8
    assert int(counters["window_rows_read"]) == 3 * 5
    assert int(counters["moe_layer_steps"]) == 4
    assert int(counters["moe_pairs_routed"]) == 4 * 2 * 2
    for k in ("win", "ik"):     # slot 0: its ring kept, its key parked
        kept = np.asarray(cache[k][:, 0])
        same = kept == before[k]
        assert same.all() if k == "win" else same[:, :rows - 1].all()


@pytest.mark.parametrize("edges", [(16, 32, 40), (32, 40), (16, 24, 40),
                                   (64, 126, 150)],
                         ids=["three-chunks", "two-chunks", "short-middle",
                              "ring-wrapped"])
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_a_prompt_prefilled_in_chunks_equals_one_prefill(tiny, edges,
                                                         interpret):
    """The selection past ``index_topk`` and the window both reach back
    across a chunk's edge: through `forward_last_with_cache`, each chunk
    padded to a bucket of 16, 32 or 64, the last row's logits and the
    three entries equal one prefill's; the last case's third chunk
    wraps the ring of 128 rows inside itself. The chunks with the full
    layers' attention as the kernel (interpreted) or its jnp twin, the
    one prefill always the twin: the mask each real query's attention
    ran under (``seen["rows"]``) and the prefill kernel's count are the
    same rows either way."""
    c, cfg, params = tiny
    total = edges[-1]
    tokens = _tokens(3, (1, total))
    cache = dots3_note.init_kv_cache(cfg, 1, 192)
    whole, want, one, seen = _prefill(params, tokens, cache, 0, cfg=cfg)
    cfg = dataclasses.replace(cfg, interpret_kernels=interpret)
    start, attended = 0, 0
    for end in edges:
        n = end - start
        bucket = next(b for b in (16, 32, 64) if n <= b)
        padded = jnp.zeros((1, bucket), tokens.dtype).at[:, :n].set(
            tokens[:, start:end])
        logits, cache, counters, chunk = _prefill_last(
            params, padded, cache, start, n - 1, cfg=cfg)
        assert (np.asarray(chunk["rows"][:, :, :n])
                == np.asarray(seen["rows"][:, :, start:end])).all()
        attended += int(counters["dsa_prefill_rows_attended"])
        start = end
    # 2 full layers; a query at position p keeps min(p + 1, 8) rows.
    assert attended == int(one["dsa_prefill_rows_attended"]) == 2 * sum(
        min(p + 1, 8) for p in range(total)) == int(seen["rows"].sum())
    np.testing.assert_allclose(logits[0], whole[0, total - 1], **TOL)
    for k in ("kv", "ik"):
        np.testing.assert_allclose(cache[k][:, :, :total],
                                   want[k][:, :, :total], **TOL)
    np.testing.assert_allclose(cache["win"], want["win"], **TOL)
    assert int(counters["moe_pairs_routed"]) == 4 * 2 * (total - edges[-2])


def test_a_slot_taken_over_decodes_as_a_fresh_one(tiny):
    """Nothing is zeroed when a slot changes owner: what a ring row or a
    latent row holds counts only by the query's position."""
    c, cfg, params = tiny
    tokens = _tokens(4, (1, 12))
    clean = dots3_note.init_kv_cache(cfg, 1, 64)
    used = jax.tree.map(lambda a: a + 3.0, clean)
    outs = []
    for cache in (clean, used):
        _, cache, _, _ = _prefill(params, tokens[:, :3], cache, 0, cfg=cfg)
        rows = []
        for i in range(3, 12):
            logits, cache, _, _ = _step(
                params, tokens[:, i:i + 1], cache,
                jnp.asarray([i], jnp.int32), cfg=cfg)
            rows.append(logits[0])
        outs.append(np.stack(rows))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", ["signed", "ties", "few", "negative_zero"])
def test_the_selection_is_the_exact_top_k_with_no_sort(case):
    """`row_select.top_rows` against `lax.top_k` (whose ties go to the
    lower index) on signed scores, on ties at the boundary, with fewer
    visible rows than k, and with -0.0 beside 0.0."""
    rng = np.random.default_rng(0)
    s, k = 200, 16
    scores = rng.standard_normal((5, s)).astype(np.float32) * 3
    visible = np.arange(s)[None, :] <= np.array([199, 150, 40, 16, 15])[:, None]
    if case == "ties":
        scores = np.round(scores)               # many equal values
    if case == "few":
        visible = np.arange(s)[None, :] < np.array([3, 9, 15, 16, 1])[:, None]
    if case == "negative_zero":
        scores = np.where(rng.random((5, s)) < 0.5, -0.0, 0.0).astype(
            np.float32)
        scores[:, ::7] = -1.0
    got = np.asarray(row_select.top_rows(jnp.asarray(scores),
                                         jnp.asarray(visible), k))
    ranked = np.where(visible, np.where(scores == 0, 0.0, scores), -np.inf)
    ids = np.asarray(lax.top_k(jnp.asarray(ranked), k)[1])
    want = np.zeros_like(visible)
    np.put_along_axis(want, ids, True, axis=1)
    want &= visible
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(visible.sum(-1), k)).all()
    within = np.asarray(row_select.top_rows_within(
        jnp.asarray(scores), jnp.asarray(visible), k, jnp.int32(60)))
    narrow = visible & (np.arange(s) < 60)
    if (narrow == visible).all():
        assert (within == want).all()


def test_index_scores_tile_by_tile_are_the_whole_product():
    q = jax.random.normal(jax.random.PRNGKey(0), (6, 2, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 2))
    keys = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    want = jnp.einsum("ths,th->ts", jax.nn.relu(
        jnp.einsum("thd,sd->ths", q, keys)), w)
    np.testing.assert_allclose(
        row_select.index_scores(q, w, keys, tile=16), want, rtol=1e-5,
        atol=1e-5)
    part = row_select.index_scores(q, w, keys, jnp.int32(20), tile=16)
    np.testing.assert_allclose(part[:, :32], want[:, :32], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(part[:, 32:]).any()


@pytest.mark.parametrize("block_s", [64, 256])
def test_the_selection_kernel_equals_its_twin(block_s):
    """``rtpu_dsa_select`` through the Pallas interpreter: a slot past
    ``k`` rows whose boundary falls inside a run of equal scores (ties to
    the lower row), one whose blocks past its length are never scored,
    one under ``k`` rows (reads them all), one that is not live."""
    b, hi, di, s, k = 4, 2, 8, 256, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (b, hi, di))
    w = jax.random.normal(keys[1], (b, hi))
    cache = jax.random.normal(keys[2], (2, b, s, di))
    cache = cache.at[1, 0, 8:250].set(cache[1, 0, 8])      # 242 equal rows
    positions = jnp.asarray([255, 100, 10, -1], jnp.int32)
    got = row_select.select_decode_rows(
        q, w, cache, positions, layer=jnp.int32(1), k=k, block_s=block_s,
        interpret=True)
    want = row_select.select_decode_rows_reference(q, w, cache[1], positions,
                                                   k)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert list(np.asarray(got).sum(-1)) == [16, 16, 11, 0]
    tied = np.asarray(got)[0, 8:250]
    assert 0 < tied.sum() < 242 and tied[:int(tied.sum())].all()


@pytest.mark.parametrize("block_s", [8, 32])
def test_the_latent_kernel_under_a_mask_equals_its_twin(block_s):
    """``keep`` through the Pallas interpreter: blocks with no kept row
    before the first kept one, a slot that keeps none at all."""
    b, h, s, dk, v = 3, 4, 32, 128, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, dk))
    cache = jax.random.normal(jax.random.PRNGKey(1), (2, b, s, dk))
    lengths = jnp.asarray([32, 20, 0], jnp.int32)
    keep = jax.random.bernoulli(jax.random.PRNGKey(2), 0.3, (b, s))
    keep = keep.at[:, :9].set(False).at[0, 30].set(True)
    got = mla_decode_attention(q, cache, lengths, layer=jnp.int32(1), v_dim=v,
                               scale=0.1, block_s=block_s, interpret=True,
                               keep=keep, name="rtpu_dsa_decode_attention")
    want = mla_decode_attention_reference(q, cache[1], lengths, v_dim=v,
                                          scale=0.1, keep=keep)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[2]).any()


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """Four chips' routed parts (4 of 16 experts each, the router and
    the gates over all 16) plus the shared expert counted ONCE are the
    uncut reference layer."""
    c, cfg, params = tiny
    d, f = cfg.d_model, cfg.moe_d_ff
    key = jax.random.split(jax.random.PRNGKey(7), 4)
    every = {"w_gate": jax.random.normal(key[0], (1, 16, d, f)) * d ** -0.5,
             "w_up": jax.random.normal(key[1], (1, 16, d, f)) * d ** -0.5,
             "w_down": jax.random.normal(key[2], (1, 16, f, d)) * f ** -0.5}
    layer = jax.tree.map(lambda a: a[1], {
        k: v for k, v in params["moe"].items()
        if k not in ("w_gate", "w_up", "w_down")})
    x = jax.random.normal(key[3], (33, d))
    uncut = dict(c, n_routed_experts=16,
                 reduced={"n_routed_experts": {"source": 16}})
    want = reference.expert_layer(
        x, dict(layer, **{k: v[0] for k, v in every.items()}), uncut)
    shared = dots3_note._swiglu(x, layer["ws_gate"], layer["ws_up"],
                                layer["ws_down"])
    total, pairs = shared, 0
    for chip in range(4):
        share = dataclasses.replace(cfg, held_experts=(4 * chip, 4))
        stacks = {k: v[0, 4 * chip:4 * chip + 4] for k, v in every.items()}
        y, experts, load, held, _, _ = dots3_note.moe_ffn(x, layer, stacks,
                                                          0, share)
        total = total + (y - shared)
        pairs += int(held)
        assert int(load.sum()) == int(held) == int(
            ((experts >= 4 * chip) & (experts < 4 * chip + 4)).sum())
    assert pairs == 33 * cfg.n_experts_per_tok
    np.testing.assert_allclose(total, want, **TOL)


@pytest.mark.parametrize("valid", [None, 9], ids=["all-real", "padded"])
def test_a_layer_that_holds_every_expert_is_the_layer_before_held(valid):
    """`grouped_swiglu(held=(0, E))` and ``held=None``: bit for bit, so
    GLM's and ZAYA's layers (pinned in tests/test_zaya.py and
    tests/test_glm_moe_lite.py) are what they were."""
    e, k, t, d, f = 8, 2, 11, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    stacks = {"w_gate": jax.random.normal(keys[0], (2 * e, d, f)),
              "w_up": jax.random.normal(keys[1], (2 * e, d, f)),
              "w_down": jax.random.normal(keys[2], (2 * e, f, d))}
    x = jax.random.normal(keys[3], (t, d))
    experts = jax.random.randint(keys[4], (t, k), 0, e)
    mask = None if valid is None else jnp.arange(t) < valid
    a = grouped_swiglu(x, experts, stacks, 1, e, mask)
    b = grouped_swiglu(x, experts, stacks, 1, e, mask, held=(0, e))
    (pairs, load), (pairs_held, load_held) = a, b
    assert (np.asarray(pairs.rows) == np.asarray(pairs_held.rows)).all()
    assert (np.asarray(load) == np.asarray(load_held)).all()
    if valid is None:           # every pair in a group: no mask to hand on
        assert pairs.there is None and bool(pairs_held.there.all())
    else:
        assert (np.asarray(pairs.there) == np.asarray(pairs_held.there)).all()
    assert int(a[1].sum()) == (t if valid is None else valid) * k


def test_the_engine_serves_the_family_through_its_seam(tiny):
    """`InferenceEngine` through ``cfg.model``: greedy tokens equal the
    functional path's with prompts prefilled in chunks past
    ``index_topk`` and the window, the family's counters are in
    ``stats()``, no prefix is reused (the ring), and a second request in
    the same slot is a fresh one."""
    from ray_tpu.serve.engine.core import InferenceEngine

    c, cfg, params = tiny
    prompts = [[int(t) for t in _tokens(10 + i, (n,))]
               for i, n in enumerate((9, 133, 14))]   # 133: the ring wraps
    prompts.insert(2, prompts[1][:40] + [7, 8])     # a resident prefix

    def functional(prompt, answer):
        logits = _forward(params, jnp.asarray([prompt + answer]), cfg=cfg)
        at = len(prompt) - 1
        return [int(t) for t in jnp.argmax(logits[0, at:at + len(answer)],
                                           axis=-1)]

    engine = InferenceEngine(cfg, params, max_batch=1, max_len=192,
                             prompt_buckets=[16, 32], prefill_chunk=16,
                             decode_chunk=4, kv_fleet_min_prefix_blocks=-1)
    try:
        got = [engine.generate(p, max_new_tokens=6)["token_ids"]
               for p in prompts]
        stats = engine.stats()
    finally:
        engine.close()
    assert got == [functional(p, a) for p, a in zip(prompts, got)]
    assert stats["prefix_reuse_vetoed"] >= 1
    assert stats["moe_layer_steps"] > 0 and stats["moe_expert_hits"] >= 0
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs_routed"]
    assert stats["dsa_queries_selected"] > 0
    assert stats["dsa_rows_selected"] <= stats["dsa_rows_visible"]
    assert stats["dsa_rows_attended"] == stats["dsa_rows_visible"]
    assert stats["window_rows_read"] > 0
    assert stats["state_bytes_per_slot"] == 3 * 128 * 128 * 4
    assert stats["kv_bytes_per_token"] == 2 * (128 + 8) * 4
    assert engine._span_attrs([{"dsa_queries_selected": 3,
                                "moe_pairs_held": 5}]) == {
        "queries_selected": 3, "expert_pairs_held": 5}
    with pytest.raises(ValueError, match="cannot serve with quantize"):
        InferenceEngine(cfg, params, max_batch=1, max_len=64,
                        quantize="int8", kv_fleet_min_prefix_blocks=-1)


def test_the_configuration_file_keeps_every_published_width():
    c = _file(rehearse=False)
    cfg = builder.config(c)
    full, sliding = cfg.full, cfg.sliding
    assert cfg.d_model == 5120 and cfg.d_ff == 13824 and cfg.moe_d_ff == 1536
    assert (full.n_heads, full.q_lora_rank, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.rope_theta) == (128, 1024, 512, 128, 64, 128, 8e7)
    assert (sliding.n_heads, sliding.q_lora_rank, sliding.kv_lora_rank,
            sliding.qk_nope_head_dim, sliding.qk_rope_head_dim,
            sliding.v_head_dim, sliding.rope_theta) == (
        64, 1024, 1024, 192, 64, 128, 5e4)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk, cfg.window,
            cfg.ring_rows) == (64, 128, 2048, 513, 640)
    assert (cfg.n_experts, cfg.held_experts, cfg.n_experts_per_tok,
            cfg.n_shared_experts) == (256, (0, 32), 8, 1)
    assert full.row_dim == 640 and sliding.row_dim == 1152
    assert cfg.layer_types == ("full_attention",) * 2 + (
        "sliding_attention",) * 3 and cfg.n_dense_layers == 1
    assert cfg.vocab_size == 19008 == 152064 // 8
    assert set(c["reduced"]) == {"num_hidden_layers", "layer_types",
                                 "n_routed_experts", "vocab_size",
                                 "max_position_embeddings"}
    assert c["reduced"]["n_routed_experts"]["source"] == 256
    assert c["expert_parallel"] == {"chips": 8, "this_chip": 0}
    assert set(c["departures"]) == {"towers", "hadamard"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # every other key as the catalog has it
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if '"dots3-note-prev"' in line)
        changed = {k for k, v in row["config"].items() if c[k] != v}
        assert changed == set(c["reduced"])
        assert c["source"] == row["source_url"]
    with pytest.raises(ValueError, match="shorter than num_hidden_layers"):
        builder.config({**c, "num_hidden_layers": 6})


def test_zaya_moe_ffn_is_bit_identical_after_the_grouped_product_learned_held():
    """``models/zaya.moe_ffn`` through `grouped_swiglu` as PR 42 left it:
    the values it gave on PR 42's PARENT (XLA:CPU, float32), with and
    without a bucket's padding. GLM's are pinned in tests/test_zaya.py."""
    from benchmark.builders import zaya as zaya_builder
    from ray_tpu.models import zaya

    with open(manifest.BENCH_DIR / "configs" / "zaya1-8b-l16.json") as f:
        c = json.load(f)
    cfg = zaya_builder.config({**c, **c["rehearse"]})
    params = zaya_builder.init_params(cfg, 5)
    stacks, scanned = split_expert_stacks(params["layers"])
    layer = jax.tree.map(lambda a: a[1], scanned)
    x = jax.random.normal(jax.random.PRNGKey(3), (11, cfg.d_model),
                          jnp.float32)
    first = [[0.09585101902484894, 0.14052051305770874,
              -0.07692547142505646],
             [-0.22789475321769714, 0.08171960711479187,
              0.19371619820594788]]
    pinned = {
        None: (first + [[-0.23532745242118835, 0.04868624359369278,
                         -0.038377795368433]],
               [1, 2, 0, 4, 3, 1, 0, 0], "0x1.bd334c0000000p+5"),
        9: (first + [[0.0, 0.0, 0.0]],
            [0, 2, 0, 4, 2, 1, 0, 0], "0x1.7a88e40000000p+5")}
    for n_valid, (rows, load_want, total) in pinned.items():
        valid = None if n_valid is None else jnp.arange(11) < n_valid
        y, expert, load, _ = jax.jit(
            lambda x: zaya.moe_ffn(x, layer, stacks, 1, cfg, valid))(x)
        assert np.asarray(y)[[0, 4, 10], :3].tolist() == rows
        assert expert.tolist() == [4, 3, 3, 4, 1, 3, 3, 1, 5, 4, 0]
        assert load.tolist() == load_want
        assert float(np.abs(np.asarray(y)).sum()).hex() == total
