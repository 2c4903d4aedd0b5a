"""``models/minicpm_sala.py`` on the CPU, small sizes, seeded float32
weights: the module against the plain reference
(``benchmark/reference/sparse_linear_decoder.py``) for the full forward
pass, for a prefill in chunks then decode through the engine's cache
across ``dense_len``, and what the cache's three kinds of entry owe the
engine: padding that steps no state and completes no window, the reset
at ``cache_index`` 0, slots that are not live left alone; each new
kernel against its ``jnp`` twin under the Pallas interpreter."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_sparse_hybrid
from benchmark.drivers.common import rel_l2 as _rel
from benchmark.reference import sparse_linear_decoder as reference
from ray_tpu.models import minicpm_sala as sala
from ray_tpu.ops import decode_attention_reference, lightning
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.sparse_attention import Selection

S, L = sala.SPARSE, sala.LIGHTNING
SEL = Selection(kernel=8, stride=4, block=16, init_blocks=1, window=32,
                topk=6, dense_len=96)
# An irregular order: runs of 1, 2, 2 and 1 layers.
CFG = sala.MiniCPMSalaConfig(
    vocab_size=97, d_model=64, mixer_types=(S, L, L, S, S, L), n_heads=4,
    n_kv_heads=2, head_dim=16, lightning_heads=4, lightning_head_dim=16,
    d_ff=96, max_seq_len=256, selection=SEL, dtype=jnp.float32,
    interpret_kernels=True)
FILE = dict(
    hidden_size=64, num_hidden_layers=6, mixer_types=list(CFG.mixer_types),
    num_key_value_heads=2, head_dim=16, lightning_nh=4, rms_norm_eps=1e-6,
    rope_theta=10000.0, scale_emb=12.0, scale_depth=1.4, mup_denominator=32,
    dim_model_base=256,
    sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16,
                       init_blocks=1, window_size=32, topk=6, dense_len=96))
# float32 on both sides, sums in another order: what a row of logits
# may differ by. bf16 anywhere on the way reads 1e-3 or more.
TOL = 2e-5
T = 160


@pytest.fixture(scope="module")
def made():
    with jax.default_matmul_precision("highest"):
        params = sala.init_params(CFG, jax.random.PRNGKey(0))
        tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                               (1, T), 1, 97))
        ref = np.asarray(reference.logits_at(
            params, tokens, [(0, p) for p in range(T)], FILE))
    return params, tokens, ref


_prefill = jax.jit(lambda p, t, c, ci, la: sala.forward_last_with_cache(
    p, t, c, ci, la, CFG))
_step = jax.jit(lambda p, t, c, ln, lv: sala.decode_step_with_cache(
    p, t, c, ln, CFG, lv))


def test_the_irregular_order_is_walked_a_run_at_a_time():
    assert CFG.segments == [(S, 0, 1), (L, 0, 2), (S, 1, 2), (L, 2, 1)]
    assert (CFG.n_sparse_layers, CFG.n_lightning_layers) == (3, 3)
    with pytest.raises(ValueError, match="mixer types"):
        dataclasses.replace(CFG, mixer_types=(S, "mamba"))


def test_full_forward_matches_the_reference(made):
    params, tokens, ref = made
    with jax.default_matmul_precision("highest"):
        logits, _, counters, seen = jax.jit(
            lambda p, t: sala.forward_with_cache(
                p, t, sala.init_kv_cache(CFG, 1, T), 0, CFG))(params, tokens)
    errs = [_rel(logits[0, p], ref[p]) for p in range(T)]
    assert max(errs) < TOL
    # Rows past dense_len select; the reference, made to follow the
    # module's blocks, finds them its own.
    mask = np.asarray(seen["block_mask"])
    assert mask.shape == (3, 1, T, 2, T // 16) and mask.dtype == bool
    blocks = serve_sparse_hybrid.listed(mask, 6)
    assert blocks.shape == (3, 1, T, 2, 6)
    assert (blocks[:, :, :96] == -1).all() and (blocks[:, :, 96:] >= 0).all()
    with jax.default_matmul_precision("highest"):
        _, report = reference.selected_logits_at(
            params, tokens, [(0, T - 1)], FILE, blocks)
    assert report["differs"].sum() == 0 and report["excess"].max() == 0.0
    assert int(counters["state_resets"]) == 1


def test_chunked_prefill_then_decode_crosses_dense_len(made):
    """Three whole chunks and a padded one (100 tokens: the prompt ends
    past dense_len), then 50 decode steps in a cache of two slots that
    started full of ones; slot 0 is never live."""
    params, tokens, ref = made
    cache = jax.tree.map(lambda a: a + 1, sala.init_kv_cache(CFG, 2, 256))
    row = {k: v[:, 1:2] for k, v in cache.items()}
    with jax.default_matmul_precision("highest"):
        for ci in (0, 32, 64):
            logits, row, counters, _ = _prefill(
                params, tokens[:, ci:ci + 32], row, ci, 31)
        assert int(counters["state_resets"]) == 0       # not this chunk
        padded = np.zeros((1, 32), np.int32)
        padded[0, :4] = tokens[0, 96:100]
        logits, row, _, seen = _prefill(params, padded, row, 96, 3)
        assert _rel(logits[0], ref[99]) < TOL
        assert (np.asarray(seen["block_mask"])[:, 0, :4].sum(-1) == 6).all()
        cache = {k: cache[k].at[:, 1:2].set(row[k]) for k in cache}
        errs = []
        for pos in range(100, 150):
            logits, cache, counters, _ = _step(
                params, np.array([[0], [tokens[0, pos]]], np.int32), cache,
                np.array([255, pos], np.int32), np.array([False, True]))
            errs.append(_rel(logits[1], ref[pos]))
        state = reference.first_state(params, tokens[0, :150], FILE)
    assert max(errs) < TOL
    assert _rel(cache["state"][0, 1], state) < TOL
    # One live slot: 3 sparse layers' rows, 6 blocks of 16 less the
    # rows of the newest block that are not there yet.
    assert int(counters["sparse_rows_held"]) == 3 * 150
    assert int(counters["sparse_rows_selected"]) == 3 * (6 * 16 - 10)
    assert int(counters["sparse_select_steps"]) == 3
    assert int(counters["lightning_state_steps"]) == 3
    # The idle slot: state and windows as they were (its parked K/V
    # write lands on its last row, its parked window on the last index).
    assert (np.asarray(cache["state"][:, 0]) == 1).all()
    assert (np.asarray(cache["kc"][:, 0, :, :-1]) == 1).all()
    assert (np.asarray(cache["k"][:, 0, :, :-1]) == 1).all()


def test_a_dense_context_in_decode_is_plain_attention(made):
    """Under dense_len the block list is every block: the step's logits
    are the reference's from the first decoded token on."""
    params, tokens, ref = made
    cache = sala.init_kv_cache(CFG, 1, 256)
    with jax.default_matmul_precision("highest"):
        _, cache, _, _ = _prefill(params, tokens[:, :32], cache, 0, 31)
        for pos in range(32, 40):
            logits, cache, counters, seen = _step(
                params, tokens[:, pos:pos + 1], cache,
                np.array([pos], np.int32), np.array([True]))
            assert _rel(logits[0], ref[pos]) < TOL
    assert (np.asarray(seen["blocks"]) == -1).all()
    assert int(counters["sparse_rows_selected"]) == int(
        counters["sparse_rows_held"]) == 3 * 40
    assert int(counters["sparse_select_steps"]) == 0


def test_padding_steps_no_state_and_completes_no_window(made):
    """A bucket of 32 with 21 real tokens against the same 21 tokens
    unpadded... the state is the same, and no query ever counts a
    window that holds padding: decode on from the padded prefill meets
    the reference."""
    params, tokens, ref = made
    with jax.default_matmul_precision("highest"):
        exact = sala.forward_with_cache(
            params, tokens[:, :20], sala.init_kv_cache(CFG, 1, 256), 0,
            CFG)[1]
        padded = np.full((1, 32), 7, np.int32)
        padded[0, :20] = tokens[0, :20]
        logits, cache, _, _ = _prefill(params, padded,
                                       sala.init_kv_cache(CFG, 1, 256), 0, 19)
        assert _rel(cache["state"], exact["state"]) < 1e-6
        assert _rel(logits[0], ref[19]) < TOL
        for pos in range(20, 36):       # rewrites the windows padding made
            logits, cache, _, _ = _step(
                params, tokens[:, pos:pos + 1], cache,
                np.array([pos], np.int32), np.array([True]))
            assert _rel(logits[0], ref[pos]) < TOL
    want = sa.window_means(cache["k"][:, :, :, :36], SEL)
    assert _rel(cache["kc"][:, :, :, :8], want) < 1e-6


def test_an_admission_resets_the_slot(made):
    params, tokens, ref = made
    dirty = jax.tree.map(lambda a: a + 3, sala.init_kv_cache(CFG, 1, 256))
    with jax.default_matmul_precision("highest"):
        logits, cache, counters, _ = _prefill(params, tokens[:, :32], dirty,
                                              0, 31)
        assert int(counters["state_resets"]) == 1
        assert _rel(logits[0], ref[31]) < TOL
        state = reference.first_state(params, tokens[0, :32], FILE)
    assert _rel(cache["state"][0, 0], state) < TOL


def test_the_engine_serves_it_in_chunks(made):
    """Through `LLMEngine`: chunked prefill, slots reused, the greedy
    tokens the reference's; every chunk's counters in the one fetch."""
    from ray_tpu.serve.llm import LLMEngine

    params, tokens, ref = made
    with jax.default_matmul_precision("highest"):
        eng = LLMEngine(cfg=CFG, params=params, max_batch=2, max_len=256,
                        prompt_buckets=[16, 32], prefill_chunk=32,
                        decode_chunk=4, kv_fleet_min_prefix_blocks=-1)
        try:
            for _ in range(3):          # the third reuses a slot
                out = eng.generate(list(map(int, tokens[0, :100])),
                                   max_new_tokens=6)
            stats = eng.stats()
        finally:
            eng.close()
        rows = np.asarray(reference.logits_at(
            params, [list(tokens[0, :100]) + out["token_ids"]],
            [(0, 99 + j) for j in range(6)], FILE))
    assert out["token_ids"] == rows.argmax(-1).tolist()
    assert stats["prefill_chunks"] == 3 * 4 and stats["state_resets"] == 3
    assert stats["kv_bytes_per_token"] == 3 * 2 * 16 * 4 * (2 + 1 / 4)
    assert stats["state_bytes_per_slot"] == 3 * 4 * 16 * 16 * 4
    assert 0 < stats["sparse_rows_selected"] < stats["sparse_rows_held"]


# The kernels against their twins -------------------------------------------

def test_lightning_chunk_scan_is_the_recurrence():
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v = (jax.random.normal(key, (2, 70, 4, 16)) for key in ks[:3])
    g = jnp.broadcast_to(lightning.log_decays(4), (2, 70, 4))
    g = g.at[:, 50:].set(0.0)           # padding: no decay, no key
    k = k.at[:, 50:].set(0.0)
    s0 = jax.random.normal(ks[3], (2, 4, 16, 16))
    with jax.default_matmul_precision("highest"):
        o1, s1 = lightning.recurrence(q, k, v, g, s0)
        o2, s2 = lightning.chunk_scan(q, k, v, g, s0, chunk=16)
        _, s3 = lightning.recurrence(q[:, :50], k[:, :50], v[:, :50],
                                     g[:, :50], s0)
    assert _rel(o2, o1) < 1e-5 and _rel(s2, s1) < 1e-5
    assert _rel(s2, s3) < 1e-6          # the padding stepped nothing
    assert np.allclose(np.exp(lightning.log_decays(32))[[0, 31]],
                       [np.exp(-2 ** -0.25), np.exp(-2 ** -8.0)])


def test_lightning_decode_kernel_is_its_twin_and_steps_one_layer():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    state = jax.random.normal(ks[0], (3, 2, 4, 16, 16))
    q, k, v = (jax.random.normal(key, (2, 4, 16)) for key in ks[1:])
    g = jnp.stack([lightning.log_decays(4), jnp.zeros(4)])
    k = k.at[1].set(0.0)                # slot 1 is not live
    o_twin, s_twin = lightning.lightning_decode(state, 1, q, k, v, g)
    o_kern, s_kern = lightning.lightning_decode(state, 1, q, k, v, g,
                                                interpret=True)
    assert (np.asarray(o_twin) == np.asarray(o_kern)).all()
    assert (np.asarray(s_twin) == np.asarray(s_kern)).all()
    assert (np.asarray(s_kern)[[0, 2]] == np.asarray(state)[[0, 2]]).all()
    assert (np.asarray(s_kern[1, 1]) == np.asarray(state[1, 1])).all()
    o_ref, s_ref = lightning.recurrence(q[:, None], k[:, None], v[:, None],
                                        g[:, None], state[1])
    assert _rel(o_kern, o_ref[:, 0]) < 1e-6 and _rel(s_kern[1], s_ref) < 1e-6


def test_sparse_decode_kernel_is_its_twin():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (3, 8, 16))
    k = jax.random.normal(ks[1], (2, 3, 2, 256, 16))
    v = jax.random.normal(ks[2], (2, 3, 2, 256, 16))
    kc = jnp.pad(sa.window_means(k[1], SEL), ((0, 0), (0, 0), (0, 1), (0, 0)))
    seen = jnp.array([200, 50, 0])      # selected, dense, not live
    ids, count = sa.select_blocks(q, kc, seen, SEL)
    assert ids.shape == (3, 2, 6) and count.tolist() == [6, 4, 0]
    # Forced: block 0 and the blocks of rows 168 .. 199.
    assert all({0, 10, 11, 12} <= set(row) for row in ids[0].tolist())
    twin = sa.sparse_decode_attention(q, k, v, ids, count, seen, layer=1,
                                      block=16)
    kern = sa.sparse_decode_attention(q, k, v, ids, count, seen, layer=1,
                                      block=16, interpret=True)
    assert _rel(kern, twin) < 1e-6
    plain = decode_attention_reference(q, k[1].transpose(0, 2, 1, 3),
                                       v[1].transpose(0, 2, 1, 3), seen)
    assert _rel(kern[1], plain[1]) < 1e-6 and not np.asarray(kern[2]).any()
    # The prefill's masked attention for the same query and rows.
    chunk = jax.random.normal(ks[0], (64, 8, 16)).at[-1].set(q[0])
    out, chosen = sa.sparse_prefill_attention(
        chunk, k[1, 0], v[1, 0], kc[0], jnp.arange(136, 200), SEL,
        q_tile=32, kv_tile=64)
    assert _rel(out[-1], twin[0]) < 1e-6
    assert np.flatnonzero(chosen[-1, 0]).tolist() == sorted(
        ids[0, 0].tolist())


@pytest.mark.parametrize("scores", ["spread", "tied", "flat"])
def test_the_prefills_mask_is_the_ranked_list_without_its_sort(scores):
    """`_best` (the k-th score found a bit at a time) marks exactly the
    blocks `_ranked`'s `top_k` lists, ties to the lower block."""
    sel = Selection()
    rng = np.random.default_rng(5)
    s = rng.random((2, 40, 512)).astype(np.float32) * 1e-3
    s = {"spread": s, "tied": np.round(s * 8e3) / 8e3,
         "flat": np.zeros_like(s)}[scores]
    t1 = jnp.asarray(rng.integers(8193, 32768, (2, 40)))
    ids, _ = sa._ranked(jnp.asarray(s), t1, sel, sel.topk)
    listed = np.zeros(s.shape, bool)
    np.put_along_axis(listed, np.asarray(ids), True, axis=-1)
    assert (np.asarray(sa._best(jnp.asarray(s), t1, sel)) == listed).all()


def test_the_selection_refuses_sizes_it_was_not_written_for():
    with pytest.raises(ValueError, match="kernel = 2 x stride"):
        Selection(kernel=32, stride=8)
    with pytest.raises(ValueError, match="forced blocks"):
        Selection(window=4096)
    with pytest.raises(ValueError, match="topk blocks"):
        Selection(dense_len=2048)
    assert Selection().list_len(32768) == 128 and SEL.list_len(256) == 6
