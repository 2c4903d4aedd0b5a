"""The grouped expert products' two implementations
(``ops/grouped_experts.py``): the Pallas kernels the chip takes from 8
pairs a held group on, interpreted here, against the ``lax.ragged_dot``
path they stand in for and against a float32 masked dense product; and
the rule that chooses between them from static shapes, by cases: one a
family's decode step and smallest prompt bucket at its cell's sizes, so
that a new family cannot move another's implementation unseen."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_experts as ge

F32 = jnp.float32
D, D_FF = 32, 48


def _stacks(key, groups, dtype):
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": jax.random.normal(kg, (groups, D, D_FF), F32).astype(dtype),
            "w_up": jax.random.normal(ku, (groups, D, D_FF), F32).astype(dtype),
            "w_down": jax.random.normal(kd, (groups, D_FF, D), F32).astype(dtype)
            * 0.2}


def _routed(key, tokens, k, n_experts):
    """Each token's k distinct experts, some experts liked better."""
    kp, kn = jax.random.split(key)
    liking = 0.8 * jax.random.normal(kp, (n_experts,))
    noise = jax.random.gumbel(kn, (tokens, n_experts))
    return jax.lax.top_k(liking + noise, k)[1].astype(jnp.int32)


def _of_sizes(sizes, key):
    """One expert a token, so that group g gets ``sizes[g]`` tokens."""
    ids = np.repeat(np.arange(len(sizes)), sizes).astype(np.int32)
    return jax.random.permutation(key, jnp.asarray(ids))[:, None]


def _dense(x, experts, stacks, layer_idx, n_experts, valid, held):
    """Float32, every expert over every token, the chosen ones kept."""
    first, count = held if held is not None else (0, n_experts)
    at = layer_idx * count
    w = {k: v[at:at + count].astype(F32) for k, v in stacks.items()}
    x = x.astype(F32)
    hidden = (jax.nn.silu(jnp.einsum("td,edf->tef", x, w["w_gate"]))
              * jnp.einsum("td,edf->tef", x, w["w_up"]))
    y = jnp.einsum("tef,efd->ted", hidden, w["w_down"])      # [T, count, d]
    local = experts - first
    there = (local >= 0) & (local < count)
    if valid is not None:
        there &= valid[:, None]
    y = jnp.take_along_axis(y, jnp.clip(local, 0, count - 1)[..., None], 1)
    return jnp.where(there[..., None], y, 0)


def _rows(pairs):
    """`ge.Pairs` -> [T, k, d] float32, the rows of no group zero (they
    hold whatever was there: only `gated_sum`'s select keeps them out)."""
    rows, there = pairs
    rows = rows.astype(F32)
    if there is not None:
        rows = jnp.where(there[:, :, None], rows, 0)
    return np.asarray(rows.transpose(1, 0, 2))


def _parents_layer(x, experts, gates, stacks, layer_idx, n_experts, valid,
                   held, interpret):
    """The expert layer as it stood before PR 62, kept here: pairs
    numbered token-major, the sorted rows zeroed where they are in no
    group, gathered back, re-laid to [T, k, d], widened, ``einsum``."""
    t, d = x.shape
    k, e = experts.shape[1], n_experts
    flat = experts.reshape(t * k)
    if held is not None:
        first, e = held
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    kernels = ge._takes_kernels(t * k, e, interpret)
    pad = -(t * k) % ge.ROW_TILE if kernels else 0
    if pad:
        flat = jnp.concatenate([flat, jnp.full((pad,), e, flat.dtype)])
    order = jnp.argsort(flat, stable=True)
    load = jnp.sum(flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :],
                   axis=0, dtype=jnp.int32)
    xs = jnp.take(x, order // k, axis=0, **({"mode": "clip"} if pad else {}))
    if kernels:
        ys = ge._kernel_products(
            xs, *(stacks[m] for m in ge.EXPERT_STACKS), load, layer_idx * e,
            interpret=bool(interpret))
    else:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((stacks["w_gate"].shape[0],), jnp.int32), load,
            (layer_idx * e,))
        hidden = (jax.nn.silu(jax.lax.ragged_dot(xs, stacks["w_gate"], sizes))
                  * jax.lax.ragged_dot(xs, stacks["w_up"], sizes))
        ys = jax.lax.ragged_dot(hidden, stacks["w_down"], sizes)
    if valid is not None or held is not None:
        ys = jnp.where((jnp.take(flat, order) < e)[:, None], ys, 0)
    back = jnp.argsort(order)
    if pad:
        back = back[:t * k]
    y = jnp.take(ys, back, axis=0).reshape(t, k, d)
    return jnp.einsum("tkd,tk->td", y.astype(F32), gates), load


def _case(name):
    """-> (experts [T, k], n_experts, layers, layer_idx, valid, held)."""
    key = jax.random.PRNGKey(sum(map(ord, name)))
    none = (1, 0, None, None)
    if name == "glm_4_of_64":
        return (_routed(key, 1024, 4, 64), 64) + none
    if name == "zaya_1_of_16":
        return (_routed(key, 1024, 1, 16), 16) + none
    if name == "dots3_held_8_of_64":        # 7 rows of 8 in no group
        return _routed(key, 128, 8, 64), 64, 1, 0, None, (16, 8)
    if name == "granite_10_of_72_held_36":   # 320 pairs: padded to 384
        return _routed(key, 32, 10, 72), 72, 2, 1, None, (0, 36)
    if name == "granite_other_half_and_padding":
        return (_routed(key, 64, 10, 72), 72, 1, 0, jnp.arange(64) < 51,
                (36, 36))
    if name == "held_and_padding":
        return (_routed(key, 128, 8, 64), 64, 2, 1, jnp.arange(128) < 77,
                (48, 8))
    if name == "bucket_padding":
        return (_routed(key, 256, 4, 16), 16, 1, 0, jnp.arange(256) < 131,
                None)
    if name == "third_layer_of_a_stack":
        return _routed(key, 256, 4, 16), 16, 4, 2, None, None
    sizes = {"empty_group_in_the_middle": [70, 61, 0, 0, 130, 59, 97, 95],
             "one_group_at_4x_the_mean": [256, 30, 41, 37, 35, 39, 33, 41],
             "no_size_a_multiple_of_a_tile": [1, 127, 129, 3, 61, 67, 111, 13],
             "first_group_empty": [0, 0, 200, 1, 100, 11, 100, 100],
             "one_group_has_them_all": [0, 0, 0, 512, 0, 0, 0, 0]}[name]
    assert sum(sizes) == 512
    return (_of_sizes(sizes, key), 8, 2, 1, None, None)


CASES = ["glm_4_of_64", "zaya_1_of_16", "dots3_held_8_of_64",
         "granite_10_of_72_held_36", "granite_other_half_and_padding",
         "held_and_padding", "bucket_padding", "third_layer_of_a_stack",
         "empty_group_in_the_middle", "one_group_at_4x_the_mean",
         "no_size_a_multiple_of_a_tile", "first_group_empty",
         "one_group_has_them_all"]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("name", CASES)
def test_kernels_give_what_ragged_dot_gives(name, dtype):
    experts, n_experts, layers, layer_idx, valid, held = _case(name)
    held_count = n_experts if held is None else held[1]
    key = jax.random.PRNGKey(7)
    stacks = _stacks(key, layers * held_count, dtype)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (experts.shape[0], D), F32).astype(dtype)
    run = lambda interpret: jax.jit(functools.partial(
        ge.grouped_swiglu, n_experts=n_experts, valid=valid, held=held,
        interpret=interpret))(x, experts, stacks, jnp.int32(layer_idx))
    assert ge._takes_kernels(experts.size, held_count, True)
    (y, load), (y_ragged, load_ragged) = run(True), run(None)
    np.testing.assert_array_equal(load, load_ragged)
    assert int(load.sum()) <= experts.size
    assert y.rows.dtype == dtype and y.rows.shape == experts.shape[::-1] + (D,)
    assert (y.there is None) == (valid is None and held is None)
    y, y_ragged = _rows(y), _rows(y_ragged)
    # The same products rounded at the same places: what differs is the
    # order of a float32 accumulation, a unit or two of the last place
    # of the dtype after the rounding (of the output's own size, and of
    # the largest, which cancellation in the down product can leave).
    ulp = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(y, y_ragged, rtol=4 * ulp,
                               atol=4 * ulp * np.abs(y_ragged).max())
    dense = np.asarray(_dense(x, experts, stacks, layer_idx, n_experts,
                              valid, held))
    scale = np.abs(dense).max()
    rounding = 0.03 if dtype == jnp.bfloat16 else 1e-5
    assert np.abs(y - dense).max() <= rounding * scale
    assert ((dense == 0) <= (y == 0)).all()      # no-group rows: not there


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("name", CASES)
def test_the_layer_is_the_parents_sort_zero_relay_and_einsum(name, dtype):
    """Pairs laid k-major, both gathers in range, the rows of no group
    selected out where they are summed: what the token-major sort, the
    zeroing pass, ``reshape(t, k, d)`` and the families' ``einsum`` gave
    (`_parents_layer`), through the interpreted kernels (which pad 320
    and 640 rows to whole tiles) and through ``ragged_dot``."""
    experts, n_experts, layers, layer_idx, valid, held = _case(name)
    held_count = n_experts if held is None else held[1]
    key = jax.random.PRNGKey(11)
    stacks = _stacks(key, layers * held_count, dtype)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (experts.shape[0], D), F32).astype(dtype)
    gates = jax.nn.softmax(jax.random.normal(
        jax.random.fold_in(key, 2), experts.shape, F32), axis=-1)

    def layer(x, experts, gates, stacks, i, interpret):
        pairs, load = ge.grouped_swiglu(x, experts, stacks, i, n_experts,
                                        valid, held, interpret)
        return ge.gated_sum(pairs, gates), load

    for interpret in (True, None):
        args = (x, experts, gates, stacks, jnp.int32(layer_idx))
        y, load = jax.jit(functools.partial(layer, interpret=interpret))(*args)
        want, load_want = jax.jit(functools.partial(
            _parents_layer, n_experts=n_experts, valid=valid, held=held,
            interpret=interpret))(*args)
        np.testing.assert_array_equal(load, load_want)
        assert y.dtype == F32 and y.shape == x.shape
        # Each row's products are its own whatever its neighbours; what
        # may differ is the order of a float32 sum of k gated rows.
        y, want = np.asarray(y), np.asarray(want)
        np.testing.assert_allclose(y, want, rtol=2e-6,
                                   atol=2e-6 * np.abs(want).max())
        if valid is not None:
            assert not y[~np.asarray(valid)].any()


@pytest.mark.parametrize("k", [1, 4, 10])
def test_rows_of_no_group_may_hold_nan_and_the_sum_has_none(k):
    """The kernels visit no tile of absent or padding pairs and the
    zeroing pass is gone: `gated_sum` is handed rows the test fills
    with NaN and infinity wherever ``there`` is false."""
    t, key = 37, jax.random.PRNGKey(k)
    rows = jax.random.normal(key, (k, t, D), F32).astype(jnp.bfloat16)
    there = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (k, t))
    there = there.at[:, 0].set(False).at[0, 1].set(True)
    gates = jax.nn.softmax(jax.random.normal(
        jax.random.fold_in(key, 2), (t, k), F32), axis=-1)
    junk = jnp.where(jnp.arange(D) % 2 == 0, jnp.nan, jnp.inf).astype(
        rows.dtype)
    dirty = jnp.where(there[:, :, None], rows, junk)
    got = np.asarray(jax.jit(ge.gated_sum)(ge.Pairs(dirty, there), gates))
    assert np.isfinite(got).all() and not got[0].any() and got[1].any()
    clean = jnp.where(there[:, :, None], rows, 0)
    np.testing.assert_array_equal(
        got, jax.jit(ge.gated_sum)(ge.Pairs(clean, None), gates))


def _jaxpr_of(tokens, k, n_experts, d, d_ff, layers, held=None,
              interpret=True):
    count = n_experts if held is None else held[1]
    sds = jax.ShapeDtypeStruct
    stacks = {"w_gate": sds((layers * count, d, d_ff), jnp.bfloat16),
              "w_up": sds((layers * count, d, d_ff), jnp.bfloat16),
              "w_down": sds((layers * count, d_ff, d), jnp.bfloat16)}
    return str(jax.make_jaxpr(functools.partial(
        ge.grouped_swiglu, n_experts=n_experts, held=held,
        interpret=interpret))(
            sds((tokens, d), jnp.bfloat16), sds((tokens, k), jnp.int32),
            stacks, sds((), jnp.int32)))


GLM = dict(k=4, n_experts=64, d=2048, d_ff=1536, layers=6)
ZAYA = dict(k=1, n_experts=16, d=2048, d_ff=2048, layers=16)
DOTS3 = dict(k=8, n_experts=256, d=5120, d_ff=1536, layers=4, held=(64, 32))
KIMI = dict(k=8, n_experts=256, d=2304, d_ff=1024, layers=26, held=(0, 16))
XING4 = dict(k=4, n_experts=64, d=3584, d_ff=1024, layers=38, held=(0, 8))
GRANITE = dict(k=10, n_experts=72, d=4096, d_ff=768, layers=10, held=(0, 36))


@pytest.mark.parametrize("shape, kernels", [
    (dict(tokens=32, **GLM), False),            # the cells' decode steps
    (dict(tokens=64, **ZAYA), False),
    (dict(tokens=16, **DOTS3), False),
    (dict(tokens=64, **KIMI), True),            # 32 pairs a held group
    (dict(tokens=32, **XING4), True),           # 16
    (dict(tokens=32, **GRANITE), True),         # 8.9: 320 rows, padded
    (dict(tokens=64, **GRANITE), True),         # the same step at 64 slots
    (dict(tokens=3, **KIMI), False),            # the checks' own few slots
    (dict(tokens=3, **GRANITE), False),
    (dict(tokens=128, **KIMI), True),           # their smallest buckets
    (dict(tokens=512, **XING4), True),
    (dict(tokens=512, **GRANITE), True),
    (dict(tokens=128, **ZAYA), True),           # zaya's prefill buckets:
    (dict(tokens=256, **ZAYA), True),           # 8 to 32 rows a group
    (dict(tokens=512, **ZAYA), True),
    (dict(tokens=1024, **GLM), True),           # GLM's three buckets
    (dict(tokens=2048, **GLM), True),
    (dict(tokens=4096, **GLM), True),
    (dict(tokens=512, **DOTS3), True),          # dots3's buckets and chunk
    (dict(tokens=2048, **DOTS3), True),
    (dict(tokens=96, **ZAYA), False),           # under 8 rows a group
    (dict(tokens=1000, **GLM), True),           # padded to whole tiles
    (dict(tokens=2048, interpret=None, **GLM), False),      # off the chip
], ids=lambda v: "-".join(f"{k}{v[k]}" for k in ("tokens", "n_experts", "k"))
    if isinstance(v, dict) else str(v))
def test_the_rule_reads_static_shapes(shape, kernels):
    text = _jaxpr_of(**shape)
    assert ("pallas_call" in text) == kernels
    assert (ge.SWIGLU in text and ge.MATMUL in text) == kernels
    assert ("ragged_dot" in text) != kernels


def test_visits_walk_each_touched_group_once_and_stop_at_the_total():
    load = jnp.asarray([0, 130, 0, 126, 1, 0], jnp.int32)     # 257 rows
    table, counts = map(np.asarray, ge._visits(load, 12, rows=512))
    assert counts.tolist() == [4, 3] and table.shape == (7, 512 // 128 + 6 - 1)
    walk = table[:, :4]
    np.testing.assert_array_equal(walk[ge.GROUP], [13, 13, 15, 16])
    np.testing.assert_array_equal(walk[ge.TILE], [0, 1, 1, 2])
    np.testing.assert_array_equal(walk[ge.START], [0, 0, 130, 256])
    np.testing.assert_array_equal(walk[ge.END], [130, 130, 256, 257])
    np.testing.assert_array_equal(walk[ge.LEAD], [1, 0, 1, 1])
    np.testing.assert_array_equal(walk[ge.ORDINAL], [0, 0, 1, 2])
    np.testing.assert_array_equal(walk[ge.AFTER], [15, 15, 16, 13])
    # Past the count every entry repeats the last visit: no block moves,
    # no tile past the groups' total (rows 257..511) is ever named.
    assert (table[:, 4:] == table[:, 3:4]).all()
    table, counts = map(np.asarray, ge._visits(
        jnp.zeros(6, jnp.int32), 12, rows=512))
    assert counts.tolist() == [0, 0] and table[ge.TILE].max() == 0
    assert 12 <= table[ge.GROUP].min() and table[ge.GROUP].max() < 18


def test_columns_are_tiled_where_two_buffers_a_matrix_do_not_fit(monkeypatch):
    """The cells' matrices fit whole; wider ones are walked a column
    tile at a time, each tile's copies following the last tile's."""
    assert ge._column_tile(2048, 1536, 2, 2) == 1536        # GLM, gate and up
    assert ge._column_tile(5120, 1536, 2, 2) == 1536        # dots3
    assert ge._column_tile(1536, 5120, 1, 2) == 5120        # dots3, down
    assert ge._column_tile(8192, 4096, 2, 2) == 1024
    monkeypatch.setattr(ge, "WEIGHT_BUFFER_BYTES", 2 * 2 * D * 128 * 4)
    d_ff = 384
    assert ge._column_tile(D, d_ff, 2, 4) == 128
    key = jax.random.PRNGKey(3)
    stacks = {"w_gate": jax.random.normal(key, (16, D, d_ff), F32),
              "w_up": jax.random.normal(jax.random.fold_in(key, 1),
                                        (16, D, d_ff), F32),
              "w_down": jax.random.normal(jax.random.fold_in(key, 2),
                                          (16, d_ff, D), F32) * 0.1}
    x = jax.random.normal(jax.random.fold_in(key, 3), (512, D), F32)
    experts = _of_sizes([0, 200, 1, 0, 100, 11, 100, 100], key)
    run = lambda interpret: jax.jit(functools.partial(
        ge.grouped_swiglu, n_experts=8, interpret=interpret))(
            x, experts, stacks, jnp.int32(1))
    (y, load), (y_ragged, load_ragged) = run(True), run(None)
    np.testing.assert_array_equal(load, load_ragged)
    y, y_ragged = _rows(y), _rows(y_ragged)
    np.testing.assert_allclose(y, y_ragged, rtol=1e-5,
                               atol=1e-5 * np.abs(y_ragged).max())
