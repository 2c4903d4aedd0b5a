"""``drivers/serve_sparse_hybrid.py``'s comparison with the reference
can fail, and passes the sound program: the eleven controls of
``benchmark/degraded_sala.py`` and three seeds, at the configuration
file's rehearsal sizes on the CPU, each in the process of the test
through the driver's own `bring_up` (the engine's slots dirtied first,
three prompts asked together and prefilled in chunks, each replayed in
its own slot of the check's cache, which starts full of ones, the
slots' decode steps staggered under the live mask)."""

import re
import time

import pytest

from benchmark import degraded_sala
from benchmark.drivers import common, serve_sparse_hybrid
from benchmark.harness import context, manifest

CELL = "minicpmsala.longdoc.flood"


def _bring_up(control, seed):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    ctx.builder = degraded_sala.degraded(ctx.builder, control)
    try:
        _, engine, _, checks = m.driver(ctx.config["driver"]).bring_up(ctx)
    except common.Incorrect as refused:
        return refused
    engine.close()
    return checks


@pytest.mark.parametrize("seed", range(3))
def test_the_sound_program_passes(seed):
    checks = _bring_up("none", seed)
    assert isinstance(checks, dict), checks
    # float32 at the rehearsal's sizes: what is left is the order of
    # sums, and no selection falls the other way.
    assert checks["prefill_rel_l2"] < 1e-4
    assert checks["step_rel_l2_max"] < 1e-4
    assert checks["select_blocks_differ_share"] == 0.0
    assert checks["replay_agree"] == 1.0
    assert checks["state_rel_l2"] < 1e-5 and checks["state_f32_share"] > 0.99
    assert checks["reuse_rel_l2_max"] < 1e-4
    assert checks["reuse_state_rel_l2"] < 1e-5
    # Every prompt was past dense_len and took more than one chunk, and
    # every token the engine streamed was the reference's own.
    assert len(checks["prompt_tokens"]) == 3
    assert min(checks["prompt_tokens"]) > 96
    assert checks["engine_tokens"] == 3 * 48 + 4
    assert checks["token_margin_max"] < 1e-4


# control -> the limit that refuses it at the rehearsal's sizes (the
# chip's readings at the cell's are in PERF.md), and whether the
# replayed logits, the system's own blocks followed, stay sound.
@pytest.mark.parametrize("control, says, sound_logits", [
    ("dense", "selected block lies 1.0000", False),
    ("no_forced", "selected block lies 1.0000", True),
    ("one_head", "selected block lies", True),
    ("kc_incomplete", "selected block lies", True),
    ("state_bf16", "rounded on the way|state is off", False),
    ("stale_state", "selected block lies|logits off", False),
    ("wrong_decay", "selected block lies|logits off", False),
    ("sparse_rope", "selected block lies|logits off", False),
    ("pad_steps_state", "selected block lies|logits off", False),
    ("ignores_live", "selected block lies|logits off", False),
    ("first_slot_blocks", "selected block lies 1.0000", True),
])
def test_a_control_is_refused(control, says, sound_logits, seed=2):
    # Seed 2's prompts end in chunks with padding to step.
    refused = _bring_up(control, seed)
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(says, str(refused)), refused
    r = refused.readings
    worst = max(r["prefill_rel_l2"], r["step_rel_l2_max"])
    assert (worst < 1e-4) == sound_logits, r
    if control == "stale_state":
        # By the end of the long prompt the stale state has all but
        # decayed away; the short request that takes the slot next
        # scans on from a whole prompt's state.
        assert r["state_rel_l2"] < 0.1 < r["reuse_rel_l2_max"]
        assert r["reuse_state_rel_l2"] > 1.0
    if control in ("no_forced", "one_head", "kc_incomplete",
                   "first_slot_blocks"):
        # Another selection, rightly attended over: only the
        # selection's own limits can refuse it.
        assert r["select_blocks_differ_share"] > 0.05
        assert r["select_excess_max"] > serve_sparse_hybrid.TOL_SELECT_EXCESS
    if control == "state_bf16":
        assert r["state_f32_share"] == 0.0
        assert r["select_blocks_differ_share"] < 1e-3
    if control in ("wrong_decay", "pad_steps_state"):
        assert r["state_rel_l2"] > serve_sparse_hybrid.TOL_STATE_REL_L2


def test_the_degraded_builder_leaves_the_familys_module_as_it_was():
    m = manifest.load()
    config = m.config(m.cell(CELL))
    config = {**config, **config["rehearse"]}
    sound = m.builder(config)
    cfg = sound.config(config)
    stale = degraded_sala.degraded(sound, "stale_state").config(config)
    assert stale.model is not cfg.model
    assert bool(cfg.model._starts_fresh(0)) and not bool(
        stale.model._starts_fresh(0))
    dense = degraded_sala.degraded(sound, "dense").config(config)
    assert dense.selection.dense_len == config["max_position_embeddings"]
    assert cfg.selection.dense_len == config["sparse_config"]["dense_len"]
    assert degraded_sala.degraded(sound, "none").reference is sound.reference
