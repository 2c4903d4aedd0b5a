"""``drivers/serve_hybrid.py``'s comparison with the reference can fail
for the Mamba-2 hybrid family too, and passes the sound program: the
three controls of ``benchmark/degraded_mamba2.py`` that are about the
STATE and one seed, at the configuration file's rehearsal sizes on the
CPU, each in the process of the test through the driver's own
`bring_up` (the engine's slots dirtied first, the check's own cache
full of ones). This directory's tests run three times over (the
manifest's tests run the suite on copies): the other eight controls and
more seeds are ``tests/test_granite_hybrid_controls.py``, which runs
once and shares `bring_up` and `REFUSED` with this file."""

import re
import time

import pytest

from benchmark import degraded_mamba2
from benchmark.drivers import common, serve_hybrid
from benchmark.harness import context, manifest

CELL = "granite4hmicro.reason.flood"


def bring_up(control, seed):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    assert ctx.config["driver"] == "serve_hybrid"
    ctx.builder = degraded_mamba2.degraded(ctx.builder, control)
    try:
        _, engine, _, checks = m.driver(ctx.config["driver"]).bring_up(ctx)
    except common.Incorrect as refused:
        return refused
    engine.close()
    return checks


def assert_sound(checks):
    assert isinstance(checks, dict), checks
    # float32 at the rehearsal's sizes: what is left is the order of sums.
    assert checks["prefill_rel_l2_max"] < 1e-3
    assert checks["step_rel_l2_max"] < 1e-3
    assert checks["replay_agree"] == 1.0
    # The first layer's state against the reference's recurrence.
    assert checks["state_rel_l2_max"] < 1e-5


def test_the_sound_program_passes(seed=0):
    assert_sound(bring_up("none", seed))


# control -> (the limit that refuses it at these sizes, whether the
# first layer's state is off too).
REFUSED = {
    # The tick's prefill hands the float32 state on rounded once; the
    # 16 steps round it 16 times more.
    "state_bf16": ("the first layer's state", True),
    "stale_state": ("logits off the reference", True),
    "pad_steps_state": ("logits off the reference", True),
    "tail_at_bucket_end": ("logits off the reference", True),
    "no_skip": ("logits off the reference", False),
    "no_conv_bias": ("logits off the reference", True),
    "gate_after_norm": ("logits off the reference", False),
    "no_residual_multiplier": ("logits off the reference", False),
    "no_logits_scaling": ("logits off the reference", False),
    # One attention layer of 8 at head size 16: its logits move by
    # 0.036, under the limit of 0.06. PERF.md has the chip's reading at
    # 4 of 40 layers and head size 64.
    "softmax_scale": (None, False),
    # In float32 the rounded weights move the logits by 0.011 and the
    # first layer's state, whose input projection is rounded, by 0.015.
    "int8": ("the first layer's state", True),
}
STATE_CONTROLS = ("state_bf16", "stale_state", "pad_steps_state")


def test_every_control_is_listed():
    assert set(REFUSED) | {"none"} == set(degraded_mamba2.CONTROLS)


def assert_refused(control, refused):
    says, state_off = REFUSED[control]
    if says is None:            # moves the logits, under the limit here
        assert 0.02 < refused["prefill_rel_l2_max"] < 0.06, refused
        return
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(says, str(refused)), refused
    readings = refused.readings
    worst = max(readings["prefill_rel_l2_max"], readings["step_rel_l2_max"])
    if "state" in says:
        assert readings["state_rel_l2_max"] > 3 * serve_hybrid.TOL_STATE_REL_L2
    else:
        assert worst > serve_hybrid.TOL_LOGITS_REL_L2
    assert (readings["state_rel_l2_max"] > 1e-4) == state_off, readings


@pytest.mark.parametrize("control", STATE_CONTROLS)
def test_a_control_is_refused(control, seed=1):
    assert_refused(control, bring_up(control, seed))


def test_the_degraded_builder_keeps_the_reference_on_the_weights_as_made():
    """The control changes what the ENGINE is given, never what the
    reference reads."""
    m = manifest.load()
    config = m.config(m.cell(CELL))
    config = {**config, **config["rehearse"]}
    sound = m.builder(config)
    wrong = degraded_mamba2.degraded(sound, "no_skip")
    cfg = wrong.config(config)
    served = wrong.init_params(cfg, 0)
    made = sound.init_params(cfg, 0)
    assert (served["mamba"]["d_skip"] == 0).all()
    assert (made["mamba"]["d_skip"] == 1).all()
    tokens, rows = [[5, 9, 200, 17, 3, 250]], [(0, 5)]
    a = wrong.reference.logits_at(served, tokens, rows, config)
    b = sound.reference.logits_at(made, tokens, rows, config)
    assert (a == b).all()
    # A control on the module leaves the family's own module as it was.
    stale = degraded_mamba2.degraded(sound, "stale_state").config(config)
    assert stale.model is not cfg.model
    assert bool(cfg.model._starts_fresh(0)) and not bool(
        stale.model._starts_fresh(0))
    scaled = degraded_mamba2.degraded(sound, "softmax_scale").config(config)
    assert scaled.attention_multiplier == scaled.head_dim ** -0.5
    assert cfg.attention_multiplier == 0.015625
