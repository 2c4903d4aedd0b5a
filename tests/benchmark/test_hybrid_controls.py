"""``drivers/serve_hybrid.py``'s comparison with the reference can fail,
and passes the sound program: the six controls of
``benchmark/degraded_hybrid.py`` and half a dozen seeds, at the
configuration file's rehearsal sizes on the CPU, each in the process of
the test through the driver's own `bring_up` (the engine's slots
dirtied first, the check's own cache full of ones)."""

import re
import time

import pytest

from benchmark import degraded_hybrid
from benchmark.drivers import common
from benchmark.harness import context, manifest

CELL = "olmohybrid.rag.flood"


def _bring_up(control, seed):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    ctx.builder = degraded_hybrid.degraded(ctx.builder, control)
    try:
        _, engine, _, checks = m.driver(ctx.config["driver"]).bring_up(ctx)
    except common.Incorrect as refused:
        return refused
    engine.close()
    return checks


@pytest.mark.parametrize("seed", range(6))
def test_the_sound_program_passes(seed):
    checks = _bring_up("none", seed)
    assert isinstance(checks, dict), checks
    # float32 at the rehearsal's sizes: what is left is the order of sums.
    assert checks["prefill_rel_l2_max"] < 1e-3
    assert checks["step_rel_l2_max"] < 1e-3
    assert checks["replay_agree"] == 1.0
    # The first layer's state against the reference's recurrence.
    assert checks["state_rel_l2_max"] < 1e-5


@pytest.mark.parametrize("control, says, sound_prefill", [
    # The tick's prefill hands the float32 state on rounded once; the
    # 16 steps round it 16 times more.
    ("state_bf16", "logits off the reference", True),
    ("no_decay", "logits off the reference", False),
    ("beta_unscaled", "logits off the reference", False),
    ("no_conv", "logits off the reference", False),
    ("stale_state", "logits off the reference", False),
    # A prompt's last row is read before its bucket's padding is
    # scanned: only what follows sees the stepped state.
    ("pad_steps_state", "logits off the reference", True),
])
def test_a_control_is_refused(control, says, sound_prefill, seed=1):
    refused = _bring_up(control, seed)
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(says, str(refused)), refused
    readings = refused.readings
    assert readings["step_rel_l2_max"] > 0.05
    # Every control but the rounded state leaves the first layer's
    # state far off; the rounded state by 2^-9 a step, which the state
    # limit refuses on its own where bf16 logits cannot tell (the chip).
    from benchmark.drivers import serve_hybrid

    assert readings["state_rel_l2_max"] > serve_hybrid.TOL_STATE_REL_L2 * (
        3 if control == "state_bf16" else 100)
    assert (readings["prefill_rel_l2_max"] < 1e-3) == sound_prefill
    if control == "stale_state":
        # The engine's tokens came through slots that served another
        # request first: they are not the replayed programs' either
        # (whose cache started full of ones, not of that request).
        assert readings["replay_agree"] < 0.5
        assert readings["token_margin_max"] > 0.05


def test_the_degraded_builder_keeps_the_reference_on_the_weights_as_made():
    """The control changes what the ENGINE is given, never what the
    reference reads."""
    m = manifest.load()
    config = m.config(m.cell(CELL))
    config = {**config, **config["rehearse"]}
    sound = m.builder(config)
    wrong = degraded_hybrid.degraded(sound, "no_decay")
    cfg = wrong.config(config)
    served = wrong.init_params(cfg, 0)
    made = sound.init_params(cfg, 0)
    assert (served["linear"]["a_log"] < -1e29).all()
    assert (made["linear"]["a_log"] > -20).all()
    tokens, rows = [[5, 9, 200, 17, 3, 250]], [(0, 5)]
    a = wrong.reference.logits_at(served, tokens, rows, config)
    b = sound.reference.logits_at(made, tokens, rows, config)
    assert (a == b).all()
    # A control on the module leaves the family's own module as it was.
    stale = degraded_hybrid.degraded(sound, "stale_state").config(config)
    assert stale.model is not cfg.model
    assert bool(cfg.model._starts_fresh(0)) and not bool(
        stale.model._starts_fresh(0))
