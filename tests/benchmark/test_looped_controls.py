"""``drivers/serve_looped.py``'s comparison with the reference can fail,
and passes the sound program: the two controls of
``benchmark/degraded_looped.py`` that only THIS family's limits refuse
(the stream handed on in bf16, which no logit shows; the final norm
left out between passes, which the later passes' gates show) and one
seed, at the configuration file's rehearsal sizes on the CPU, each in
the process of the test through the driver's own `bring_up`. This
directory's tests run three times over (the manifest's tests run the
suite on copies): the other controls are
``tests/test_ouro_controls.py``, which runs once and shares `bring_up`,
`REFUSED` and `assert_refused` with this file."""

import re
import time

import pytest

from benchmark import degraded_looped
from benchmark.drivers import common, serve_looped as driver
from benchmark.harness import context, manifest

CELL = "ouro26b.math.flood"


def bring_up(control, seed):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    assert ctx.config["driver"] == "serve_looped"
    ctx.builder = degraded_looped.degraded(ctx.builder, control)
    try:
        _, engine, _, checks = m.driver(ctx.config["driver"]).bring_up(ctx)
    except common.Incorrect as refused:
        return refused
    engine.close()
    return checks


def test_the_sound_program_passes(seed=0):
    checks = bring_up("none", seed)
    assert isinstance(checks, dict), checks
    # float32 at the rehearsal's sizes: what is left is the order of sums.
    assert max(checks["prefill_rel_l2_max"], checks["step_rel_l2_max"]) < 1e-4
    assert checks["token_margin_max"] == 0.0 == 1.0 - checks["replay_agree"]
    assert checks["gates_abs_max"] < 1e-5
    assert checks["stream_handed_rel_max"] < 1e-6


LOGITS, GATES, HANDED = ("logits off the reference", "the exit gate is off",
                         "a block application did not hand on")
# control -> a limit that refuses it at these sizes: its message.
REFUSED = {
    # Sound at a prompt's end (a fresh prefill attends to its own rows):
    # the step reads one pass's rows where four are stated.
    "shared_cache": LOGITS,
    "no_norm_between": GATES,
    "no_branch_norms": LOGITS,
    "three_passes": GATES,
    # 2^-9 of every value handed on, and nothing else: 12 float32 block
    # applications carry it into the logits as 5e-3.
    "stream_bf16": HANDED,
    # NOT refused here: 12 float32 block applications of rounded
    # matrices stay under the limits that 192 of bf16 products need (on
    # the chip 0.132 where the limit is 0.06); its readings stand well
    # over the sound program's all the same.
    "int8": None,
}


def test_every_control_is_listed():
    assert set(REFUSED) | {"none"} == set(degraded_looped.CONTROLS)


HERE = ("stream_bf16", "no_norm_between")


def assert_refused(control, refused):
    if REFUSED[control] is None:
        assert isinstance(refused, dict), refused
        assert refused["prefill_rel_l2_max"] > 1e-3
        assert refused["gates_abs_max"] > 1e-3
        return
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(REFUSED[control], str(refused)), refused
    readings = refused.readings
    if control == "stream_bf16":
        assert refused.limits == ["TOL_STREAM_HANDED"]
        assert readings["stream_handed_rel_max"] > 30 * driver.TOL_STREAM_HANDED
    else:
        assert readings["stream_handed_rel_max"] < driver.TOL_STREAM_HANDED
    if control == "shared_cache":
        assert readings["prefill_rel_l2_max"] < 1e-4 < 0.1 < readings[
            "step_rel_l2_max"]
    if control == "no_norm_between":
        # The first pass is the sound one's; the later ones' gates show
        # what they were handed.
        first, *later = readings["gates_abs_max_by_pass"]
        assert first < 1e-5 and max(later) > driver.TOL_GATES_ABS
    if control == "three_passes":
        assert "3 passes read where the reference runs 4" in str(refused)


@pytest.mark.parametrize("control", HERE)
def test_a_control_is_refused(control, seed=1):
    assert_refused(control, bring_up(control, seed))


def test_the_degraded_builder_keeps_the_reference_and_the_module():
    """The control changes what the ENGINE runs, never what the
    reference reads, and leaves the family's own module as it was."""
    m = manifest.load()
    config = m.config(m.cell(CELL))
    config = {**config, **config["rehearse"]}
    sound = m.builder(config)
    cfg = sound.config(config)
    once = degraded_looped.degraded(sound, "three_passes")
    tokens, rows = [[5, 9, 200, 17, 3, 250]], [(0, 5)]
    made = sound.init_params(cfg, 0)
    assert (once.reference.logits_at(once.init_params(cfg, 0), tokens, rows,
                                     config)
            == sound.reference.logits_at(made, tokens, rows, config)).all()
    assert once.config(config).n_loops == 3 and cfg.n_loops == 4
    shared = degraded_looped.degraded(sound, "shared_cache").config(config)
    assert shared.model is not cfg.model
    assert [shared.model._entry(u, 1, cfg) for u in range(4)] == [10] * 4
    assert [cfg.model._entry(u, 1, cfg) for u in range(4)] == [1, 4, 7, 10]
