"""``drivers/serve_routed_mhc.py``'s comparison with the reference can
fail, and passes the sound program: the four controls of
``benchmark/degraded_mhc.py`` that only THIS family's fifth and seventh
limits can refuse or that are its mechanism's own (the maps' product on
bf16 operands, Sinkhorn passes rounded to bf16 and streams handed on in
bf16, which no logit shows at this depth; Sinkhorn cut to one pass) and
one seed, at the
configuration file's rehearsal sizes on the CPU, each in the process of
the test through the driver's own `bring_up`. This directory's tests
run three times over (the manifest's tests run the suite on copies):
the other controls and more seeds are ``tests/test_xing_mhc_controls.py``,
which runs once and shares `bring_up` and `REFUSED` with this file."""

import re
import time

import pytest

from benchmark import degraded_mhc
from benchmark.drivers import common, serve_routed_mhc as driver
from benchmark.harness import context, manifest

CELL = "xing4.rag.flood"


def bring_up(control, seed):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    assert ctx.config["driver"] == "serve_routed_mhc"
    ctx.builder = degraded_mhc.degraded(ctx.builder, control)
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
    assert checks["route_excess_max"] < 1e-3
    assert checks["token_margin_strict_share"] == 1.0
    # The first sub-layer's maps against the reference's, and what the
    # final norm read against the sum of the last layer's streams.
    assert checks["maps_abs_max"] < 1e-5
    assert checks["streams_end_rel_max"] < 1e-6
    # Every sub-layer's write-back against this file's own float64 one.
    assert checks["streams_handed_rel_max"] < 1e-6


def test_the_sound_program_passes(seed=0):
    assert_sound(bring_up("none", seed))


# control -> (a limit that refuses it at these sizes: its message;
# whether the first sub-layer's maps are off too).
ROUTE, LOGITS, MAPS, END, HANDED = (
    "a chosen expert lies", "logits off the reference",
    "the first sub-layer's maps", "the final norm did not read the sum",
    "a sub-layer did not hand on")
REFUSED = {
    # 2^-8 of a map of order one, and nothing else: four layers of it
    # move a row of float32 logits by a tenth of the limit.
    "maps_bf16": (MAPS, True),
    "sinkhorn_bf16": (MAPS, True),
    # 2^-9 of every value handed on, which ten sub-layers of float32
    # carry into the logits as 3e-3 to 1e-2 (and 80 of bf16 products
    # hide): read where this file can compute it exactly.
    "streams_bf16": (HANDED, False),
    # Wrong mathematics in the maps shows in them and moves the stream
    # every router reads (a refusal names every limit that did).
    "sinkhorn_one_pass": (MAPS, True),
    "post_without_2": (MAPS, True),
    "res_identity": (MAPS, True),
    "maps_without_norm": (MAPS, True),
    # Sound maps, wrong elsewhere.
    # Stream 0 is a quarter of the sum and then some: read where it is
    # exact (at 40 layers on the chip no logit's limit refuses it).
    "sum_stream0": (END, False),
    "no_yarn": (ROUTE, False),
    "no_mscale": (ROUTE, False),
    "held_shifted": (ROUTE, False),
    # The rounded embedding moves the first sub-layer's maps; four
    # float32 layers of rounded matrices stay under the limits that 40
    # layers of bf16 products need (on the chip every limit but the
    # tokens' refuses it).
    "int8": (MAPS, True),
}
HERE = ("maps_bf16", "sinkhorn_bf16", "streams_bf16", "sinkhorn_one_pass")


def test_every_control_is_listed():
    assert set(REFUSED) | {"none"} == set(degraded_mhc.CONTROLS)


def assert_refused(control, refused):
    says, maps_off = REFUSED[control]
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(says, str(refused)), refused
    readings = refused.readings
    if control in ("maps_bf16", "sinkhorn_bf16"):
        assert refused.limits == ["TOL_MAPS_ABS"]
        assert readings["maps_abs_max"] > 3 * driver.TOL_MAPS_ABS
        # ... and by nothing else: the logits and the routing hold.
        assert max(readings["prefill_rel_l2_max"],
                   readings["step_rel_l2_max"]) < driver.TOL_LOGITS_REL_L2
        assert readings["route_excess_max"] < driver.TOL_ROUTE_EXCESS
    elif says == HANDED:
        assert refused.limits == ["TOL_STREAMS_HANDED"]
        assert readings["streams_handed_rel_max"] > 30 * driver.TOL_STREAMS_HANDED
        assert max(readings["prefill_rel_l2_max"],
                   readings["step_rel_l2_max"]) < driver.TOL_LOGITS_REL_L2
        assert readings["streams_end_rel_max"] < driver.TOL_STREAMS_END
    elif says == END:
        assert readings["streams_end_rel_max"] > 0.5
        assert readings["route_excess_max"] < driver.TOL_ROUTE_EXCESS
    elif says != MAPS:
        assert "TOL_LOGITS_REL_L2" in refused.limits
        assert readings["streams_end_rel_max"] < driver.TOL_STREAMS_END
    if says != HANDED:
        assert readings["streams_handed_rel_max"] < driver.TOL_STREAMS_HANDED
    assert (readings["maps_abs_max"] > driver.TOL_MAPS_ABS) == maps_off


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
    once = degraded_mhc.degraded(sound, "sinkhorn_one_pass")
    tokens, rows = [[5, 9, 200, 17, 3, 250]], [(0, 5)]
    made = sound.init_params(cfg, 0)
    assert (once.reference.logits_at(once.init_params(cfg, 0), tokens, rows,
                                     config)
            == sound.reference.logits_at(made, tokens, rows, config)).all()
    assert once.config(config).mhc.sinkhorn_iters == 1
    assert cfg.mhc.sinkhorn_iters == config["hc_sinkhorn_iters"] == 20
    shifted = degraded_mhc.degraded(sound, "held_shifted").config(config)
    assert shifted.held_experts == (1, 4) and cfg.held_experts == (0, 4)
    plain = degraded_mhc.degraded(sound, "no_mscale").config(config)
    assert plain.attn_scale == plain.qk_head_dim ** -0.5 < cfg.attn_scale
    first = degraded_mhc.degraded(sound, "sum_stream0").config(config)
    assert first.model is not cfg.model
    import jax.numpy as jnp
    apart = jnp.arange(24.0).reshape(1, 2, 4, 3)
    streams = apart.reshape(1, 2, 12)
    assert (cfg.model._streams_out(streams, 4) == apart.sum(2)).all()
    assert (first.model._streams_out(streams, 4) == apart[:, :, 0]).all()
