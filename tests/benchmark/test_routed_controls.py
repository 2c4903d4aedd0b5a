"""``drivers/serve_routed.py``'s comparison with the reference can fail,
and passes the sound program: the seven controls of
``benchmark/degraded_routed.py`` and a dozen seeds, at the configuration
file's rehearsal sizes on the CPU, in bf16 (the precision the cell
serves in), each in the process of the test through the driver's own
`bring_up`."""

import time

import pytest

from benchmark import degraded_routed
from benchmark.drivers import common
from benchmark.harness import context, manifest

CELL = "glm47flash.code.flood"


# The rehearsal's width of 64 is too narrow to test the CHIP's limits
# in bf16: a row's relative error is that of its last hidden state, and
# over a dozen seeds its worst row reads 0.019 to 0.051 at 64 columns
# and 0.021 to 0.028 at 512 (the chip, at 2048: PERF.md). So these
# tests widen the hidden state and leave every limit as it is.
WIDER = {"hidden_size": 512, "num_attention_heads": 8}


def _bring_up(control, seed, dtype="bfloat16"):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    ctx.config = dict(ctx.config, torch_dtype=dtype, **WIDER)
    ctx.builder = degraded_routed.degraded(ctx.builder, control)
    try:
        _, engine, _, checks = m.driver(ctx.config["driver"]).bring_up(ctx)
    except common.Incorrect as refused:
        return refused
    engine.close()
    return checks


@pytest.mark.parametrize("seed", range(12))
def test_the_sound_program_passes_in_bf16(seed):
    checks = _bring_up("none", seed)
    assert isinstance(checks, dict), checks
    # The experts it chose were the reference's but for near-ties.
    assert checks["route_choices_differ_share"] < 0.1
    # The replayed step is the function the engine's chunk scans: its
    # logits put the engine's tokens first (on the CPU all of them; the
    # chip's two compilations round differently, PERF.md).
    assert checks["replay_agree"] >= 0.9
    assert checks["token_margin_strict_share"] >= 0.9


@pytest.mark.parametrize("control, says", [
    ("int8", "logits off the reference|under the reference's boundary"),
    ("select_on_score", "under the reference's boundary"),
    ("no_scale", "logits off the reference|under the reference's boundary"),
    ("no_norm", "logits off the reference|under the reference's boundary"),
    # One program wrong, every other sound: the step, the tick's prefill.
    ("decode_int8", "logits off the reference|under the reference's "
                    "boundary|an engine token lies"),
    ("tick_row", "logits off the reference|under the reference's boundary"),
    ("chunk_token", "of the engine's tokens lie within"),
])
def test_a_control_is_refused(control, says, seed=1):
    # (seed 1: every control is refused on seeds 0 and 1 alike.)
    """Weights rounded to int8 move the logits (and with them the later
    layers' routing); a router that selects on the score alone chooses
    experts far under the reference's boundary; one without the 1.8 or
    without the normalisation weighs the experts' outputs wrongly; a
    decode step alone on int8 weights, or a tick prefill alone that
    reads the wrong row, is caught because the check reads those
    programs and not a stand-in; a chunk alone that is fed the wrong
    token passes everything but the engine's own tokens."""
    import re

    refused = _bring_up(control, seed)
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(says, str(refused)), refused
    if control == "chunk_token":
        # The control held to what it says: one wrong trace of the step
        # (the chunk's), one sound (the check's replay).
        assert degraded_routed._chunk_token.traces == 2
        assert refused.readings["step_rel_l2_max"] < 0.03


def test_the_degraded_builder_keeps_the_reference_on_the_weights_as_made():
    """The control changes what the ENGINE is given, never what the
    reference reads."""
    m = manifest.load()
    config = m.config(m.cell(CELL))
    config = {**config, **config["rehearse"]}
    sound = m.builder(config)
    wrong = degraded_routed.degraded(sound, "select_on_score")
    cfg = wrong.config(config)
    served = wrong.init_params(cfg, 0)
    assert not served["moe"]["router_bias"].any()
    made = sound.init_params(cfg, 0)
    assert made["moe"]["router_bias"].any()
    tokens = [[5, 9, 200, 17, 3, 250]]
    rows = [(0, 5)]
    a = wrong.reference.logits_at(served, tokens, rows, config)
    b = sound.reference.logits_at(made, tokens, rows, config)
    assert (a == b).all()
