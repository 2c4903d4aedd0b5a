"""``drivers/serve_routed_sparse.py``'s comparison with the reference can
fail, and passes the sound program: the thirteen controls of
``benchmark/degraded_dots3.py`` at the configuration file's rehearsal
sizes on the CPU (float32; index_topk 8, window 5, 4 of 16 experts
held), each in the process of the test through the driver's own
`bring_up` (the engine, the tick's chunked prefill, the replayed
step)."""

import re
import time

import pytest

from benchmark import degraded_dots3
from benchmark.drivers import common
from benchmark.harness import context, manifest

CELL = "dots3.longdoc.flood"


# A score kept in bf16 exchanges a row in a hundred: the rehearsal's two
# prompts of some 50 and 80 rows, 8 chosen of each, may hold none, so
# this control's prompts are longer.
LONGER = {"bf16_scores": [[200, 222], [230, 254]]}


def _bring_up(control, seed):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    if control in LONGER:
        ctx.config = dict(ctx.config, driver_args=dict(
            ctx.config["driver_args"], check_prompt_lens=LONGER[control]))
    return degraded_dots3.bring_up(m, ctx, control)


# What the limits say (`serve_routed_sparse.warm_and_check`). A control
# that moves the whole stream is refused by whichever limit is read
# first; one that only a direct reading can see names its limit.
STREAM = ("logits off the reference|under the reference's best|a chosen "
          "expert lies|expert choices differ|a selected row lies|of the "
          "selected rows differ")
OWN_SELECTION = "are not the float32 top 8 of its own queries"
WINDOW = "other rows than the 5 of the published window"
GATES = "gates lie .* off the float32 router"


@pytest.mark.parametrize("control, says", [
    ("none", None),
    # The latent attention.
    ("no_rescale", STREAM),
    ("no_gate", STREAM),
    # The window, read off the mask each attention ran under.
    ("window_minus_1", WINDOW),
    ("window_plus_1", WINDOW),
    ("window_not_reset", WINDOW),
    # The selection: against the reference's boundary, and against the
    # step's own operands in float32.
    ("top_minus_1", "a selected row lies 1.0000|" + OWN_SELECTION),
    ("approx_topk", "a selected row lies|" + OWN_SELECTION),
    ("no_relu", "a selected row lies|of the selected rows differ|"
                + OWN_SELECTION),
    ("no_head_weights", "a selected row lies|of the selected rows differ|"
                        + OWN_SELECTION),
    ("bf16_scores", OWN_SELECTION + "|a selected row lies|of the selected "
                    "rows differ"),
    # The router: the range its gates are normalised over, its precision.
    ("gates_over_held", GATES + "|" + STREAM),
    ("bf16_router", GATES + "|a chosen expert lies"),
    ("int8", STREAM),
])
def test_the_check_refuses_a_control_and_passes_the_sound_program(
        control, says, seed=1):
    got = _bring_up(control, seed)
    if says is None:
        assert isinstance(got, dict), got
        assert got["window_queries_wrong"] == 0
        assert got["own_select_rows_differ_share"] == 0.0
        assert got["own_gates_rel_l2_max"] < 1e-5
        assert got["select_excess_max"] == 0.0
        assert max(got["prefill_rel_l2"], got["step_rel_l2_max"],
                   got["reuse_rel_l2_max"]) < 1e-4
        assert got["replay_agree"] == 1.0
        return
    assert isinstance(got, common.Incorrect), got
    assert re.search(says, str(got)), got
    # The patch is gone: the modules are the sound ones again.
    from ray_tpu.models import dots3_note
    from ray_tpu.ops import row_select

    assert dots3_note._is_a_row.__module__ == dots3_note.__name__
    assert row_select.top_rows.__module__ == row_select.__name__


def test_the_degraded_builder_keeps_the_reference_on_the_weights_as_made():
    """The control changes what the ENGINE is given, never what the
    reference reads."""
    m = manifest.load()
    config = m.config(m.cell(CELL))
    config = {**config, **config["rehearse"]}
    sound = m.builder(config)
    wrong = degraded_dots3.degraded(sound, "int8")
    cfg = wrong.config(config)
    served = wrong.init_params(cfg, 0)
    made = sound.init_params(cfg, 0)
    assert (served["lm_head"] != made["lm_head"]).any()
    assert (served["moe"]["router_bias"] == made["moe"]["router_bias"]).all()
    tokens = [[5, 9, 200, 17, 3, 250, 9, 9, 31, 77, 1, 2]]
    rows = [(0, 11)]
    a = wrong.reference.logits_at(served, tokens, rows, config)
    b = sound.reference.logits_at(made, tokens, rows, config)
    assert (a == b).all()
    assert degraded_dots3.degraded(sound, "top_minus_1").config(
        config).index_topk == cfg.index_topk - 1
