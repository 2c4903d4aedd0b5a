"""``drivers/serve_routed_tail.py``'s comparison with the reference can
fail, and passes the sound program: the ten controls of
``benchmark/degraded_zaya.py``, at the configuration file's rehearsal
sizes on the CPU, each in the process of the test through the driver's
own `bring_up` (the engine, the tick's programs, the replayed step)."""

import re
import time

import pytest

from benchmark import degraded_zaya
from benchmark.drivers import common, serve_local
from benchmark.harness import context, manifest

CELL = "zaya1.reason.flood"


def _bring_up(control, seed, **changed):
    """-> the check's readings, or the `Incorrect` it raised."""
    m, ctx, _ = context.build(str(manifest.ROOT), CELL, seed=seed,
                              seconds=0.0, t_start=time.perf_counter(),
                              rehearse=True)
    ctx.config = dict(ctx.config, **changed)
    return degraded_zaya.bring_up(m, ctx, control)


@pytest.fixture
def own_controls_alone(monkeypatch):
    """`serve_routed`'s four controls pass whatever they read (its
    requests still warm the engine), so that what refuses is one of the
    two this driver adds."""
    from benchmark.drivers import serve_routed

    def passes(handle, engine, params, config, cfg, seed, reference):
        eng = config["driver_args"]["engine"]
        serve_routed._ask(handle, serve_local._check_prompts(
            eng["prompt_buckets"], eng["max_len"], cfg.vocab_size, seed))
        return {}

    monkeypatch.setattr(serve_routed, "warm_and_check", passes)


# The rehearsal's width of 64 is too narrow to hold a bf16 program to
# the CHIP's limits (tests/benchmark/test_routed_controls.py has the
# readings), so in bf16 the hidden state is widened and every limit
# left as it is.
WIDER = {"hidden_size": 512, "num_attention_heads": 8, "head_dim": 32,
         "router_hidden_size": 64}


@pytest.mark.parametrize("seed, changed", [
    (0, {}), (1, {}), (2, dict(WIDER, torch_dtype="bfloat16")),
    (3, dict(WIDER, torch_dtype="bfloat16"))],
    ids=["f32-0", "f32-1", "bf16-2", "bf16-3"])
def test_the_sound_program_passes(seed, changed):
    checks = _bring_up("none", seed, **changed)
    assert isinstance(checks, dict), checks
    assert checks["route_choices_differ_share"] < 0.1
    assert checks["replay_agree"] >= 0.9
    # The router is float32 whatever the stream's type.
    assert checks["router_rel_l2_max"] < 1e-5
    if not changed:
        assert max(checks[k] for k in (
            "prefill_rel_l2_max", "step_rel_l2_max", "tail_rel_l2_max",
            "rows_rel_l2_max", "first_rows_rel_l2_max")) < 1e-5


@pytest.mark.parametrize("control, says", [
    # A step of CCA left out: the rows the slot keeps are another
    # function of the tokens, and the logits follow.
    ("no_value_shift", "logits off the reference|under the reference's"),
    ("no_qk_mean", "logits off the reference|under the reference's"),
    ("taps_reversed", "logits off the reference|under the reference's"),
    ("no_key_temperature", "logits off the reference|under the "
                           "reference's|what the slot keeps"),
    ("no_l2_norm", "logits off the reference|under the reference's"),
    # The router: its choice, its weight, its precision.
    ("select_on_p", "under the reference's boundary"),
    ("no_gate", "logits off the reference|under the reference's"),
    ("bf16_router", "the router's p off the reference's router"),
    # (its router's matrices are rounded too)
    ("int8", "logits off the reference|under the reference's|the router's "
             "p off the reference's router"),
    ("tail_not_reset", "the last owner's tail was read|logits off the "
                       "reference|under the reference's"),
])
def test_a_control_is_refused(control, says, seed=1):
    refused = _bring_up(control, seed)
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(says, str(refused)), refused
    # The patch is gone: the module is the sound one again.
    from ray_tpu.models import zaya

    assert zaya.route.__module__ == zaya.__name__


@pytest.mark.parametrize("control, says", [
    # At the cell's sizes a prompt's first token is one of hundreds and
    # a temperature moves the logits by little: what the slot KEEPS
    # shows both whatever the prompt's length.
    ("tail_not_reset", "the last owner's tail was read|what the slot "
                       "keeps off the reference"),
    ("no_key_temperature", "what the slot keeps off the reference"),
    ("no_value_shift", "what the slot keeps off the reference|the last "
                       "owner's tail"),
])
def test_what_the_slot_keeps_refuses_alone(own_controls_alone, control, says,
                                           seed=2):
    refused = _bring_up(control, seed)
    assert isinstance(refused, common.Incorrect), refused
    assert re.search(says, str(refused)), refused


def test_the_degraded_builder_keeps_the_reference_on_the_weights_as_made():
    """The control changes what the ENGINE is given, never what the
    reference reads."""
    m = manifest.load()
    config = m.config(m.cell(CELL))
    config = {**config, **config["rehearse"]}
    sound = m.builder(config)
    wrong = degraded_zaya.degraded(sound, "select_on_p")
    cfg = wrong.config(config)
    served = wrong.init_params(cfg, 0)
    assert not served["layers"]["router_bias"].any()
    made = sound.init_params(cfg, 0)
    assert made["layers"]["router_bias"].any()
    tokens = [[5, 9, 200, 17, 3, 250]]
    rows = [(0, 5)]
    a = wrong.reference.logits_at(served, tokens, rows, config)
    b = sound.reference.logits_at(made, tokens, rows, config)
    assert (a == b).all()
