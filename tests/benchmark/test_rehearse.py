"""``run.py`` end to end: every cell at a tiny size on the CPU, traced
and not; and the refusal to measure without the chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest

RUN = [sys.executable, str(manifest.BENCH_DIR / "run.py")]
CELLS = list(manifest.load().cells)    # a later cell rehearses too
SERVING_CELL = "mistral7b.chat.steady"


def _run(*args, env=None):
    env = dict(os.environ if env is None else env)
    env.pop("XLA_FLAGS", None)      # the run sets its own device count
    return subprocess.run(RUN + list(args), capture_output=True, text=True,
                          timeout=600, env=env, cwd=manifest.ROOT)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_every_cell(cell, trace):
    out = _run("--workload", cell, "--seed", str(2**31 + 17), "--seconds",
               "2", "--trace", str(trace), "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 2
    # A rehearsal names the CPU and carries no metric.
    assert last["device"]["platform"] == "cpu" and last["metrics"] == {}
    m = manifest.load()
    kind = "per_layer" if trace else "end_to_end"
    read = set(last["rehearsal_metric_names"])
    listed = {x["name"] for x in m.metrics_of(cell, kind)}
    assert read <= listed and (trace or read == listed)
    if trace:
        assert last["device"]["busy_s"] > 0 and last["breakdown"]["device_ops"]


@pytest.mark.parametrize("seed", [0, 1])
def test_a_degraded_replica_is_refused(seed):
    """The engine's own weight-only int8 against the reference on the
    weights as made: 4e-2 to 5e-2 off at 12 tiny layers, or a token
    over 1 % of the logit spread under the best (the undegraded
    rehearsal above reads 1e-6 and 0)."""
    out = subprocess.run(
        [sys.executable, str(manifest.BENCH_DIR / "degraded.py"),
         "--workload", SERVING_CELL, "--rehearse", "--layers", "12",
         "--seed", str(seed)],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert out.returncode == 0, out.stdout[-500:] + out.stderr[-2000:]
    assert "refused" in json.loads(out.stdout.strip().splitlines()[-1])


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run("--workload", SERVING_CELL, "--seed", "1", "--seconds", "1",
               "--trace", "0", env=env)
    assert out.returncode != 0
    assert "needs platform 'tpu'" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
    out = _run("--workload", "no.such.cell", "--seed", "1", "--seconds", "1",
               "--rehearse")
    assert out.returncode != 0 and not out.stdout.strip()
