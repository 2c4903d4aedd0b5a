"""Job submission + CLI tests (reference analog:
python/ray/tests/test_job_manager.py + dashboard job cli tests).
"""

import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.jobs import JobStatus, JobSubmissionClient


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def test_job_submit_success_and_logs(cluster):
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job says hi')\"")
    status = client.wait_until_finish(job_id, timeout=120)
    assert status == JobStatus.SUCCEEDED
    assert "job says hi" in client.get_job_logs(job_id)
    infos = {j.submission_id: j for j in client.list_jobs()}
    assert infos[job_id].status == "SUCCEEDED"


def test_job_entrypoint_joins_cluster(cluster):
    """The submitted driver connects to THIS cluster via RTPU_ADDRESS and
    can run tasks on it."""
    script = (
        "import ray_tpu; ray_tpu.init();\n"
        "f = ray_tpu.remote(lambda: 21)\n"
        "print('answer', 2 * ray_tpu.get(f.remote(), timeout=60))\n")
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"{script}\"")
    status = client.wait_until_finish(job_id, timeout=180)
    logs = client.get_job_logs(job_id)
    assert status == JobStatus.SUCCEEDED, logs
    assert "answer 42" in logs


def test_job_failure_and_runtime_env(cluster):
    client = JobSubmissionClient()
    bad = client.submit_job(entrypoint=f"{sys.executable} -c 'exit(3)'")
    assert client.wait_until_finish(bad, timeout=120) == JobStatus.FAILED
    assert "rc=3" in client.get_job_info(bad).message

    envd = client.submit_job(
        entrypoint=f"{sys.executable} -c \"import os; "
                   f"print('V=' + os.environ['JOBVAR'])\"",
        runtime_env={"env_vars": {"JOBVAR": "zap"}})
    assert client.wait_until_finish(envd, timeout=120) == JobStatus.SUCCEEDED
    assert "V=zap" in client.get_job_logs(envd)


def test_job_stop(cluster):
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c 'import time; time.sleep(120)'")
    time.sleep(2.0)
    assert client.stop_job(job_id)
    status = client.wait_until_finish(job_id, timeout=60)
    assert status == JobStatus.STOPPED


def test_cli_status_and_submit(cluster):
    """Drive the CLI as a REAL subprocess against this live cluster."""
    addr = cluster.head_addr
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "status",
         "--address", addr],
        capture_output=True, text=True, timeout=120, cwd="/root/repo")
    assert out.returncode == 0, out.stderr
    assert "alive" in out.stdout  # head node (+ the CLI driver node)
    assert "Resources:" in out.stdout

    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "submit",
         "--address", addr, "--timeout", "120", "--",
         sys.executable, "-c", "print('cli job ran')"],
        capture_output=True, text=True, timeout=180, cwd="/root/repo")
    assert out.returncode == 0, out.stderr + out.stdout
    assert "cli job ran" in out.stdout
    assert "SUCCEEDED" in out.stdout
