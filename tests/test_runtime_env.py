"""Runtime environment tests (reference analog:
python/ray/tests/test_runtime_env_env_vars.py / test_runtime_env_working_dir):
env application at worker spawn, per-env worker isolation, and loud
rejection of unsupported fields.
"""

import os

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


def test_env_vars_applied(cluster):
    @ray_tpu.remote(runtime_env={"env_vars": {"RTPU_TEST_FLAG": "hello42"}})
    def read_env():
        return os.environ.get("RTPU_TEST_FLAG")

    assert ray_tpu.get(read_env.remote(), timeout=90) == "hello42"

    @ray_tpu.remote
    def read_default():
        return os.environ.get("RTPU_TEST_FLAG")

    assert ray_tpu.get(read_default.remote(), timeout=90) is None


def test_working_dir_applied(cluster, tmp_path):
    marker = tmp_path / "marker.txt"
    marker.write_text("present")

    @ray_tpu.remote(runtime_env={"working_dir": str(tmp_path)})
    def read_cwd():
        return os.getcwd(), open("marker.txt").read()

    cwd, content = ray_tpu.get(read_cwd.remote(), timeout=90)
    assert os.path.realpath(cwd) == os.path.realpath(str(tmp_path))
    assert content == "present"


def test_py_modules_applied(cluster, tmp_path):
    mod = tmp_path / "rtpu_test_module_xyz.py"
    mod.write_text("MAGIC = 1234\n")

    @ray_tpu.remote(runtime_env={"py_modules": [str(tmp_path)]})
    def import_it():
        import rtpu_test_module_xyz

        return rtpu_test_module_xyz.MAGIC

    assert ray_tpu.get(import_it.remote(), timeout=90) == 1234


def test_envs_do_not_share_workers(cluster):
    @ray_tpu.remote(runtime_env={"env_vars": {"WHICH_ENV": "A"}})
    def pid_a():
        return os.getpid(), os.environ["WHICH_ENV"]

    @ray_tpu.remote(runtime_env={"env_vars": {"WHICH_ENV": "B"}})
    def pid_b():
        return os.getpid(), os.environ["WHICH_ENV"]

    @ray_tpu.remote
    def pid_default():
        return os.getpid()

    pids_a = {p for p, e in ray_tpu.get(
        [pid_a.remote() for _ in range(6)], timeout=120)}
    pids_b = {p for p, e in ray_tpu.get(
        [pid_b.remote() for _ in range(6)], timeout=120)}
    pids_d = set(ray_tpu.get([pid_default.remote() for _ in range(6)],
                             timeout=120))
    assert not (pids_a & pids_b), "envs A and B shared a worker"
    assert not (pids_a & pids_d), "env A shared a default worker"
    assert not (pids_b & pids_d), "env B shared a default worker"
    # Env values were really isolated.
    envs_a = {e for _p, e in ray_tpu.get(
        [pid_a.remote() for _ in range(3)], timeout=120)}
    assert envs_a == {"A"}


def test_actor_runtime_env(cluster):
    @ray_tpu.remote(runtime_env={"env_vars": {"ACTOR_ENV": "yes"}})
    class EnvActor:
        def read(self):
            return os.environ.get("ACTOR_ENV")

    a = EnvActor.remote()
    assert ray_tpu.get(a.read.remote(), timeout=90) == "yes"


def test_unsupported_runtime_env_raises(cluster):
    with pytest.raises(ValueError, match="unsupported runtime_env"):
        @ray_tpu.remote(runtime_env={"container": {"image": "x"}})
        def f():
            return 1

        f.remote()

    with pytest.raises(ValueError, match="env_vars"):
        @ray_tpu.remote(runtime_env={"env_vars": {"X": 1}})
        def g():
            return 1

        g.remote()


def _build_tiny_wheel(tmp_path, name="rtpu_envtest_pkg", version="1.2.3"):
    """A minimal local wheel so pip installs work with zero egress
    (the reference mocks indices in its runtime_env tests similarly)."""
    import subprocess
    import sys

    src = tmp_path / "pkgsrc"
    (src / name).mkdir(parents=True)
    (src / name / "__init__.py").write_text(
        f"__version__ = {version!r}\n"
        f"def marker():\n    return 'installed-{version}'\n")
    (src / "pyproject.toml").write_text(
        '[build-system]\nrequires = ["setuptools"]\n'
        'build-backend = "setuptools.build_meta"\n'
        f'[project]\nname = "{name}"\nversion = "{version}"\n')
    wheels = tmp_path / "wheels"
    wheels.mkdir()
    subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-deps", "--no-index",
         "--no-build-isolation", "--wheel-dir", str(wheels), str(src)],
        check=True, capture_output=True, timeout=300)
    return str(wheels)


def test_pip_runtime_env_installs_and_isolates(cluster, tmp_path):
    """pip env: the task runs in a venv where the package imports; the
    DEFAULT env must not see it (reference: pip.py per-URI virtualenvs)."""
    wheels = _build_tiny_wheel(tmp_path)
    env = {"pip": {"packages": ["rtpu_envtest_pkg"], "no_index": True,
                   "find_links": wheels}}

    @ray_tpu.remote(runtime_env=env)
    def with_pkg():
        import rtpu_envtest_pkg

        return rtpu_envtest_pkg.marker()

    @ray_tpu.remote
    def without_pkg():
        try:
            import rtpu_envtest_pkg  # noqa: F401

            return "leaked"
        except ImportError:
            return "isolated"

    # Generous timeout: the FIRST call builds the venv (~5-10s).
    assert ray_tpu.get(with_pkg.remote(), timeout=180) == "installed-1.2.3"
    assert ray_tpu.get(without_pkg.remote(), timeout=60) == "isolated"
    # Cache hit: the second task over the same env reuses the venv (fast).
    import time as _time

    t0 = _time.monotonic()
    assert ray_tpu.get(with_pkg.remote(), timeout=60) == "installed-1.2.3"
    assert _time.monotonic() - t0 < 30


def test_py_executable_runtime_env(cluster):
    import sys

    @ray_tpu.remote(runtime_env={"py_executable": sys.executable})
    def which_python():
        return sys.executable

    assert ray_tpu.get(which_python.remote(), timeout=90) == sys.executable


def test_pip_runtime_env_failure_fails_fast(cluster, tmp_path):
    """An uninstallable pip env must FAIL the task with the install error
    (not hang through endless lease spillbacks)."""
    env = {"pip": {"packages": ["rtpu-definitely-missing-pkg"],
                   "no_index": True, "find_links": str(tmp_path)}}

    @ray_tpu.remote(runtime_env=env)
    def doomed():
        return 1

    with pytest.raises(Exception, match="runtime_env|env"):
        ray_tpu.get(doomed.remote(), timeout=120)
