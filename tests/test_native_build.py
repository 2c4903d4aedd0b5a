"""The native store library is built from its one source, once.

`ray_tpu/_cpp/build.py:ensure_built` names the artefact by a digest of
`shm_store.cc` and the compile command, so these hold without any switch:
processes that need it at the same moment compile it once and all load a
whole file; a library from other source is another file and is never
opened; a machine that cannot compile says so in one error.
"""

import os
import shutil
import stat
import subprocess
import sys
import textwrap

import pytest

from ray_tpu._cpp import build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counting_gxx(tmp_path):
    """A `g++` first on PATH that logs one line per compile, then runs
    the real one. `-print-file-name` and the like are not compiles."""
    real = shutil.which("g++")
    if real is None:
        pytest.skip("no g++ on this machine")
    bindir = tmp_path / "bin"
    bindir.mkdir()
    log = tmp_path / "compiles.log"
    wrapper = bindir / "g++"
    wrapper.write_text(textwrap.dedent(f"""\
        #!/bin/sh
        echo "$$" >> {log}
        sleep 0.5   # hold the compile open so the others really wait
        exec {real} "$@"
        """))
    wrapper.chmod(wrapper.stat().st_mode | stat.S_IEXEC)
    return str(bindir), log


_CHILD = """
import sys
sys.path.insert(0, {repo!r})
from ray_tpu._cpp import build
build._default_dir = lambda: {dest!r}
from ray_tpu.core import shm_store
lib = shm_store._load_lib()
assert int(lib.rtpu_lib_layout_version()) == shm_store._LAYOUT_VERSION
print(lib._name)
"""


def test_concurrent_first_loads_compile_once(tmp_path):
    bindir, log = _counting_gxx(tmp_path)
    dest = tmp_path / "dest"  # does not exist yet: an empty destination
    env = dict(os.environ, PATH=bindir + os.pathsep + os.environ["PATH"],
               JAX_PLATFORMS="cpu")
    env.pop("RTPU_SHM_STORE_SO", None)
    code = _CHILD.format(repo=REPO, dest=str(dest))
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True) for _ in range(6)]
    outs = [p.communicate(timeout=120) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err
    loaded = {out.strip() for out, _ in outs}
    assert len(loaded) == 1, loaded
    assert len(log.read_text().splitlines()) == 1, "compiled more than once"
    libs = sorted(f for f in os.listdir(dest) if f.endswith(".so"))
    assert libs == [os.path.basename(loaded.pop())]
    assert libs[0].startswith("libshm_store-")
    # nothing half-written is left beside it
    assert not [f for f in os.listdir(dest) if f.endswith(".tmp")]


def test_library_from_other_source_is_not_opened(tmp_path, monkeypatch):
    dest = tmp_path / "dest"
    dest.mkdir()
    # What a checkout of older source leaves behind: the old fixed name
    # and another digest's file. Neither is a loadable library, so
    # opening either would raise.
    (dest / "libshm_store.so").write_bytes(b"layout v1, not an ELF file")
    (dest / "libshm_store-0123456789abcdef.so").write_bytes(b"stale")
    so = build.ensure_built(out_dir=str(dest))
    assert os.path.basename(so) not in (
        "libshm_store.so", "libshm_store-0123456789abcdef.so")
    import ctypes
    assert int(ctypes.CDLL(so).rtpu_lib_layout_version()) >= 2
    assert build.ensure_built(out_dir=str(dest)) == so  # found, not rebuilt

    # Other source, or other flags, is another name.
    edited = tmp_path / "shm_store.cc"
    edited.write_bytes(open(build.SOURCE, "rb").read() + b"\n// edited\n")
    monkeypatch.setattr(build, "SOURCE", str(edited))
    so2 = build.ensure_built(out_dir=str(dest))
    assert so2 != so and os.path.exists(so2)
    monkeypatch.undo()
    monkeypatch.setattr(build, "CXX", build.CXX + ["-DRTPU_OTHER_FLAGS"])
    assert build.ensure_built(out_dir=str(dest)) not in (so, so2)


def test_missing_compiler_is_one_clear_error(tmp_path, monkeypatch):
    monkeypatch.setenv("PATH", str(tmp_path))  # no g++ here
    with pytest.raises(OSError) as e:
        build.ensure_built(out_dir=str(tmp_path / "dest"))
    assert "g++" in str(e.value) and "RTPU_SHM_STORE_SO" in str(e.value)
    assert not [f for f in os.listdir(tmp_path / "dest")
                if f.endswith((".so", ".tmp"))]


def test_override_from_outside_is_still_version_checked(tmp_path):
    """RTPU_SHM_STORE_SO is input from outside: a library with another
    layout version is refused at load."""
    src = tmp_path / "old.cc"
    src.write_text('extern "C" unsigned long long '
                   'rtpu_lib_layout_version() { return 1; }\n')
    old = tmp_path / "libold.so"
    subprocess.run(["g++", "-shared", "-fPIC", "-o", str(old), str(src)],
                   check=True)
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from ray_tpu.core import shm_store\n"
            "try:\n    shm_store._load_lib()\n"
            "except OSError as e:\n    print(e)\n" % REPO)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, RTPU_SHM_STORE_SO=str(old),
                 JAX_PLATFORMS="cpu"), timeout=120)
    assert "stale shm store library" in out.stdout, out.stderr
    assert "layout version 1" in out.stdout
