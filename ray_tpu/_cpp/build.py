"""Build the native store library from its one source, `shm_store.cc`.

`ensure_built()` is the one place that decides whether a compile is
needed, and `ray_tpu.core.shm_store._load_lib()` calls it on first use.
The artefact's NAME carries a digest of the source and of the compile
command (`libshm_store-<digest>.so`), so a library built from other
source or other flags is simply another file that nobody opens: nothing
is checked in, nothing can be stale, and there is no switch to rebuild.

Concurrent callers (the xdist workers of a test run; the head, nodes and
workers of one cluster) compile at most once: an `flock` on a file beside
the artefact serialises them, `g++` writes to a temporary name in the
destination and `os.replace` publishes it whole.

Run directly (`python ray_tpu/_cpp/build.py [--sanitize=address|thread]
[--out-dir DIR]`) to get the path printed; `RTPU_SHM_STORE_SO` names a
library built elsewhere (a sanitizer build, a read-only install).
"""

from __future__ import annotations

import argparse
import fcntl
import hashlib
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(HERE, "shm_store.cc")

CXX = ["g++", "-O2", "-g", "-std=c++17", "-shared", "-fPIC"]
LIBS = ["-lpthread", "-lrt"]

#: --sanitize flag -> extra g++ flags. Sanitized builds are for hunting
#: races/overflows in shm_store.cc under the dataplane tests. Their flags
#: are part of the digest, so the loader never picks one up by itself:
#: they are loaded via RTPU_SHM_STORE_SO.
SANITIZERS = {
    "address": ["-fsanitize=address", "-fno-omit-frame-pointer"],
    "thread": ["-fsanitize=thread", "-fno-omit-frame-pointer"],
}


def _default_dir() -> str:
    """Beside the source when that is writable (a checkout), else a
    per-user directory under the system's temporary directory (a
    read-only install)."""
    if os.access(HERE, os.W_OK):
        return HERE
    return os.path.join(tempfile.gettempdir(), f"rtpu_native_{os.getuid()}")


def ensure_built(out_dir: str | None = None, sanitize: str | None = None,
                 verbose: bool = False) -> str:
    """Path of the library built from the current `shm_store.cc` with the
    current flags, compiling it first if no such file exists yet."""
    flags = CXX + (SANITIZERS[sanitize] if sanitize else [])
    with open(SOURCE, "rb") as f:
        digest = hashlib.sha256(
            f.read() + "\0".join(flags + LIBS).encode()).hexdigest()[:16]
    dest = out_dir or _default_dir()
    os.makedirs(dest, exist_ok=True)
    out = os.path.join(dest, f"libshm_store-{digest}.so")
    if os.path.exists(out):
        return out
    with open(os.path.join(dest, ".libshm_store.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when `lock` closes
        if os.path.exists(out):
            return out  # another process compiled it while we waited
        tmp = os.path.join(dest, ".libshm_store.tmp")  # ours: we hold the lock
        cmd = flags + ["-o", tmp, SOURCE] + LIBS
        if verbose:
            print("+", " ".join(cmd), file=sys.stderr)
        try:
            subprocess.run(cmd, check=True, capture_output=not verbose,
                           text=True)
            os.replace(tmp, out)
        except FileNotFoundError as e:
            raise OSError(
                "the native store library must be compiled from "
                f"{SOURCE} and `g++` was not found; install g++, or set "
                "RTPU_SHM_STORE_SO to a library built elsewhere with "
                "`python ray_tpu/_cpp/build.py --out-dir DIR`") from e
        except subprocess.CalledProcessError as e:
            raise OSError(
                f"`{' '.join(cmd)}` failed ({e.returncode}); set "
                "RTPU_SHM_STORE_SO to a library built elsewhere if this "
                f"machine cannot compile it:\n{e.stderr or ''}") from e
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sanitize", choices=sorted(SANITIZERS),
                   help="build with AddressSanitizer/ThreadSanitizer "
                        "(load via RTPU_SHM_STORE_SO)")
    p.add_argument("--out-dir",
                   help="directory for the built .so (default: beside the "
                        "source, or a per-user temporary directory when "
                        "that is read-only)")
    args = p.parse_args()
    out = ensure_built(out_dir=args.out_dir, sanitize=args.sanitize,
                       verbose=True)
    print(out)
    if args.sanitize:
        # dlopen-ing a sanitized .so into a plain python process aborts
        # ("runtime does not come first in initial library list") unless
        # the sanitizer runtime is preloaded.
        rt_lib = {"address": "libasan.so", "thread": "libtsan.so"}[
            args.sanitize]
        preload = subprocess.run(
            ["g++", f"-print-file-name={rt_lib}"],
            capture_output=True, text=True).stdout.strip()
        print(f"run the cluster against the sanitized ({args.sanitize}) "
              f"build with:\n"
              f"  export RTPU_SHM_STORE_SO={out}\n"
              f"  export LD_PRELOAD={preload or rt_lib}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
