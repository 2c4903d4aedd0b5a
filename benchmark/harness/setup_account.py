"""Where set-up went, from the program's own compile account.

``ray_tpu.util.compile_cache.account()`` (PR 37) keeps, for the whole
process, the seconds JAX spent tracing, lowering, compiling and loading
programs from the persistent cache, and the constructors' wall time
less the compiles inside them. A run is `correct` only with no
compilation inside its window, so when the readers run the totals are
the set-up's. A program without the account (a parent commit) gives
``None``, the rule of ``benchmark/README.md``.
"""

from __future__ import annotations

SECONDS = ("trace_lower_s", "cache_load_s", "compile_s", "init_s")


def parts(run: dict):
    """``setup_s`` in five parts that add up to it, and the count of
    backend requests; None where the program keeps no account."""
    from ray_tpu.util import compile_cache

    account = getattr(compile_cache, "account", lambda: None)()
    if account is None:
        return None
    t = account.totals()
    out = {"trace_lower_s": t["trace_s"] + t["lower_s"],
           "cache_load_s": t["cache_load_s"], "compile_s": t["compile_s"],
           "init_s": t["init_s"], "programs_requested": t["requests"]}
    out["rest_s"] = run["setup_s"] - sum(out[k] for k in SECONDS)
    return out


def part(run: dict, key: str):
    found = parts(run)
    return None if found is None else found[key]
