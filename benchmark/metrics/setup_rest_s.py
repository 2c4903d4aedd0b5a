"""``setup_s`` less the four parts the program accounts for
(`setup_trace_lower_s`, `setup_cache_load_s`, `setup_compile_s`,
`setup_init_s`): imports, the builder's weights, the reference, the
warm-up's device time. What the benchmark's own files spend."""

from benchmark.harness import setup_account


def read(run):
    return setup_account.part(run, "rest_s")
