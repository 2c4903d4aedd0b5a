"""Seconds of set-up spent in backend requests that the persistent
compilation cache served (the key, the read, the load): what a warm
start pays in place of compiling."""

from benchmark.harness import setup_account


def read(run):
    return setup_account.part(run, "cache_load_s")
