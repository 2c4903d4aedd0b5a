"""Seconds of set-up spent in backend compiles that missed or did not
consult the persistent cache: about 0 on a warm start; where it is
not, the account's rows name the program."""

from benchmark.harness import setup_account


def read(run):
    return setup_account.part(run, "compile_s")
