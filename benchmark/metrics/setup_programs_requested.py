"""Backend compiles the process asked for during set-up, hit or not
(a second signature or a second lowering of one program counts again);
the window runs a handful of them."""

from benchmark.harness import setup_account


def read(run):
    return setup_account.part(run, "programs_requested")
