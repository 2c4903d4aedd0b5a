"""Wall seconds of the program's constructors (the engine's, or
``spmd.sharded_init`` + ``make_train_step`` + ``make_eval_step``),
less the compile seconds booked inside them."""

from benchmark.harness import setup_account


def read(run):
    return setup_account.part(run, "init_s")
