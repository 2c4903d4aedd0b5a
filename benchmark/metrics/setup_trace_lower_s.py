"""Seconds of set-up JAX spent tracing the programs to jaxprs and
lowering them to modules (`/jax/core/compile/jaxpr_trace_duration`,
`jaxpr_to_mlir_module_duration`, summed by the program's compile
account on the wall clock): paid on every start, warm or cold."""

from benchmark.harness import setup_account


def read(run):
    return setup_account.part(run, "trace_lower_s")
