"""Live slot-steps / slot-steps the decode program ran in the window:
``decode_steps`` over ``decode_host_syncs`` x ``decode_chunk`` x slots."""

from benchmark.harness import stats


def read(run):
    eng = run["config"]["driver_args"]["engine"]
    ran = (stats.counter_delta(run, "decode_host_syncs")
           * eng["decode_chunk"] * eng["max_batch"])
    if not ran:
        return None
    return stats.counter_delta(run, "decode_steps") / ran * 100.0
