"""Device milliseconds of one decode step: the ``decode_chunk``
program's executions in the trace, over ``decode_chunk`` steps each."""


def read(run):
    runs = (run.get("trace") or {}).get("program_s", {}).get("decode_chunk")
    if not runs:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    return sum(runs) / len(runs) / chunk * 1e3
