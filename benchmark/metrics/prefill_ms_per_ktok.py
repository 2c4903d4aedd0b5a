"""Device milliseconds of the ``prefill`` program's executions in the
trace per 1000 REAL prompt tokens prefilled during the traced stretch
(``prefill_tokens`` of ``engine.stats()`` at its start and end), so
bucket padding counts as cost."""


def read(run):
    runs = (run.get("trace") or {}).get("program_s", {}).get("prefill")
    c = run["counters"]
    if not runs or "trace_end" not in c:
        return None
    tokens = c["trace_end"]["prefill_tokens"] - c["trace_start"]["prefill_tokens"]
    return sum(runs) * 1e3 / tokens * 1e3 if tokens else None
