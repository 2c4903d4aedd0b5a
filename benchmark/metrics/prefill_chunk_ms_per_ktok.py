"""Device milliseconds of a ``prefill`` execution per 1000 REAL prompt
tokens of a dispatched chunk, over the traced stretch: the MEAN device
time of the stretch's ``prefill`` executions over the MEAN real tokens
of the chunks dispatched in it (``prefill_chunk_tokens`` over
``prefill_chunks_dispatched`` of ``engine.stats()``, counted at the
dispatch). Two means, so a chunk in flight at either edge of the
stretch shifts neither by a prompt, where `prefill_ms_per_ktok` divides
by ``prefill_tokens``, which rises by a whole prompt at its last chunk."""


def read(run):
    runs = (run.get("trace") or {}).get("program_s", {}).get("prefill")
    c = run.get("counters") or {}
    if not runs or "prefill_chunk_tokens" not in c.get("trace_end", ()):
        return None
    chunks, tokens = (c["trace_end"][k] - c["trace_start"][k]
                      for k in ("prefill_chunks_dispatched",
                                "prefill_chunk_tokens"))
    if not chunks or not tokens:
        return None
    return sum(runs) / len(runs) * 1e3 / (tokens / chunks) * 1e3
