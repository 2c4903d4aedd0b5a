"""How far from doubly stochastic the stream-to-stream maps are after
their Sinkhorn passes: the largest |row or column sum - 1| of any H_res
in any program call (a prefill's tokens or a decode step's slots, all
80 sub-layers; float32) of the engine's life up to the window's end.
The program gives a call's largest as ``mhc_sinkhorn_err_max`` and the
engine KEEPS the largest of what it fetches (the family's
``COUNTER_MAXES``; it sums every other counter), so this reads the last
snapshot as it stands: one bad call in the window shows whole. It reads
the logits' spread (a drawn model's is wide; twenty passes leave its
slowest row some 1e-2 off), and a program that cut the passes reads ten
times higher. A program without the counter reads nothing."""


def read(run):
    end = (run.get("counters") or {}).get("end") or {}
    return end.get("mhc_sinkhorn_err_max")
