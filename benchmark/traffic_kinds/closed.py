"""Kind ``closed``: ``clients`` callers, each sending its next request
when the last returned. A closed loop's length is not known beforehand:
its clients walk a pool of ``pool`` requests, round and round."""

import numpy as np

from benchmark.harness import loadgen, traffic


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             max_total: int) -> list:
    rng = np.random.default_rng(seed)
    return traffic.sized(mix, rng, np.zeros(mix["pool"]), vocab, max_total)


def drive(stream_fn, reqs, mix, *, seconds, vocab, t0, on_window_end):
    return loadgen.closed_loop(stream_fn, reqs, clients=mix["clients"],
                               seconds=seconds, vocab=vocab, t0=t0,
                               on_window_end=on_window_end)
