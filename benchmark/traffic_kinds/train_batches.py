"""Kind ``train_batches``: one batch of uniform random token ids a step."""

import numpy as np


def batch(mix: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """Batch ``step`` of a training run: token ids [batch, seq]."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, (mix["batch"], mix["seq"]), dtype=np.int32)
