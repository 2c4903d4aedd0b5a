"""Tokens of every step begun in the window / the seconds from the
window's start to the last step's end (batches made and placed inside
the loop, every step ended by ``block_until_ready``)."""


def read(run):
    return len(run["steps"]) * run["tokens_per_step"] / run["window_s"]
