"""What both drivers share: the relative error and the refusal. What
belongs to a model family (its sizes as the program's configuration,
its weights from the seed, its plain reference) is the configuration's
builder, ``benchmark/builders/<name>.py``."""

from __future__ import annotations


def rel_l2(got, ref) -> float:
    import jax.numpy as jnp

    g, r = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))


class Incorrect(RuntimeError):
    """A correctness check of the run did not hold."""


def require(ok, what) -> None:
    if not ok:
        raise Incorrect(str(what))
