"""Rotary position embeddings (RoPE), Llama-3 style (half-dim rotation),
and YaRN's scaling of the frequencies (arXiv:2309.00071, as the
DeepSeek-V3 family's published code computes it)."""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, theta: float = 500000.0) -> jnp.ndarray:
    """Inverse frequencies for each pair of rotated dims: [head_dim // 2]."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A config's ``rope_scaling`` of ``type`` ``yarn``: a model trained
    to ``original_max_position_embeddings`` positions read ``factor``
    times further. Frequencies that turn more than ``beta_fast`` times
    in the original length are kept, those that turn less than
    ``beta_slow`` times are divided by ``factor`` (their positions
    interpolated), a linear ramp over the frequency's INDEX blends the
    two between; attention's logits are then sharpened by ``mscale``
    (`softmax_mscale`)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @classmethod
    def of(cls, rope_scaling: dict) -> "YarnScaling":
        """From a config's ``rope_scaling`` group."""
        kind = rope_scaling.get("type", rope_scaling.get("rope_type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling of type {kind!r}, not yarn")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in rope_scaling.items() if k in names})

    def _turns_index(self, turns: float, head_dim: int, theta: float):
        """The (fractional) index of the frequency that makes ``turns``
        turns in the original length."""
        return (head_dim * math.log(self.original_max_position_embeddings
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    def frequencies(self, head_dim: int, theta: float) -> jnp.ndarray:
        """The ``head_dim // 2`` scaled inverse frequencies, float32."""
        half = head_dim // 2
        plain = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                                / head_dim)
        low = max(math.floor(self._turns_index(self.beta_fast, head_dim,
                                               theta)), 0)
        high = min(math.ceil(self._turns_index(self.beta_slow, head_dim,
                                               theta)), head_dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        return jnp.asarray(plain / self.factor * ramp + plain * (1 - ramp),
                           jnp.float32)

    def _get_mscale(self, mscale: float) -> float:
        if self.factor <= 1:
            return 1.0
        return 0.1 * mscale * math.log(self.factor) + 1.0

    @property
    def rotation_mscale(self) -> float:
        """What cos and sin are multiplied by."""
        return (self._get_mscale(self.mscale)
                / self._get_mscale(self.mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        """What the softmax scale ``head_dim ** -0.5`` is multiplied by:
        ``mscale ** 2`` over all dimensions (0: none)."""
        if not self.mscale_all_dim:
            return 1.0
        return self._get_mscale(self.mscale_all_dim) ** 2


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 500000.0, freqs=None) -> jnp.ndarray:
    """Rotate ``x`` [..., seq, heads, head_dim] by per-position angles.

    ``positions``: integer array broadcastable to [..., seq] — passing explicit
    positions (rather than arange) keeps the same code path correct for
    sequence-sharded (ring attention) and KV-cache decode cases.
    ``freqs`` [head_dim // 2]: the inverse frequencies where they are
    not ``theta``'s plain ones (`YarnScaling.frequencies`).
    """
    dtype = x.dtype
    if freqs is None:
        freqs = rope_frequencies(x.shape[-1], theta)            # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., seq, hd/2]
    angles = angles[..., None, :]                               # [..., seq, 1, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)
