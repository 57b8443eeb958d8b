"""Rotary position embeddings."""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Yarn(NamedTuple):
    """A published ``rope_scaling`` of type ``yarn`` (arXiv:2309.00071, as
    DeepSeek-V3's modeling file applies it): the context grew ``factor``
    times from ``original_max_position``; a pair that turns more than
    ``beta_fast`` times in the original context keeps its frequency, one
    that turns less than ``beta_slow`` times has it divided by ``factor``,
    and the pairs between blend the two along a linear ramp."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _m(factor: float, mscale: float) -> float:
        return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0

    def table_scale(self) -> float:
        """What the sin and cos tables are multiplied by."""
        return (self._m(self.factor, self.mscale)
                / self._m(self.factor, self.mscale_all_dim))

    def softmax_scale(self) -> float:
        """What the attention's ``d ** -0.5`` is multiplied by."""
        return self._m(self.factor, self.mscale_all_dim) ** 2

    def inv_freq(self, head_dim: int, theta: float) -> jax.Array:
        """[head_dim // 2] float32: each pair's frequency, the blend."""
        half = head_dim // 2

        def pair_that_turns(times: float) -> float:
            return head_dim * math.log(self.original_max_position / (
                times * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(pair_that_turns(self.beta_fast)), 0)
        high = min(math.ceil(pair_that_turns(self.beta_slow)), head_dim - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        plain = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                 / head_dim))
        return plain / self.factor * ramp + plain * (1.0 - ramp)


def rope_angles(seq_len: int, head_dim: int, theta: float = 10000.0,
                dtype=jnp.float32, yarn: Optional[Yarn] = None):
    """(sin, cos) tables of shape [seq_len, head_dim // 2]; under ``yarn``
    at its blended frequencies and times its ``table_scale``."""
    if yarn is not None:
        angles = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32),
                           yarn.inv_freq(head_dim, theta))
        scale = yarn.table_scale()
        return ((jnp.sin(angles) * scale).astype(dtype),
                (jnp.cos(angles) * scale).astype(dtype))
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)
    return jnp.sin(angles).astype(dtype), jnp.cos(angles).astype(dtype)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array,
               positions: jax.Array | None = None) -> jax.Array:
    """Rotate pairs (x[..., ::2], x[..., 1::2]).

    x: [batch, seq, heads, head_dim]; sin/cos: [max_seq, head_dim//2] tables,
    gathered at ``positions`` ([batch, seq], defaults to arange) — the gather
    form supports decode-time offsets without retracing.
    """
    if positions is None:
        s = sin[: x.shape[1]][None, :, None, :]
        c = cos[: x.shape[1]][None, :, None, :]
    else:
        s = sin[positions][:, :, None, :]
        c = cos[positions][:, :, None, :]
    return _rotate(x, s, c)


def _rotate(x: jax.Array, s: jax.Array, c: jax.Array) -> jax.Array:
    """The pairs of ``x`` [b, s, h, d] turned by ``s``, ``c`` (broadcast
    against [b, s, h, d / 2])."""
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    rotated = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


def section_pairs(sections: Sequence[int], half: int) -> Tuple[int, ...]:
    """``sections`` (frequency pairs a position stream, as a published
    ``mrope_section`` gives them for the main head width) for a head of
    ``half`` pairs: as they are where they add up to ``half``, else scaled
    to it (16 | 24 | 24 of 64 is 8 | 12 | 12 of 32)."""
    total = sum(sections)
    pairs = tuple(n * half // total for n in sections)
    if sum(pairs) != half:
        raise ValueError(f"sections {tuple(sections)} do not scale to "
                         f"{half} pairs")
    return pairs


def rope_angles_by_sections(positions: jax.Array, head_dim: int,
                            theta: float, sections: Sequence[int],
                            dtype=jnp.float32):
    """(sin, cos) [b, s, head_dim // 2] of a rotation by sections:
    ``positions`` [n, b, s] holds one position stream a section, and
    frequency pair ``i`` (``theta ** (-2 i / head_dim)``, as
    ``rope_angles``') takes the stream of the section it lies in, the
    first ``sections[0]`` pairs the first stream's and so on. With every
    stream ``arange(s)`` the tables are ``rope_angles``' to the bit."""
    half = head_dim // 2
    pairs = section_pairs(sections, half)
    if len(pairs) != positions.shape[0]:
        raise ValueError(f"{positions.shape[0]} position streams for "
                         f"{len(pairs)} sections")
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    stream = np.repeat(np.arange(len(pairs)), pairs)               # [half]
    pos = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream]
    angles = pos * inv_freq
    return jnp.sin(angles).astype(dtype), jnp.cos(angles).astype(dtype)


def apply_rope_by_position(x: jax.Array, sin: jax.Array, cos: jax.Array
                           ) -> jax.Array:
    """``apply_rope`` with tables a position of the batch: x [b, s, h, d],
    sin / cos [b, s, d // 2] (``rope_angles_by_sections``')."""
    return _rotate(x, sin[:, :, None, :], cos[:, :, None, :])
