"""Exact running totals of device integers that stay on the device.

``Counter.add_device`` (``obs/metrics.py``) feeds one of these: each
addition is two small device dispatches, never a wait or a transfer to the
host; ``int(total)`` reads it.  With 64-bit types off, JAX has no integer
wide enough for a long run's total, so the total is kept as two ``uint32``
words with a carry: exact up to 2**64, provided each single addition sums
to under 2**32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["DeviceTotal"]


@jax.jit
def _carry_add(words, s):
    lo = words[0] + s
    return jnp.stack([lo, words[1] + (lo < s).astype(jnp.uint32)])


class DeviceTotal:
    """A sum of integer device arrays, one ``(lo, hi)`` pair of words per
    placement that the arrays came from (a sum over a mesh stays on the
    mesh)."""

    __slots__ = ("_words",)

    def __init__(self):
        self._words: dict = {}  # sharding -> uint32[2]

    def add(self, x) -> None:
        s = jnp.sum(x, dtype=jnp.uint32)
        words = self._words.get(s.sharding)
        self._words[s.sharding] = _carry_add(
            jnp.zeros((2,), jnp.uint32) if words is None else words, s)

    def __int__(self) -> int:
        return sum(int(lo) + (int(hi) << 32) for lo, hi in
                   (jax.device_get(w) for w in self._words.values()))
