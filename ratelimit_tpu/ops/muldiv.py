"""Exact ``floor(a * b / d)`` in uint32 arithmetic.

The stateful kernels scale a count by an elapsed fraction of the
window (sliding-window's weighted previous count, GCRA's refilled
cells) and floor the result — one admission rides on that floor.  The
device has no 64-bit integers (no jax_enable_x64) and a TPU's f32
divide is not correctly rounded (measured on a v5e, PERF.md PR 21), so
the product is taken apart instead.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def floor_muldiv(a: jax.Array, b: jax.Array, d: jax.Array) -> jax.Array:
    """``floor(a * b / d)`` exactly, for any uint32 ``a`` and
    ``b <= d < 2**20`` — every window unit up to DAY.

    The 52-bit product never exists.  With ``a = q*d + r`` the result
    is ``q*b + floor(r*b / d)``; ``r*b`` still has up to 34 bits, so
    it is divided in two base-256 steps whose intermediates stay under
    2**32: ``(r >> 8) * b < d**2 / 256`` and
    ``256*r1 + (r & 255)*b < 512 * d``."""
    q, r = a // d, a % d
    t1 = (r >> 8) * b
    q1, r1 = t1 // d, t1 % d
    tail = ((r1 << 8) + (r & jnp.uint32(255)) * b) // d
    return q * b + (q1 << 8) + tail
