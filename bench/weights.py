"""Helpers for seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program, so the plain
reference can use the very same arrays without taking anything the
program made.  Each architecture's module (``bench/arch/<name>.py``)
lays them out as the program's parameter tree and draws them in the
served dtype with these helpers.  Large leaves are drawn a block of
rows at a time (``lax.map``), so no float32 copy of a whole leaf is
ever held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_PAD = 256


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    k = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0xFFFFFFFF)


def _normal(key, shape, scale, dtype, blocks: int, offset: float = 0.0):
    """N(offset, scale) of ``shape``, drawn ``blocks`` leading rows at a
    time so the float32 draw never spans the whole leaf."""
    assert shape[0] % blocks == 0, (shape, blocks)
    sub = (shape[0] // blocks,) + tuple(shape[1:])
    keys = jax.random.split(key, blocks)
    out = jax.lax.map(
        lambda k: (offset + scale * jax.random.normal(k, sub, jnp.float32)
                   ).astype(dtype), keys)
    return out.reshape(shape)


def _row_blocks(n: int, cap: int = 16) -> int:
    for b in range(cap, 0, -1):
        if n % b == 0:
            return b
    return 1
