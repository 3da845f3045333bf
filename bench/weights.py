"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program, so the plain
reference can use the very same arrays without taking anything the
program made.  They are laid out as the program's parameter tree
(pattern-stacked blocks, vocabulary padded to 256 rows) and drawn in
the served dtype.  Large leaves are drawn a block of rows at a time
(``lax.map``), so no float32 copy of a whole leaf is ever held.

Scales: embedding N(0, 1); every projection N(0, fan_in^-1/2); norm
weights 1 + N(0, 0.1); QKV biases N(0, 0.1).  Norm weights and biases
are not left at 1 and 0, so that a path that skipped them would show.
"""
from __future__ import annotations

import functools
from typing import Dict

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


def _build(hf: Dict, arch: Dict, dtype, key):
    d = int(hf["hidden_size"])
    hq, hkv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    hd = int(hf.get("head_dim") or d // hq)
    ff, n_layers = int(hf["intermediate_size"]), int(hf["num_hidden_layers"])
    pv = padded_vocab(int(hf["vocab_size"]))
    qd, kvd = hq * hd, hkv * hd

    ks = iter(jax.random.split(key, 16))
    L = n_layers

    def mat(shape, fan_in):
        return _normal(next(ks), (L,) + shape, fan_in ** -0.5, dtype, L)

    mixer = {"wq": mat((d, qd), d), "wk": mat((d, kvd), d),
             "wv": mat((d, kvd), d), "wo": mat((qd, d), qd)}
    if arch.get("qkv_bias"):
        for name, n in (("bq", qd), ("bk", kvd), ("bv", kvd)):
            mixer[name] = _normal(next(ks), (L, n), 0.1, dtype, 1)
    if arch.get("qk_norm"):
        for name in ("q_norm", "k_norm"):
            mixer[name] = _normal(next(ks), (L, hd), 0.1, dtype, 1, 1.0)
    block = {
        "ln1": _normal(next(ks), (L, d), 0.1, dtype, 1, 1.0),
        "mixer": mixer,
        "ln2": _normal(next(ks), (L, d), 0.1, dtype, 1, 1.0),
        "ffn": {"wg": mat((d, ff), d), "wi": mat((d, ff), d),
                "wo": mat((ff, d), ff)},
    }
    return {
        "embed": _normal(next(ks), (pv, d), 1.0, dtype,
                         _row_blocks(pv)),
        "blocks": [block],
        "final_norm": _normal(next(ks), (d,), 0.1, dtype, 1, 1.0),
        "lm_head": _normal(next(ks), (d, pv), d ** -0.5, dtype,
                           _row_blocks(d)),
    }


def make(hf: Dict, arch: Dict, seed: int, dtype=jnp.bfloat16):
    """The parameter tree for a dense GQA decoder (``hf``: published
    config keys; ``arch``: {qk_norm, qkv_bias}), drawn from ``seed``."""
    return jax.jit(functools.partial(_build, hf, arch, dtype))(
        seed_key(seed))


def shapes(hf: Dict, arch: Dict, dtype=jnp.bfloat16):
    """The tree's shapes, drawing nothing."""
    return jax.eval_shape(functools.partial(_build, hf, arch, dtype),
                          jax.random.key(0))
