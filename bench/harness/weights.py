"""Seeded weights, made on the device in one jitted call.

Every leaf is drawn from its own key, ``fold_in(seed key, crc32(path))``,
so its value depends only on the seed, its path and its shape: the served
model (whose tree comes from the program) and the reference (whose tree
comes from the configuration file) get the same numbers without sharing
an array.  The values follow the leaf's name:

  scale                  1 + 0.1 n     (norm gains)
  bias, b, b1, b2, b?    0.1 n         (biases and norm shifts)
  pos                    0.02 n        (learned patch positions)
  anything else          n / sqrt(shape[-2])   (weights, gate tables)

with ``n`` a standard normal draw.  Biases and norm parameters get noise,
not 0 and 1, so that a path that drops one gives another answer.
"""

from __future__ import annotations

import zlib
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["seed_words", "leaf_rule", "make_weights", "path_name"]

BIAS_NAMES = frozenset({"bias", "b", "b1", "b2", "bq", "bk", "bv"})


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two 32-bit words (JAX keys hold 32)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def path_name(path) -> str:
    """``layers/b1/moe/w1`` for a ``tree_flatten_with_path`` key path."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def leaf_rule(path: str, shape) -> tuple[float, float]:
    """(offset, std) of a leaf, from its name."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return 1.0, 0.1
    if name in BIAS_NAMES:
        return 0.0, 0.1
    if name == "pos":
        return 0.0, 0.02
    if len(shape) < 2:
        raise ValueError(f"{path}: no init rule for a {len(shape)}-d leaf")
    return 0.0, 1.0 / float(np.sqrt(shape[-2]))


def make_weights(spec: Mapping[str, tuple[tuple[int, ...], str]], seed: int):
    """{path: array} for ``spec`` {path: (shape, dtype)}, on the default
    device, from one jitted call whose arguments are the seed words (so
    every seed runs the same compiled program)."""
    paths = sorted(spec)

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out = {}
        for p in paths:
            shape, dtype = spec[p]
            off, std = leaf_rule(p, shape)
            k = jax.random.fold_in(key, zlib.crc32(p.encode()) & 0x7FFFFFFF)
            v = off + std * jax.random.normal(k, shape, jnp.float32)
            out[p] = v.astype(dtype)
        return out

    lo, hi = seed_words(seed)
    return jax.jit(build)(lo, hi)
