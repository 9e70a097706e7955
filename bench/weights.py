"""Seeded random weights, drawn on the device: what every architecture
module (``bench/arch/``) shares.

The benchmark owns the weights: an architecture module draws them from
``--seed`` and hands them to the program, and its plain reference draws
the same slices again after the program has been freed.  Every leaf is
drawn slice by slice along its first axis (one layer of a stacked leaf,
or ``ROW_BLOCK`` rows of an embedding table) from a key that depends
only on the seed, the leaf and the slice index, so a slice drawn alone
equals the same slice drawn inside the whole tree, bit for bit.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

#: rows of an embedding table drawn per slice (bounds the float32
#: temporary of a draw to ROW_BLOCK x d_model)
ROW_BLOCK = 8192


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed (more than 32 bits allowed)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def draw(leaf_key: jax.Array, index, spec: Tuple[Tuple[int, ...], str, float],
         dtype):
    """Slice ``index`` of the leaf keyed ``leaf_key``, in ``dtype``
    (traceable).  ``spec`` is (shape of one slice, init kind, scale): a
    "norm" slice is 1 + scale * N(0, 1), any other scale * N(0, 1)."""
    shape, kind, scale = spec
    z = jax.random.normal(jax.random.fold_in(leaf_key, index), shape,
                          jnp.float32)
    w = 1.0 + scale * z if kind == "norm" else scale * z
    return w.astype(dtype)


def table(block: Callable, vocab: int, rows: int, dtype):
    """An embedding table of ``rows`` rows: the first ``vocab`` drawn in
    ROW_BLOCK slices (``block(i)`` is slice ``i``), any rows past them
    zero."""
    n = -(-vocab // ROW_BLOCK)
    t = jax.lax.map(block, jnp.arange(n)).reshape(n * ROW_BLOCK, -1)[:vocab]
    if rows > vocab:
        t = jnp.concatenate([t, jnp.zeros((rows - vocab, t.shape[1]), dtype)])
    return t
