"""Bit-packed vertex rows: one bit per (vertex, partition), 32 to a word.

ADWISE's replica table holds, for every vertex, which of the K partitions
already hold a replica of it. As a ``(V+1, K)`` bool table it costs V·K
bytes; packed it is ``(V+1, words(K))`` uint32, 8x smaller. Layout:

- partition ``p`` is bit ``p % 32`` of word ``p // 32``;
- the pad bits of the last word are 0;
- a row of one word (K <= 32) drops its word axis: the table is ``(V+1,)``.
  The chip's compiler lays a ``(V+1, 1)`` table out apart from the
  ``(V+1,)`` tables beside it and re-lays it out on every scan step;
- a batched table adds a leading instance axis.

The scan reads whole rows (:func:`unpack` of gathered words) and tests and
sets single bits (:func:`get_bit`, :func:`set_bit`) on the carry's words.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BITS = 32

__all__ = ["BITS", "words", "row_shape", "zeros", "pack_np", "pack", "unpack", "get_bit",
           "set_bit"]


def words(k: int) -> int:
    """uint32 words per row of ``k`` bits."""
    return -(-int(k) // BITS)


def row_shape(k: int) -> tuple:
    """The shape of one packed row of ``k`` bits."""
    n = words(k)
    return () if n == 1 else (n,)


def zeros(rows: int, k: int) -> jax.Array:
    """An empty table of ``rows`` rows of ``k`` bits."""
    return jnp.zeros((rows,) + row_shape(k), jnp.uint32)


def pack_np(rows: np.ndarray) -> np.ndarray:
    """``(..., K)`` bool → ``(..., *row_shape(K))`` uint32, on the host."""
    rows = np.asarray(rows, bool)
    k = rows.shape[-1]
    pad = words(k) * BITS - k
    if pad:
        rows = np.concatenate(
            [rows, np.zeros(rows.shape[:-1] + (pad,), bool)], axis=-1)
    # Little bit order puts partition 8j+i at bit i of byte j; four bytes
    # read little-endian are then the word of partitions 32w .. 32w+31.
    packed = np.packbits(rows, axis=-1, bitorder="little")
    packed = np.ascontiguousarray(packed).view("<u4").astype(np.uint32)
    return packed.reshape(rows.shape[:-1] + row_shape(k))


def pack(rows: jax.Array) -> jax.Array:
    """``(..., K)`` bool → ``(..., *row_shape(K))`` uint32, on the device."""
    k = rows.shape[-1]
    pad = words(k) * BITS - k
    bits = jnp.pad(rows.astype(jnp.uint32),
                   [(0, 0)] * (rows.ndim - 1) + [(0, pad)])
    bits = bits.reshape(rows.shape[:-1] + (words(k), BITS))
    shifts = jnp.arange(BITS, dtype=jnp.uint32)
    packed = jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)
    return packed.reshape(rows.shape[:-1] + row_shape(k))


def unpack(row_words: jax.Array, k: int) -> jax.Array:
    """``(..., *row_shape(k))`` uint32 → ``(..., k)`` bool."""
    lead = row_words.shape[:row_words.ndim - len(row_shape(k))]
    shifts = jnp.arange(BITS, dtype=jnp.uint32)
    bits = (row_words.reshape(lead + (words(k), 1)) >> shifts) & jnp.uint32(1)
    return bits.reshape(lead + (-1,))[..., :k].astype(bool)


def _word_and_bit(table: jax.Array, rows: jax.Array, p: jax.Array) -> tuple:
    """The index of the word that holds bit ``p`` of each row, and the bit."""
    p = p.astype(jnp.int32)
    at = rows if table.ndim == 1 else (rows, p >> 5)
    return at, jnp.uint32(1) << (p & (BITS - 1)).astype(jnp.uint32)


def get_bit(table: jax.Array, rows: jax.Array, p: jax.Array) -> jax.Array:
    """Bit ``p`` of each row in ``rows``: ``table[rows, p]`` of the bool
    table. ``rows`` and ``p`` broadcast together."""
    at, bit = _word_and_bit(table, rows, p)
    return (table[at] & bit) != 0


def set_bit(table: jax.Array, rows: jax.Array, p: jax.Array,
            on: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(was, table)``: bit ``p`` of each row as it was (:func:`get_bit`),
    and ``table`` with that bit set where ``on`` (the bool table's
    ``table.at[rows, p].max(on)``).

    Entries that name one word must set one and the same bit, or none: a
    self-loop names its vertex twice with one partition, and rows where
    ``on`` is False (the scatter dump) write their word back unchanged.
    """
    at, bit = _word_and_bit(table, rows, p)
    word = table[at]
    return (word & bit) != 0, table.at[at].max(
        word | jnp.where(on, bit, jnp.uint32(0)))
