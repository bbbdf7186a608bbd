"""Bit-packed replica rows (core/bitrows.py) and the ADWISE scan on them.

The helpers are checked against the (V+1, K) bool table they stand for.
The scan is checked at partition counts on both sides of the 32-bit word
(K = 1, 5, 31, 32, 33, 100), on seeded streams with self-loops and
duplicate edges, on every path that carries the table:

- resident, file ring and two-instance spotlight against the benchmark's
  plain reference ADWISE (``bench/reference.py``, numpy, its own bool
  table), which in float32 reproduces the scan's assignment exactly;
- warm re-streaming passes, which the reference does not model, against
  the same scan run on an unpacked bool table.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AdwiseConfig,
    adwise,
    bitrows,
    partition_file,
    partition_stream,
    spotlight_partition,
    warm_from_assignment,
)
from repro.graph.io import EdgeFileReader, write_edge_file

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:  # `python -m pytest` adds cwd; be robust
    sys.path.insert(0, str(REPO_ROOT))

from bench import reference  # noqa: E402

KS = [1, 5, 31, 32, 33, 100]
N, M, WMAX = 60, 400, 16


def stream(seed: int) -> np.ndarray:
    """A seeded stream over few vertices (so repeats are common), with a
    self-loop every 17th edge and a copy of its predecessor every 23rd."""
    rng = np.random.default_rng(seed)
    e = rng.integers(0, N, (M, 2)).astype(np.int32)
    e[::17, 1] = e[::17, 0]
    e[5::23] = e[4::23][: len(e[5::23])]
    return e


def bool_table(rng, rows: int, k: int) -> np.ndarray:
    return rng.random((rows, k)) < 0.3


# ----------------------------------------------------------------------------
# The helpers against the bool table
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("k", KS)
def test_pack_unpack_round_trip(k):
    rows = bool_table(np.random.default_rng(k), 9, k)
    packed = bitrows.pack_np(rows)
    assert packed.dtype == np.uint32
    assert packed.shape == (9,) + bitrows.row_shape(k)
    np.testing.assert_array_equal(np.asarray(bitrows.pack(jnp.asarray(rows))),
                                  packed)
    np.testing.assert_array_equal(np.asarray(bitrows.unpack(jnp.asarray(packed), k)),
                                  rows)
    # Partition p is bit p % 32 of word p // 32; the pad bits are 0.
    words = packed.reshape(9, bitrows.words(k))
    p = np.arange(bitrows.words(k) * 32)
    bits = (words[:, p // 32] >> (p % 32).astype(np.uint32)) & 1
    np.testing.assert_array_equal(bits[:, :k], rows)
    assert not bits[:, k:].any()


@pytest.mark.parametrize("k", KS)
def test_get_and_set_bits_match_the_bool_table(k):
    rng = np.random.default_rng(100 + k)
    rows = bool_table(rng, 12, k)
    table = jnp.asarray(bitrows.pack_np(rows))
    vs = rng.integers(0, 12, 20)
    ps = rng.integers(0, k, 20)
    np.testing.assert_array_equal(
        np.asarray(bitrows.get_bit(table, jnp.asarray(vs), jnp.asarray(ps))),
        rows[vs, ps])
    # As in the scan: the rows set share no vertex, the rest name the dump.
    on = jnp.asarray(rng.random(8) < 0.5)
    vs = jnp.where(on, jnp.arange(8, dtype=jnp.int32), 11)
    ps = jnp.asarray(rng.integers(0, k, 8), jnp.int32)
    was, table = bitrows.set_bit(table, vs, ps, on)
    np.testing.assert_array_equal(np.asarray(was),
                                  rows[np.asarray(vs), np.asarray(ps)])
    want = jnp.asarray(rows).at[vs, ps].max(on)
    np.testing.assert_array_equal(np.asarray(bitrows.unpack(table, k)),
                                  np.asarray(want))


def test_self_loop_sets_its_bit_once():
    k, v = 40, 5
    table = bitrows.zeros(v + 1, k)
    u = jnp.array([2], jnp.int32)
    p = jnp.array([37], jnp.int32)
    on = jnp.array([True])
    # u == v: the step names the vertex twice with one partition.
    was, table = bitrows.set_bit(table, jnp.concatenate([u, u]),
                                 jnp.concatenate([p, p]),
                                 jnp.concatenate([on, on]))
    # Both entries read the bit before the step: the step counts a new
    # replica for each endpoint, as the bool table's reads did.
    assert not np.asarray(was).any()
    rows = np.asarray(bitrows.unpack(table, k))
    assert rows[2, 37] and rows.sum() == 1
    assert np.asarray(table)[2].tolist() == [0, 1 << 5]


@pytest.mark.parametrize("k", [5, 100])
def test_dump_row_stays_zero(k):
    v = 7
    table = jnp.asarray(bitrows.pack_np(np.ones((v + 1, k), bool)))
    table = table.at[v].set(0)
    # Unchosen slots all point at the dump row, with any partition.
    rows = jnp.array([v, v, 3, v], jnp.int32)
    ps = jnp.array([0, k - 1, 1, 2], jnp.int32)
    on = jnp.array([False, False, True, False])
    _, table = bitrows.set_bit(table, rows, ps, on)
    assert not np.asarray(table)[v].any()
    assert not bool(bitrows.get_bit(table, jnp.int32(v), jnp.int32(k - 1)))


# ----------------------------------------------------------------------------
# The scan on packed rows: every path, every word boundary
# ----------------------------------------------------------------------------

def reference_assign(edges: np.ndarray, k: int) -> np.ndarray:
    return reference.adwise_reference(edges, N, k, window_max=WMAX,
                                      dtype="float32")["assign"]


def file_of(tmp_path, edges: np.ndarray) -> str:
    path = os.path.join(tmp_path, "g.adw")
    write_edge_file(path, edges, N)
    return path


@pytest.mark.parametrize("path", ["resident", "file", "spotlight"])
@pytest.mark.parametrize("k", KS)
def test_packed_scan_matches_reference(tmp_path, path, k):
    edges = stream(k)
    cfg = AdwiseConfig(k=k, window_max=WMAX)
    if path == "resident":
        got = partition_stream(edges, N, cfg).assign
        want = reference_assign(edges, k)
    elif path == "file":
        with EdgeFileReader(file_of(tmp_path, edges)) as r:
            res = partition_file(r, "adwise", k, chunk_edges=64,
                                 spill_dir=str(tmp_path), window_max=WMAX)
            got = np.array(res.assign)
        want = reference_assign(edges, k)
    else:
        # Two instances as one batched program, each on its half of the
        # stream with every partition open: each half is its own ADWISE.
        got = spotlight_partition(edges, N, k, z=2, spread=k,
                                  strategy="adwise", cfg=cfg).assign
        half = M // 2
        want = np.concatenate([reference_assign(edges[:half], k),
                               reference_assign(edges[half:], k)])
    np.testing.assert_array_equal(got, want)


class BoolRows:
    """The replica table unpacked: (V+1, K) bool, one byte per bit."""

    @staticmethod
    def zeros(rows, k):
        return jnp.zeros((rows, k), bool)

    @staticmethod
    def pack_np(rows):
        return np.asarray(rows, bool)

    @staticmethod
    def unpack(rows, k):
        return rows

    @staticmethod
    def get_bit(table, rows, p):
        return table[rows, p]

    @staticmethod
    def set_bit(table, rows, p, on):
        return table[rows, p], table.at[rows, p].max(on)


def warm_passes(tmp_path, edges, k) -> list:
    """A warm re-streaming pass in memory, and a two-pass file re-stream."""
    os.makedirs(tmp_path, exist_ok=True)
    cfg = AdwiseConfig(k=k, window_max=WMAX)
    first = partition_stream(edges, N, cfg).assign
    warm = warm_from_assignment(edges, first, N, k)
    resident = partition_stream(edges, N, cfg, warm=warm).assign
    with EdgeFileReader(file_of(tmp_path, edges)) as r:
        res = partition_file(r, "adwise-restream", k, chunk_edges=64,
                             spill_dir=str(tmp_path / "rs"), window_max=WMAX,
                             passes=2, keep_best=False)
        ring = np.array(res.assign)
    return [resident, ring]


def on_bool_table(monkeypatch, run):
    """``run()`` on the packed table, then on the bool table."""
    packed = run()
    jax.clear_caches()  # the scan programs are cached by their static core
    monkeypatch.setattr(adwise, "bitrows", BoolRows)
    try:
        unpacked = run()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    return packed, unpacked


@pytest.mark.parametrize("k", KS)
def test_warm_restream_pass_matches_bool_table(tmp_path, monkeypatch, k):
    edges = stream(1000 + k)
    packed, unpacked = on_bool_table(
        monkeypatch, lambda: warm_passes(tmp_path / str(len(os.listdir(tmp_path))), edges, k))
    for got, want in zip(packed, unpacked):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k", [5, 33])
def test_batched_picks_match_bool_table(monkeypatch, k):
    """Several vertex-disjoint placements a step (``assign_batch`` 4)."""
    edges = stream(2000 + k)
    cfg = AdwiseConfig(k=k, window_max=32, assign_batch=4)
    packed, unpacked = on_bool_table(
        monkeypatch, lambda: partition_stream(edges, N, cfg).assign)
    np.testing.assert_array_equal(packed, unpacked)
