"""Out-of-core I/O subsystem: binary format, text ingest, external shuffle,
EdgeStream bridges, chunked metric accumulation."""
import os
import struct

import numpy as np
import pytest

from repro.graph import (
    EdgeStream,
    make_graph,
    partition_balance,
    quality_from_chunks,
    replica_sets_from_assignment,
    replica_sets_from_chunks,
    replication_degree,
    rmat,
    sync_volume,
)
from repro.graph.io import (
    HEADER_BYTES,
    MAGIC,
    EdgeFileReader,
    EdgeFileWriter,
    ingest_text,
    read_edge_file,
    shuffle_file,
    write_edge_file,
)

from conftest import random_edges


@pytest.fixture(scope="module")
def graph_file(tmp_path_factory):
    edges, n = make_graph("tiny_social", seed=4)
    path = str(tmp_path_factory.mktemp("io") / "g.adw")
    write_edge_file(path, edges, n)
    return path, edges, n


# ----------------------------------------------------------------------------
# Binary format
# ----------------------------------------------------------------------------

def test_binary_roundtrip(graph_file):
    path, edges, n = graph_file
    with EdgeFileReader(path) as r:
        assert r.num_edges == len(edges)
        assert r.num_vertices == n
        assert (r.read_all() == edges).all()
        # Bounded-chunk iteration reconstructs the stream.
        cat = np.concatenate(list(r.chunks(251)))
        assert (cat == edges).all()
        # Random-access row ranges, clipped at both ends.
        assert (r.read(100, 37) == edges[100:137]).all()
        assert r.read(len(edges) - 3, 100).shape == (3, 2)
        assert r.read(len(edges) + 5, 10).shape == (0, 2)


def test_reader_mmap_mode(graph_file):
    path, edges, _ = graph_file
    with EdgeFileReader(path, mmap=True) as r:
        assert (r.read_all() == edges).all()
        assert (r.read(7, 9) == edges[7:16]).all()


def test_sub_readers_match_split_bounds(graph_file):
    path, edges, n = graph_file
    m = len(edges)
    for z in (1, 3, 7):
        bounds = EdgeStream.split_bounds(m, z)
        with EdgeFileReader(path) as r:
            subs = r.split(z)
            assert len(subs) == z
            for i, s in enumerate(subs):
                lo, hi = int(bounds[i]), int(bounds[i + 1])
                assert s.num_edges == hi - lo
                assert (s.read_all() == edges[lo:hi]).all()
                # Nested sub-ranges address locally.
                if s.num_edges >= 2:
                    assert (s.sub(1, s.num_edges).read_all() == edges[lo + 1 : hi]).all()


def test_reader_io_accounting(graph_file):
    path, edges, _ = graph_file
    with EdgeFileReader(path) as r:
        subs = r.split(2)
        for s in subs:
            for _ in s.chunks(100):
                pass
        # Sub-reader IO flows to the root counters.
        assert r.rows_read == len(edges)
        assert r.read_seconds >= 0.0


def test_writer_streams_and_infers_n(tmp_path):
    path = str(tmp_path / "w.adw")
    rng = np.random.default_rng(0)
    chunks = [random_edges(rng, 50, 40) for _ in range(5)]
    with EdgeFileWriter(path) as w:
        for c in chunks:
            w.append(c)
    all_edges = np.concatenate(chunks)
    got, n = read_edge_file(path)
    assert (got == all_edges).all()
    assert n == int(all_edges.max()) + 1


def test_version_and_magic_rejection(tmp_path):
    header_fmt = "<8sIIQQQ"
    bad_version = str(tmp_path / "v99.adw")
    with open(bad_version, "wb") as f:
        f.write(struct.pack(header_fmt, MAGIC, 99, 1, 0, 0, 0).ljust(HEADER_BYTES, b"\0"))
    with pytest.raises(ValueError, match="version 99"):
        EdgeFileReader(bad_version)

    bad_magic = str(tmp_path / "magic.adw")
    with open(bad_magic, "wb") as f:
        f.write(struct.pack(header_fmt, b"NOTADWSE", 1, 1, 0, 0, 0).ljust(HEADER_BYTES, b"\0"))
    with pytest.raises(ValueError, match="not an ADWISE"):
        EdgeFileReader(bad_magic)

    bad_dtype = str(tmp_path / "dtype.adw")
    with open(bad_dtype, "wb") as f:
        f.write(struct.pack(header_fmt, MAGIC, 1, 7, 0, 0, 0).ljust(HEADER_BYTES, b"\0"))
    with pytest.raises(ValueError, match="dtype"):
        EdgeFileReader(bad_dtype)

    truncated = str(tmp_path / "trunc.adw")
    with open(truncated, "wb") as f:
        f.write(struct.pack(header_fmt, MAGIC, 1, 1, 1000, 10, 0).ljust(HEADER_BYTES, b"\0"))
        f.write(b"\0" * 16)  # 2 rows of payload, header claims 1000
    with pytest.raises(ValueError, match="truncated"):
        EdgeFileReader(truncated)

    short = str(tmp_path / "short.adw")
    with open(short, "wb") as f:
        f.write(b"ADW")
    with pytest.raises(ValueError, match="truncated header"):
        EdgeFileReader(short)


# ----------------------------------------------------------------------------
# Text ingest
# ----------------------------------------------------------------------------

_ADVERSARIAL = """# SNAP-style comment
% matrix-market-style comment
// c-style comment

5\t7
  7   5
3 3
5 7 99 extra fields ignored

\t
9\t2
"""


def test_ingest_adversarial(tmp_path):
    src = str(tmp_path / "adv.txt")
    dst = str(tmp_path / "adv.adw")
    with open(src, "w") as f:
        f.write(_ADVERSARIAL)
    rep = ingest_text(src, dst)
    edges, n = read_edge_file(dst)
    # Self-loop and the duplicate (5,7) are preserved: the file IS the stream.
    expect = np.array([[5, 7], [7, 5], [3, 3], [5, 7], [9, 2]], np.int32)
    assert (edges == expect).all()
    assert n == 10  # max id + 1 inferred
    assert rep.comment_lines == 3
    assert rep.blank_lines == 3  # empty line, whitespace-only line, trailing
    assert rep.num_edges == 5


def test_ingest_relabel_dense_first_appearance(tmp_path):
    src = str(tmp_path / "sparse.txt")
    dst = str(tmp_path / "sparse.adw")
    with open(src, "w") as f:
        f.write("1000000 42\n42 -3\n1000000 7\n")
    with pytest.raises(ValueError, match="negative"):
        ingest_text(src, dst)
    rep = ingest_text(src, dst, relabel=True)
    edges, n = read_edge_file(dst)
    # Dense ids in first-appearance order: 1000000->0, 42->1, -3->2, 7->3.
    assert (edges == np.array([[0, 1], [1, 2], [0, 3]])).all()
    assert n == 4 and rep.num_vertices == 4


def test_ingest_malformed_line_reports_position(tmp_path):
    src = str(tmp_path / "bad.txt")
    dst = str(tmp_path / "bad.adw")
    with open(src, "w") as f:
        f.write("1 2\n# ok\nonly_one_field\n")
    with pytest.raises(ValueError, match=r"bad\.txt:3"):
        ingest_text(src, dst)
    # A failed ingest must not leave a valid-looking truncated binary behind.
    assert not os.path.exists(dst)
    with open(src, "w") as f:
        f.write("1 2\n3 notanint\n")
    with pytest.raises(ValueError, match=r"bad\.txt:2"):
        ingest_text(src, dst)
    assert not os.path.exists(dst)


def test_writer_abort_on_exception(tmp_path):
    path = str(tmp_path / "partial.adw")
    with pytest.raises(RuntimeError):
        with EdgeFileWriter(path) as w:
            w.append(np.array([[0, 1]], np.int32))
            raise RuntimeError("body failed")
    assert not os.path.exists(path)


def test_ingest_chunking_invariance(tmp_path):
    """The chunk_lines bound never changes the output stream."""
    rng = np.random.default_rng(5)
    edges = random_edges(rng, 40, 200)
    src = str(tmp_path / "c.txt")
    with open(src, "w") as f:
        for i, (u, v) in enumerate(edges):
            if i % 17 == 0:
                f.write("# interleaved comment\n")
            f.write(f"{u} {v}\n")
    outs = []
    for chunk_lines in (3, 64, 10_000):
        dst = str(tmp_path / f"c{chunk_lines}.adw")
        ingest_text(src, dst, chunk_lines=chunk_lines)
        outs.append(read_edge_file(dst))
    for got, n in outs:
        assert (got == edges).all()
        assert n == outs[0][1]
    # Relabeled: the incremental id table must give the same global
    # first-appearance mapping for every chunking.
    relabeled = []
    for chunk_lines in (3, 10_000):
        dst = str(tmp_path / f"r{chunk_lines}.adw")
        ingest_text(src, dst, relabel=True, chunk_lines=chunk_lines)
        relabeled.append(read_edge_file(dst))
    assert (relabeled[0][0] == relabeled[1][0]).all()
    assert relabeled[0][1] == relabeled[1][1]
    # And the mapping is first-appearance order: sequential dense ids.
    flat = relabeled[0][0].reshape(-1)
    first_seen = flat[np.sort(np.unique(flat, return_index=True)[1])]
    assert (first_seen == np.arange(relabeled[0][1])).all()


def test_ingest_pinned_num_vertices(tmp_path):
    src = str(tmp_path / "p.txt")
    dst = str(tmp_path / "p.adw")
    with open(src, "w") as f:
        f.write("0 1\n1 2\n")
    ingest_text(src, dst, num_vertices=500)
    _, n = read_edge_file(dst)
    assert n == 500
    # Ids beyond a pinned n fail at ingest time, not at partition time.
    with pytest.raises(ValueError, match="pinned num_vertices"):
        ingest_text(src, dst, num_vertices=2)


# ----------------------------------------------------------------------------
# External shuffle
# ----------------------------------------------------------------------------

def test_shuffle_is_permutation_and_deterministic(graph_file, tmp_path):
    path, edges, n = graph_file
    a = str(tmp_path / "a.adw")
    b = str(tmp_path / "b.adw")
    shuffle_file(path, a, seed=3, chunk_edges=300)
    shuffle_file(path, b, seed=3, chunk_edges=300)
    got_a, n_a = read_edge_file(a)
    got_b, _ = read_edge_file(b)
    assert n_a == n
    assert (got_a == got_b).all(), "same seed must give the same permutation"
    assert got_a.shape == edges.shape
    assert not (got_a == edges).all(), "shuffle must not be the identity"
    order = lambda e: e[np.lexsort((e[:, 1], e[:, 0]))]
    assert (order(got_a) == order(edges)).all(), "rows must be a permutation"
    c = str(tmp_path / "c.adw")
    shuffle_file(path, c, seed=4, chunk_edges=300)
    got_c, _ = read_edge_file(c)
    assert not (got_c == got_a).all(), "different seeds, different permutation"


def test_shuffle_recursive_buckets(graph_file, tmp_path, monkeypatch):
    """With the open-file cap forced to 2, buckets overflow the chunk budget
    and must be re-scattered recursively — still a uniform permutation."""
    import repro.graph.io.shuffle as sh

    monkeypatch.setattr(sh, "_MAX_OPEN", 2)
    path, edges, _ = graph_file
    out = str(tmp_path / "rec.adw")
    shuffle_file(path, out, seed=9, chunk_edges=150)
    got, _ = read_edge_file(out)
    order = lambda e: e[np.lexsort((e[:, 1], e[:, 0]))]
    assert (order(got) == order(edges)).all()
    assert not (got == edges).all()


# ----------------------------------------------------------------------------
# EdgeStream bridges + the NpzFile leak fix
# ----------------------------------------------------------------------------

def test_edgestream_file_bridges(tmp_path, tiny_social):
    edges, n = tiny_social
    stream = EdgeStream(edges, n)
    p = str(tmp_path / "bridge.adw")
    stream.to_file(p)
    back = EdgeStream.from_file(p)
    assert back.num_vertices == n and (back.edges == stream.edges).all()


def test_edgestream_load_owns_arrays(tmp_path, tiny_social):
    """`load` copies out of the NpzFile under a context manager: the handle
    is closed and the returned arrays are owned (mutable, no lazy backing)."""
    edges, n = tiny_social
    p = str(tmp_path / "s.npz")
    EdgeStream(edges, n).save(p)
    loaded = EdgeStream.load(p)
    assert (loaded.edges == EdgeStream(edges, n).edges).all()
    # Owned data: mutating must not raise and must not touch the file.
    loaded.edges[0, 0] = 123
    again = EdgeStream.load(p)
    assert again.edges[0, 0] != 123 or edges[0, 0] == 123


# ----------------------------------------------------------------------------
# Chunked metric accumulation
# ----------------------------------------------------------------------------

def test_chunked_metrics_match_in_memory(graph_file):
    path, edges, n = graph_file
    k = 8
    rng = np.random.default_rng(0)
    assign = rng.integers(0, k, len(edges)).astype(np.int32)
    ref_rep = replica_sets_from_assignment(edges, assign, n, k)
    with EdgeFileReader(path) as r:
        pairs = (
            (chunk, assign[s : s + len(chunk)])
            for s, chunk in zip(range(0, len(edges), 301), r.chunks(301))
        )
        rep = replica_sets_from_chunks(pairs, n, k)
    assert (rep == ref_rep).all()

    with EdgeFileReader(path) as r:
        pairs = (
            (chunk, assign[s : s + len(chunk)])
            for s, chunk in zip(range(0, len(edges), 301), r.chunks(301))
        )
        q = quality_from_chunks(pairs, n, k)
    assert q["replication_degree"] == replication_degree(ref_rep)
    assert q["imbalance"] == partition_balance(assign, k)
    assert q["unassigned"] == 0


@pytest.mark.parametrize("unassigned", ["raise", "drop"])
def test_chunked_metrics_on_sparse_vertex_table(unassigned):
    """Most of the (V, k) table untouched (isolated vertices, a prefix of a
    stream), self-loops and repeated edges: the numbers read from the touched
    rows equal those of the whole table."""
    rng = np.random.default_rng(7)
    n, k, m = 5000, 37, 400
    edges = rng.integers(0, 300, (m, 2)).astype(np.int32) * 13
    edges[::9, 1] = edges[::9, 0]
    edges[1::11] = edges[0]
    assign = rng.integers(0, k, m).astype(np.int32)
    if unassigned == "drop":
        assign[::7] = -1
    pairs = ((edges[s : s + 64], assign[s : s + 64]) for s in range(0, m, 64))
    q = quality_from_chunks(pairs, n, k, unassigned=unassigned)
    ref_rep = replica_sets_from_assignment(edges, assign, n, k,
                                           unassigned=unassigned)
    assert (q["replicas"] == ref_rep).all()
    assert q["replication_degree"] == replication_degree(ref_rep)
    assert q["sync_volume"] == sync_volume(ref_rep) > 0


def test_chunked_metrics_unassigned_policies(graph_file):
    path, edges, n = graph_file
    k = 4
    assign = np.zeros(len(edges), np.int32)
    assign[::5] = -1
    with EdgeFileReader(path) as r:
        pairs = ((c, assign[s : s + len(c)])
                 for s, c in zip(range(0, len(edges), 200), r.chunks(200)))
        with pytest.raises(ValueError, match="unassigned"):
            replica_sets_from_chunks(pairs, n, k)
    with EdgeFileReader(path) as r:
        pairs = ((c, assign[s : s + len(c)])
                 for s, c in zip(range(0, len(edges), 200), r.chunks(200)))
        q = quality_from_chunks(pairs, n, k, unassigned="drop")
    assert q["unassigned"] == int((assign < 0).sum())


def test_rmat_roundtrip_property():
    """Random R-MAT graphs survive the write→read round trip bit-for-bit."""
    for seed in range(3):
        import tempfile

        edges, n = rmat(8, 500, seed=seed)
        with tempfile.TemporaryDirectory() as td:
            p = os.path.join(td, "r.adw")
            write_edge_file(p, edges, n)
            got, n2 = read_edge_file(p)
            assert n2 == n and (got == edges).all()


# ----------------------------------------------------------------------------
# Vectorized bytes-level ingester vs the per-line parity oracle
# ----------------------------------------------------------------------------


def _ingest_both(tmp_path, content, name="p", newline="", **kw):
    """Run both parsers over the same text; assert identical outcome."""
    src = str(tmp_path / f"{name}.txt")
    with open(src, "w", newline=newline) as f:
        f.write(content)
    outcomes = []
    for parser in ("python", "bytes"):
        dst = str(tmp_path / f"{name}.{parser}.adw")
        try:
            rep = ingest_text(src, dst, parser=parser, **kw)
            outcomes.append(("ok", rep, read_edge_file(dst)))
        except ValueError as e:
            outcomes.append(("err", str(e).replace(src, "SRC"), None))
    (k1, a1, d1), (k2, a2, d2) = outcomes
    assert k1 == k2, f"{content!r}: python={k1} bytes={k2} ({a1} / {a2})"
    if k1 == "err":
        assert a1 == a2, f"{content!r}: error messages diverged"
        return None
    (e1, n1), (e2, n2) = d1, d2
    assert (e1 == e2).all() and n1 == n2, f"{content!r}: binaries diverged"
    for field in ("num_edges", "num_vertices", "lines", "comment_lines",
                  "blank_lines", "bytes_read", "relabeled"):
        assert getattr(a1, field) == getattr(a2, field), (content, field)
    return a2


def test_ingest_bytes_parser_parity(tmp_path):
    """The vectorized parser reproduces the reference parser bit-for-bit on
    every supported shape: comments (all three prefixes, interleaved),
    blanks, tabs/multi-space, trailing fields, CRLF, a missing final
    newline, and negative ids under relabel."""
    rng = np.random.default_rng(11)
    body = []
    for i, (u, v) in enumerate(random_edges(rng, 300, 900)):
        sep = ["\t", " ", "  ", " \t "][i % 4]
        trail = " 7 0" if i % 5 == 0 else ""
        body.append(f"{u}{sep}{v}{trail}")
        if i % 97 == 0:
            body.append("")
        if i % 131 == 0:
            body.append(["# note", "% note", "// note"][i % 3])
    content = "# header\n% header2\n// header3\n" + "\n".join(body) + "\n"
    rep = _ingest_both(tmp_path, content, name="mixed")
    assert rep.comment_lines >= 3 and rep.blank_lines > 0
    # Pure-clean body (tier-0 C tokenizer end to end).
    clean = "\n".join(f"{u} {v}" for u, v in random_edges(rng, 99, 500))
    _ingest_both(tmp_path, clean + "\n", name="clean")
    # CRLF and a file without a trailing newline.
    _ingest_both(tmp_path, "1 2\r\n3 4\r\n5 6", name="crlf")
    # Lone-\r terminators (classic-Mac; text mode treats them as newlines).
    _ingest_both(tmp_path, "1 2\r3 4\r# c\r5 6", name="mac")
    # Signed / exotic-but-int()-valid tokens ride the python fallback.
    _ingest_both(tmp_path, "+1 2\n3 +4\n", name="plus")
    _ingest_both(tmp_path, "-3 -9\n-9 -3\n", name="neg", relabel=True)
    # Empty and comment-only files.
    _ingest_both(tmp_path, "", name="empty")
    _ingest_both(tmp_path, "# a\n\n% b\n", name="comments_only")
    # Valid non-ASCII text (accented comment, unicode NBSP separator —
    # str.split() treats it as whitespace) parses identically.
    _ingest_both(tmp_path, "# café\n1 2\n3 4\n", name="unicode")


def test_ingest_bytes_parser_rejects_invalid_utf8(tmp_path):
    """The text-mode reference decodes the whole file; the bytes parser
    must fail on undecodable bytes exactly like it (not silently ingest)."""
    src = str(tmp_path / "latin1.txt")
    with open(src, "wb") as f:
        f.write(b"# caf\xe9 header\n1 2\n3 4\n")
    for parser in ("python", "bytes"):
        with pytest.raises(UnicodeDecodeError):
            ingest_text(src, str(tmp_path / f"{parser}.adw"), parser=parser)


def test_ingest_bytes_parser_error_parity(tmp_path):
    """Malformed inputs raise the exact reference error from every tier."""
    for i, content in enumerate([
        "1 2\n3\n",                      # too few fields
        "1 2\nx y\n",                    # non-integer
        "1 2\n3 4.5\n",                  # float id
        "-1 5\n",                        # negative without relabel
        "99999999999999999999 1\n",      # > int64 (overflow both parsers)
        "1 2\n- 3\n",                    # lone dash
        "1 2\r3 4\n5 6\nx y\n",          # lone-\r line before the bad line:
                                         # the reported line number must
                                         # count it (universal newlines)
    ]):
        assert _ingest_both(tmp_path, content, name=f"bad{i}") is None


def test_ingest_id_policy_errors_report_exact_line(tmp_path):
    """Negative-id / pinned-n violations point at the offending line itself
    (not a batch or block start), identically for both parsers and any
    batching."""
    lines = [f"{i} {i + 1}" for i in range(100)]
    lines[86] = "5 -7"  # line 87 (1-based)
    src = str(tmp_path / "neg.txt")
    with open(src, "w") as f:
        f.write("\n".join(lines) + "\n")
    for parser, kw in [("python", dict(chunk_lines=30)),
                       ("python", {}),
                       ("bytes", dict(chunk_bytes=256)),
                       ("bytes", {})]:
        with pytest.raises(ValueError, match="near line 87"):
            ingest_text(src, str(tmp_path / "o.adw"), parser=parser, **kw)
    # Multiple violations of different types/magnitudes: the FIRST one in
    # stream order wins, for every parser and every batch/block granularity
    # (argmin/argmax would pick the most extreme value instead, which
    # diverges once the violations straddle a batch boundary).
    lines2 = [f"{i} {i + 1}" for i in range(60)]
    lines2[9] = "5 -1"    # first violation (line 10)
    lines2[44] = "-99 5"  # more extreme, later
    src3 = str(tmp_path / "two.txt")
    with open(src3, "w") as f:
        f.write("\n".join(lines2) + "\n")
    for parser, kw in [("python", dict(chunk_lines=30)), ("python", {}),
                       ("bytes", dict(chunk_bytes=128)), ("bytes", {})]:
        with pytest.raises(ValueError, match="id -1 near line 10"):
            ingest_text(src3, str(tmp_path / "o3.adw"), parser=parser, **kw)
    # Pinned-n violation, with comments/blanks shifting the data-row index.
    content = "# head\n\n10 11\n999 1\n"
    src2 = str(tmp_path / "pin.txt")
    with open(src2, "w") as f:
        f.write(content)
    for parser in ("python", "bytes"):
        with pytest.raises(ValueError, match="near line 4"):
            ingest_text(src2, str(tmp_path / "o2.adw"), parser=parser,
                        num_vertices=100)


def test_ingest_bytes_chunking_invariance(tmp_path):
    """Block boundaries never change the fast parser's output."""
    rng = np.random.default_rng(3)
    edges = random_edges(rng, 50, 400)
    content = "# head\n" + "\n".join(f"{u} {v}" for u, v in edges) + "\n"
    src = str(tmp_path / "blk.txt")
    with open(src, "w") as f:
        f.write(content)
    outs = []
    for cb in (16, 301, 1 << 20):
        dst = str(tmp_path / f"blk{cb}.adw")
        ingest_text(src, dst, parser="bytes", chunk_bytes=cb)
        outs.append(read_edge_file(dst))
    for got, n in outs:
        assert (got == edges).all() and n == outs[0][1]


# ----------------------------------------------------------------------------
# External shuffle: the hard O(chunk) bucket bound
# ----------------------------------------------------------------------------


def test_shuffle_hard_bound_adversarial(tmp_path):
    """An adversarially skewed stream (one dominant edge, sorted tail) with
    a tiny open-file budget must recurse — and every in-memory bucket load
    stays within the hard 2x-chunk bound, proven by the returned report."""
    m, chunk = 6000, 64
    skew = np.zeros((m // 2, 2), np.int32)          # one repeated edge
    tail = np.stack([np.arange(m - m // 2), np.arange(m - m // 2)], 1)
    edges = np.concatenate([skew, tail.astype(np.int32)])
    src = str(tmp_path / "skew.adw")
    write_edge_file(src, edges, int(edges.max()) + 1)
    dst = str(tmp_path / "skew_shuf.adw")
    rep = shuffle_file(src, dst, seed=5, chunk_edges=chunk, max_open=2)
    assert rep.depth >= 2, "tiny max_open must force recursive re-splits"
    assert rep.max_loaded_rows <= rep.bound_rows == 2 * chunk
    got, _ = read_edge_file(dst)
    order = lambda e: e[np.lexsort((e[:, 1], e[:, 0]))]
    assert (order(got) == order(edges)).all()
    assert not (got == edges).all()
    # Deterministic in seed.
    dst2 = str(tmp_path / "skew_shuf2.adw")
    rep2 = shuffle_file(src, dst2, seed=5, chunk_edges=chunk, max_open=2)
    got2, _ = read_edge_file(dst2)
    assert (got == got2).all()
    assert rep2.max_loaded_rows == rep.max_loaded_rows


def test_shuffle_rejects_degenerate_fanout(tmp_path):
    src = str(tmp_path / "x.adw")
    write_edge_file(src, np.zeros((10, 2), np.int32), 1)
    with pytest.raises(ValueError, match="max_open"):
        shuffle_file(src, str(tmp_path / "y.adw"), max_open=1)


def test_shuffle_report_default_path(graph_file, tmp_path):
    path, edges, _ = graph_file
    rep = shuffle_file(path, str(tmp_path / "s.adw"), seed=1, chunk_edges=300)
    assert rep.num_edges == len(edges)
    assert 0 < rep.max_loaded_rows <= rep.bound_rows
    assert rep.buckets >= 1 and rep.depth >= 0


# ----------------------------------------------------------------------------
# Graph500 Kronecker generator: the cut is the file's prefix
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("max_edges", [1, 777, 16 * 256 - 1, 10 ** 6])
def test_kronecker_cut_is_prefix_of_full_file(max_edges):
    from repro.graph import kronecker

    full, n = kronecker(8, seed=3)
    cut, n_cut = kronecker(8, seed=3, max_edges=max_edges)
    assert n == n_cut == 256 and len(full) == 16 * 256
    np.testing.assert_array_equal(cut, full[:max_edges])


def test_kronecker_is_graph500_shaped():
    from repro.graph import kronecker

    e, n = kronecker(10, seed=0)
    assert n == 1024 and e.shape == (16 * 1024, 2) and e.dtype == np.int32
    assert (e >= 0).all() and (e < n).all()
    assert (np.diff(e[:, 0]) >= 0).all()  # file order: sorted by source
    deg = np.bincount(e.ravel(), minlength=n)
    assert deg.max() > 20 * deg.mean()  # the initiator's skew survives
    assert not np.array_equal(e, kronecker(10, seed=1)[0])
