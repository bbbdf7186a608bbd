"""Pallas kernels vs pure-jnp oracles across the kernel-tier ladder.

The dispatch layer (`kernels/ops.py`) resolves every op to a tier that can
genuinely run (`xla` / lowered pallas); `interpret` is an explicit debug
request. Parity is asserted tier-by-tier: every tier the install can run —
plus interpret where pallas exists at all — must agree with the `xla`
reference within documented fp tolerance, and the pure-mask paths (invalid
rows / disallowed columns) must be bit-identical NEG_INF everywhere.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.segment_sum import csr_block_layout, segment_sum_xla, EB, SB

def _tiers_under_test(op: str) -> list:
    """Every runnable tier, plus the explicit interpret debug tier."""
    return [*ops.available_tiers(op), ops.INTERPRET_TIER]


# ----------------------------------------------------------------------------
# window_score
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("w,k,use_cs", [
    (1, 2, True), (7, 3, True), (128, 32, True), (200, 20, True),
    (130, 64, False), (64, 5, False),
])
def test_window_score_shapes(w, k, use_cs):
    rng = np.random.default_rng(w * 31 + k)
    v = 200
    uv = rng.integers(0, v, (w, 2)).astype(np.int32)
    valid = rng.random(w) < 0.85
    repu = rng.random((w, k)) < 0.2
    repv = rng.random((w, k)) < 0.2
    degu = rng.integers(1, 40, w).astype(np.int32)
    degv = rng.integers(1, 40, w).astype(np.int32)
    bal = rng.random(k).astype(np.float32)
    allowed = rng.random(k) < 0.9
    args = (uv, valid, repu, repv, degu, degv, bal, allowed,
            jnp.float32(1.3), jnp.int32(40))
    b = ops.window_score(*args, use_cs=use_cs, tier="xla")
    for tier in _tiers_under_test("window_score"):
        a = ops.window_score(*args, use_cs=use_cs, tier=tier)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
            err_msg=f"tier={tier}")
        # Masked (invalid-row / disallowed-col) entries are produced by the
        # same jnp.where(..., NEG_INF) on every tier: bit-identical.
        mask = (~valid)[:, None] | (~allowed)[None, :]
        np.testing.assert_array_equal(
            np.asarray(a)[mask], np.asarray(b)[mask], err_msg=f"tier={tier}")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), w=st.integers(1, 80), k=st.integers(1, 40))
def test_window_score_property(seed, w, k):
    rng = np.random.default_rng(seed)
    uv = rng.integers(0, 50, (w, 2)).astype(np.int32)
    valid = rng.random(w) < 0.7
    repu = rng.random((w, k)) < 0.3
    repv = rng.random((w, k)) < 0.3
    degu = rng.integers(1, 10, w).astype(np.int32)
    degv = rng.integers(1, 10, w).astype(np.int32)
    bal = rng.random(k).astype(np.float32)
    allowed = np.ones(k, bool)
    args = (uv, valid, repu, repv, degu, degv, bal, allowed,
            jnp.float32(0.7), jnp.int32(10))
    a = np.asarray(ops.window_score(*args, tier=ops.INTERPRET_TIER))
    b = np.asarray(ops.window_score(*args, tier="xla"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # Masking invariant: invalid rows / disallowed cols are -inf-ish.
    assert (a[~valid] < -1e29).all()


# ----------------------------------------------------------------------------
# segment_sum
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("e,d,s,dtype", [
    (10, 8, 5, np.float32), (1000, 64, 300, np.float32),
    (3000, 32, 700, np.float32), (513, 128, 129, np.float32),
    (2048, 16, 256, np.float16),
])
def test_segment_sum_shapes(e, d, s, dtype):
    rng = np.random.default_rng(e + d)
    seg = np.sort(rng.integers(0, s, e)).astype(np.int32)
    data = rng.normal(size=(e, d)).astype(dtype)
    # Oracle in fp32: the kernel accumulates in fp32 regardless of input dtype
    # (MXU-style mixed precision), so compare against the fp32 reference.
    b = ops.segment_sum_sorted(jnp.asarray(data, jnp.float32), seg, s, tier="xla")
    for tier in _tiers_under_test("segment_sum"):
        a = ops.segment_sum_sorted(jnp.asarray(data), seg, s, tier=tier)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-3, err_msg=f"tier={tier}")


@pytest.mark.parametrize("e,d,s", [
    (10, 8, 5), (1000, 64, 300), (513, 16, 129), (3000, 32, 700),
])
def test_segment_sum_xla_fast_path_parity(e, d, s):
    """jax.ops.segment_sum over the blocked CSR layout agrees with the plain
    sorted-segment reference: the layout itself is right, before any
    kernel reads it. Needs no pallas at all."""
    rng = np.random.default_rng(e * 13 + d)
    seg = np.sort(rng.integers(0, s, e)).astype(np.int32)
    data = rng.normal(size=(e, d)).astype(np.float32)
    perm, loc, chunk_ptr, nchunks, e_pad = csr_block_layout(seg, s, d)
    gather = np.where(perm[:, None] >= 0, data[np.maximum(perm, 0)], 0.0)
    a = segment_sum_xla(
        jnp.asarray(gather, jnp.float32), jnp.asarray(loc),
        jnp.asarray(chunk_ptr), s,
    )
    b = kref.segment_sum_ref(jnp.asarray(data), jnp.asarray(seg), s)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_segment_sum_pallas_interpret_matches_blocked_reference():
    """The blocked kernel (interpret mode) and the blocked XLA reduction
    agree on one layout, whose chunks span several segment blocks."""
    from repro.kernels import segment_sum as ss

    rng = np.random.default_rng(7)
    e, d, s = 1400, 8, 300
    seg = np.sort(rng.integers(0, s, e)).astype(np.int32)
    data = rng.normal(size=(e, d)).astype(np.float32)
    perm, loc, chunk_ptr, nchunks, e_pad = csr_block_layout(seg, s, d)
    gather = jnp.asarray(
        np.where(perm[:, None] >= 0, data[np.maximum(perm, 0)], 0.0),
        jnp.float32)
    out = ss.segment_sum_pallas(
        gather, jnp.asarray(loc), jnp.asarray(chunk_ptr),
        jnp.asarray(nchunks), s, max_chunks=int(nchunks.max()),
        interpret=True,
    )
    ref = segment_sum_xla(gather, jnp.asarray(loc), jnp.asarray(chunk_ptr), s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_csr_block_layout_rejects_unsorted_ids():
    with pytest.raises(ValueError, match=r"sorted ascending.*seg_ids\[1\]=5"):
        csr_block_layout(np.array([1, 5, 3], np.int32), 10, 4)


def test_csr_block_layout_rejects_out_of_range_ids():
    with pytest.raises(ValueError, match=r"\[0, 10\).*seg_ids\[2\]=10"):
        csr_block_layout(np.array([0, 4, 10], np.int32), 10, 4)
    with pytest.raises(ValueError, match=r"seg_ids\[0\]=-1"):
        csr_block_layout(np.array([-1, 0, 3], np.int32), 10, 4)
    with pytest.raises(ValueError, match="num_segments"):
        csr_block_layout(np.array([], np.int32), 0, 4)


def test_csr_block_layout_degenerate_empty_and_single_segment():
    # m=0: an all-padding layout that the blocked reduction turns to zeros.
    perm, loc, chunk_ptr, nchunks, e_pad = csr_block_layout(
        np.array([], np.int32), 300, 4)
    assert (perm == -1).all() and e_pad % EB == 0 and e_pad > 0
    out = segment_sum_xla(
        jnp.zeros((e_pad, 4), jnp.float32), jnp.asarray(loc),
        jnp.asarray(chunk_ptr), 300)
    assert out.shape == (300, 4) and not np.asarray(out).any()
    # Single segment: every edge lands in block 0 / local id 0.
    e = 700
    perm, loc, chunk_ptr, nchunks, e_pad = csr_block_layout(
        np.zeros(e, np.int32), 1, 4)
    live = perm >= 0
    assert live.sum() == e and (loc[live] == 0).all()
    data = np.arange(e, dtype=np.float32)[:, None].repeat(4, 1)
    gather = np.where(perm[:, None] >= 0, data[np.maximum(perm, 0)], 0.0)
    out = segment_sum_xla(
        jnp.asarray(gather, jnp.float32), jnp.asarray(loc),
        jnp.asarray(chunk_ptr), 1)
    np.testing.assert_allclose(np.asarray(out)[0], data.sum(0), rtol=1e-6)


def test_segment_sum_sorted_empty_stream():
    out = ops.segment_sum_sorted(
        jnp.zeros((0, 4), jnp.float32), np.array([], np.int32), 7)
    assert out.shape == (7, 4) and not np.asarray(out).any()


def test_csr_block_layout_invariants():
    rng = np.random.default_rng(0)
    e, s = 5000, 1000
    seg = np.sort(rng.integers(0, s, e)).astype(np.int32)
    perm, loc, chunk_ptr, nchunks, e_pad = csr_block_layout(seg, s, 4)
    live = perm >= 0
    assert live.sum() == e
    assert sorted(perm[live]) == list(range(e))  # a permutation of all edges
    assert (loc[live] >= 0).all() and (loc[live] < SB).all()
    assert e_pad % EB == 0
    # Each block's chunks hold exactly its edges.
    for b in range(len(chunk_ptr)):
        lo, hi = chunk_ptr[b] * EB, (chunk_ptr[b] + nchunks[b]) * EB
        rows = perm[lo:hi]
        segs = seg[rows[rows >= 0]]
        if len(segs):
            assert (segs // SB == b).all()


# ----------------------------------------------------------------------------
# flash_attention
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,tq,tk,dh,dtype", [
    (1, 1, 1, 8, 8, 32, np.float32),
    (2, 4, 2, 130, 130, 64, np.float32),
    (1, 8, 1, 256, 256, 128, np.float32),   # MQA
    (2, 4, 4, 64, 64, 64, np.float16),
    (1, 4, 2, 1, 513, 64, np.float32),      # decode append
    (1, 2, 2, 100, 356, 32, np.float32),    # chunked continuation
])
def test_flash_attention_shapes(b, hq, hkv, tq, tk, dh, dtype):
    rng = np.random.default_rng(b * 7 + tq)
    q = rng.normal(size=(b, hq, tq, dh)).astype(dtype)
    k = rng.normal(size=(b, hkv, tk, dh)).astype(dtype)
    v = rng.normal(size=(b, hkv, tk, dh)).astype(dtype)
    b_ = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             tier="xla")
    tol = 5e-3 if dtype == np.float16 else 2e-3
    for tier in _tiers_under_test("flash_attention"):
        a = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                tier=tier)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32), rtol=tol,
                                   atol=tol, err_msg=f"tier={tier}")


# ----------------------------------------------------------------------------
# tier resolver / autotune table
# ----------------------------------------------------------------------------

def test_available_tiers_never_interpret_and_end_on_xla():
    for op in ("window_score", "segment_sum", "flash_attention"):
        tiers = ops.available_tiers(op)
        assert tiers[-1] == "xla"
        assert ops.INTERPRET_TIER not in tiers
        if jax.default_backend() != "tpu":
            assert "pallas-tpu" not in tiers
    with pytest.raises(ValueError, match="unknown op"):
        ops.available_tiers("nope")


def test_resolve_tier_default_is_never_interpret(monkeypatch):
    monkeypatch.delenv(ops.KERNEL_TIER_ENV, raising=False)
    for op in ("window_score", "segment_sum", "flash_attention"):
        assert ops.resolve_tier(op) in ops.available_tiers(op)


def test_resolve_tier_env_override(monkeypatch):
    monkeypatch.setenv(ops.KERNEL_TIER_ENV, "xla")
    assert ops.resolve_tier("window_score") == "xla"
    monkeypatch.setenv(ops.KERNEL_TIER_ENV, "bogus-tier")
    with pytest.raises(ValueError, match="unknown kernel tier"):
        ops.resolve_tier("window_score")
    # Explicit tier= beats a contradictory env var.
    monkeypatch.setenv(ops.KERNEL_TIER_ENV, "xla")
    assert ops.resolve_tier("window_score", "xla") == "xla"


def test_resolve_tier_interpret_is_explicit_debug_only(monkeypatch):
    monkeypatch.delenv(ops.KERNEL_TIER_ENV, raising=False)
    assert ops.resolve_tier("window_score") != ops.INTERPRET_TIER
    assert ops.resolve_tier("window_score", "interpret") == ops.INTERPRET_TIER
    monkeypatch.setenv(ops.KERNEL_TIER_ENV, "interpret")
    assert ops.resolve_tier("window_score") == ops.INTERPRET_TIER


def test_resolve_tier_unavailable_request_downgrades_loudly(monkeypatch):
    avail = ops.available_tiers("window_score")
    if "pallas-tpu" in avail:
        pytest.skip("pallas-tpu available: nothing to downgrade")
    ops.clear_tier_cache()
    with pytest.warns(RuntimeWarning, match="NOT pallas-tpu timings"):
        got = ops.resolve_tier("window_score", "pallas-tpu")
    assert got == avail[0]


@pytest.mark.parametrize("path", ["explicit_tier", "autotune_candidate"])
def test_tpu_backend_never_falls_back_to_xla(monkeypatch, path):
    """On a TPU backend a kernel that cannot run raises; it is never timed
    or dispatched as the XLA tier in its place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(ops.KERNEL_TIER_ENV, raising=False)
    ops.clear_tier_cache()
    if path == "explicit_tier":
        with pytest.raises(RuntimeError, match="cannot run on this TPU"):
            ops.resolve_tier("window_score", "pallas-cpu")
        return

    def refused():
        raise NotImplementedError("Unimplemented primitive in Pallas TPU lowering")

    with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
        ops.autotune_record("window_score", "256x32",
                            {"pallas-tpu": refused, "xla": lambda: jnp.zeros(())})
    assert not ops._TIER_MEMO


def test_autotune_microbench_caches_on_disk(monkeypatch, tmp_path):
    """Two candidate tiers -> one timed shoot-out, verdict cached in the
    on-disk table and the in-process memo (candidates never re-run)."""
    import time as _time

    cache = tmp_path / "kernel_tiers.json"
    monkeypatch.setenv(ops.AUTOTUNE_CACHE_ENV, str(cache))
    monkeypatch.delenv(ops.KERNEL_TIER_ENV, raising=False)
    monkeypatch.setattr(
        ops, "available_tiers", lambda op: ("pallas-cpu", "xla"))
    ops.clear_tier_cache()
    calls = {"pallas-cpu": 0, "xla": 0}

    def slow():
        calls["pallas-cpu"] += 1
        _time.sleep(0.02)
        return jnp.zeros(())

    def fast():
        calls["xla"] += 1
        return jnp.zeros(())

    cands = {"pallas-cpu": slow, "xla": fast}
    assert ops.resolve_tier("window_score", bucket="64x64",
                            candidates=cands) == "xla"
    assert calls["pallas-cpu"] > 0 and calls["xla"] > 0
    doc = json.loads(cache.read_text())
    [(key, entry)] = list(doc["entries"].items())
    assert key.startswith("window_score|64x64|") and entry["tier"] == "xla"
    assert set(entry["walls_s"]) == {"pallas-cpu", "xla"}
    # Second resolve: memoised, no re-benchmark.
    before = dict(calls)
    assert ops.resolve_tier("window_score", bucket="64x64",
                            candidates=cands) == "xla"
    assert calls == before
    # Fresh process simulation: memo cleared, disk table answers.
    ops.clear_tier_cache()
    assert ops.resolve_tier("window_score", bucket="64x64",
                            candidates=cands) == "xla"
    assert calls == before
    ops.clear_tier_cache()


def test_measured_score_cost_feeds_latency_model(monkeypatch, tmp_path):
    from repro.engine import latency_model

    monkeypatch.setenv(ops.AUTOTUNE_CACHE_ENV, str(tmp_path / "kt.json"))
    ops.clear_tier_cache()
    assert ops.measured_score_cost_s() is None
    # Record a wall for a 512x128 window_score bucket: 6.5536 ms / (512*128)
    # scores = 1e-7 s per score.
    ops.autotune_record(
        "window_score", "512x128", {"xla": lambda: jnp.zeros(())})
    memo_key = ("window_score", "512x128", jax.default_backend())
    ops._TIER_MEMO[memo_key]["walls_s"]["xla"] = 6.5536e-3
    cost = ops.measured_score_cost_s()
    assert cost == pytest.approx(1e-7)
    stats = dict(score_rows=1000, h2d_bytes=0)
    lat = latency_model.partition_latency(stats, m=1000, k=4)
    expect = 1000 * 4 * cost + 1000 * latency_model.EDGE_IO_COST_S
    assert lat == pytest.approx(expect)
    # The calibrated constant still rules when nothing was measured.
    ops.clear_tier_cache()
    monkeypatch.setenv(ops.AUTOTUNE_CACHE_ENV, str(tmp_path / "empty.json"))
    lat = latency_model.partition_latency(stats, m=1000, k=4)
    expect = 1000 * 4 * latency_model.SCORE_COST_S \
        + 1000 * latency_model.EDGE_IO_COST_S
    assert lat == pytest.approx(expect)
    ops.clear_tier_cache()


def test_flash_attention_ref_is_softmax_attention():
    """The oracle itself vs a literal softmax implementation."""
    rng = np.random.default_rng(3)
    b, h, t, dh = 1, 2, 16, 8
    q = rng.normal(size=(b, h, t, dh)).astype(np.float32)
    k = rng.normal(size=(b, h, t, dh)).astype(np.float32)
    v = rng.normal(size=(b, h, t, dh)).astype(np.float32)
    out = np.asarray(kref.flash_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
    for bb in range(b):
        for hh in range(h):
            logits = q[bb, hh] @ k[bb, hh].T / np.sqrt(dh)
            mask = np.tril(np.ones((t, t), bool))
            logits = np.where(mask, logits, -1e30)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(out[bb, hh], p @ v[bb, hh], rtol=1e-4,
                                       atol=1e-5)
