"""Pallas TPU kernel: blocked segment-sum (edge→vertex accumulation).

The vertex-cut processing engine's dominant op is accumulating per-edge
messages into destination vertices (gather-apply-scatter). On TPU a raw
scatter is VPU-serial; the TPU-native phrasing is a *blocked CSR* one-hot
matmul:

  * edges are pre-sorted by destination segment (static per graph),
  * each segment block (SB=128 rows of the output) owns a contiguous,
    EB-aligned run of edge chunks (host-side padding aligns the runs),
  * grid = (num_segment_blocks, max_chunks_per_block); the kernel builds a
    local (EB, SB) one-hot from the in-chunk destination ids and accumulates
    `one_hotᵀ @ data` (MXU) into the output tile resident in VMEM.

Chunk ranges are passed as scalar-prefetch operands so BlockSpec index maps
can steer each program to its chunk (PrefetchScalarGridSpec) — the standard
ragged-block pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SB = 128  # segment (output row) block
EB = 512  # edge chunk


def csr_block_layout(seg_ids: np.ndarray, num_segments: int, d: int):
    """Host-side preprocessing: pad the sorted edge list into EB-aligned runs.

    Returns (perm, loc, chunk_ptr, nchunks, e_pad) where
      perm: int64 (E_pad,) — index into the original edge array (-1 = padding),
      loc:  int32 (E_pad,) — destination id *local to its segment block*,
      chunk_ptr: int32 (n_sblocks,) — first chunk index of each block,
      nchunks:   int32 (n_sblocks,) — number of chunks of each block.

    Invalid layouts are rejected up front with a ValueError naming the
    offending position — unsorted or out-of-range ids would otherwise
    surface as index garbage deep in the padding math. Degenerate inputs are
    legal: ``m=0`` yields an all-padding layout and a single segment block
    still gets its one (padded) chunk run.
    """
    seg_ids = np.asarray(seg_ids)
    if seg_ids.ndim != 1:
        raise ValueError(
            f"csr_block_layout: seg_ids must be 1-D, got shape {seg_ids.shape}"
        )
    if num_segments < 1:
        raise ValueError(
            f"csr_block_layout: num_segments must be >= 1, got {num_segments}"
        )
    if seg_ids.size:
        drop = np.diff(seg_ids) < 0
        if drop.any():
            i = int(np.argmax(drop))
            raise ValueError(
                "csr_block_layout: segment ids must be sorted ascending; "
                f"seg_ids[{i}]={int(seg_ids[i])} > "
                f"seg_ids[{i + 1}]={int(seg_ids[i + 1])}"
            )
        bad = (seg_ids < 0) | (seg_ids >= num_segments)
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                "csr_block_layout: segment ids must lie in "
                f"[0, {num_segments}); seg_ids[{i}]={int(seg_ids[i])}"
            )
    n_sblocks = -(-num_segments // SB)
    # Edge range per segment block.
    lo = np.searchsorted(seg_ids, np.arange(n_sblocks) * SB)
    hi = np.searchsorted(seg_ids, np.minimum((np.arange(n_sblocks) + 1) * SB, num_segments))
    counts = hi - lo
    nchunks = np.maximum(-(-counts // EB), 1).astype(np.int32)
    chunk_ptr = np.concatenate([[0], np.cumsum(nchunks)[:-1]]).astype(np.int32)
    e_pad = int(nchunks.sum()) * EB
    perm = np.full(e_pad, -1, dtype=np.int64)
    loc = np.zeros(e_pad, dtype=np.int32)
    for b in range(n_sblocks):
        n = counts[b]
        dst = chunk_ptr[b] * EB
        perm[dst : dst + n] = np.arange(lo[b], hi[b])
        loc[dst : dst + n] = seg_ids[lo[b] : hi[b]] - b * SB
    return perm, loc, chunk_ptr, nchunks, e_pad


def segment_sum_xla(
    data_padded: jax.Array,  # (E_pad, D) f32 — permuted by csr_block_layout
    loc: jax.Array,  # (E_pad,) int32 — block-local destination ids
    chunk_ptr: jax.Array,  # (n_sblocks,) int32
    num_segments: int,
) -> jax.Array:
    """`jax.ops.segment_sum` over the same blocked CSR layout.

    The layout's reference, independent of Pallas: global destination ids
    are reconstructed from the layout (block-of-chunk × SB + local id) and
    handed to XLA's segment sum, so a wrong `csr_block_layout` shows here
    before it shows in the kernel. Padding rows carry zero data, so they
    contribute nothing wherever their reconstructed id lands.
    """
    e_pad, _ = data_padded.shape
    n_sblocks = chunk_ptr.shape[0]
    n_total_chunks = e_pad // EB
    chunk_ids = jnp.arange(n_total_chunks, dtype=chunk_ptr.dtype)
    block_of_chunk = jnp.searchsorted(chunk_ptr, chunk_ids, side="right") - 1
    seg = jnp.repeat(block_of_chunk.astype(jnp.int32), EB) * SB + loc
    s_pad = n_sblocks * SB
    assert num_segments <= s_pad, (
        f"num_segments={num_segments} exceeds the layout's {s_pad} padded rows"
    )
    out = jax.ops.segment_sum(
        data_padded.astype(jnp.float32), seg, num_segments=s_pad
    )
    return out[:num_segments]


def _kernel(chunk_ptr_ref, nchunks_ref, loc_ref, data_ref, out_ref):
    b = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(c < nchunks_ref[b])
    def _acc():
        # (1, EB) local ids; padding rows have data == 0. The one-hot is
        # built already transposed, (SB, EB), so the MXU takes it as is.
        onehot_t = (
            jax.lax.broadcasted_iota(jnp.int32, (SB, EB), 0) == loc_ref[...]
        ).astype(jnp.float32)
        contrib = jax.lax.dot(
            onehot_t, data_ref[...], preferred_element_type=jnp.float32
        )
        out_ref[...] += contrib


def segment_sum_pallas(
    data_padded: jax.Array,  # (E_pad, D) f32 — permuted by csr_block_layout, pad rows zero
    loc: jax.Array,  # (E_pad,) int32
    chunk_ptr: jax.Array,  # (n_sblocks,) int32
    nchunks: jax.Array,  # (n_sblocks,) int32
    num_segments: int,
    *,
    max_chunks: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """(S_pad, D) blocked segment sum; rows ≥ num_segments are zero padding.

    ``interpret=True`` is a debug flag only — tier dispatch (including the
    decision to run this kernel at all) lives in ``ops.segment_sum_sorted``.
    """
    e_pad, d = data_padded.shape
    n_sblocks = chunk_ptr.shape[0]
    n_total_chunks = e_pad // EB
    if max_chunks is None:
        max_chunks = n_total_chunks  # safe upper bound for the chunk grid dim
    s_pad = n_sblocks * SB

    def data_index(b, c, ptr, nch):
        return (jnp.minimum(ptr[b] + c, n_total_chunks - 1), 0)

    def loc_index(b, c, ptr, nch):
        return (jnp.minimum(ptr[b] + c, n_total_chunks - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_sblocks, max_chunks),
        in_specs=[
            # One chunk's ids as a (1, EB) row: a block equal to the array's
            # last two dims, which the TPU tiling rule accepts.
            pl.BlockSpec((None, 1, EB), loc_index),
            pl.BlockSpec((EB, d), data_index),
        ],
        out_specs=pl.BlockSpec((SB, d), lambda b, c, ptr, nch: (b, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, d), jnp.float32),
        interpret=interpret,
    )(chunk_ptr, nchunks, loc.reshape(n_total_chunks, 1, EB), data_padded)
    return out[:num_segments] if num_segments <= s_pad else out
