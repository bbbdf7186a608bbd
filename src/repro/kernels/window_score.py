"""Pallas TPU kernel for the ADWISE window scoring hot loop.

The partitioner's inner loop evaluates g(e,p) = λ·B(p) + R(e,p) + CS(e,p)
for every (window edge, partition) pair — w × k scores per assignment. The
paper's whole latency knob is this computation (§III-A/B), so it is the
kernel-worthy hot spot.

TPU adaptation (see DESIGN.md §3/§5): the clustering score's window-local
neighbourhood test is an O(W²) endpoint-match which we phrase as two
(BW, W) × (W, K) matmuls — MXU work — fused with the VPU-friendly R and
λ·B terms, one pass over VMEM-resident window state:

  grid  = (W / BW,)                       one program per row tile
  VMEM  = u,v,valid (1, W) rows and replica tables (W, K) for the match;
          the tile's u,v,valid,2-Ψ (BW, 1) columns; λ·B/allowed (1, K);
          out tile (BW, K)

The tile's replica rows are a ref-level ``pl.ds`` load from the whole-window
tables; the (BW, 1) columns come through their own BlockSpecs so the kernel
needs no transpose. Nothing slices a loaded value (Mosaic has no
value-level dynamic_slice).

W and K are padded to multiples of (BW=128, 128) so matmul operands are
MXU-aligned. Padded rows/columns carry valid=0 / allowed=0 and are masked to
NEG_INF, exactly like the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

NEG_INF = -1e30
BW = 128  # row-tile size (MXU sublane-aligned)
LANE = 128  # lane padding for K


def _kernel(
    u_row_ref,  # (1, W) int32    window endpoints, full width (CS match)
    v_row_ref,  # (1, W) int32
    valid_row_ref,  # (1, W) int32 (0/1)
    u_ref,  # (BW, 1) int32   this tile's rows, as columns
    v_ref,  # (BW, 1) int32
    valid_ref,  # (BW, 1) int32 (0/1)
    fu_ref,  # (BW, 1) f32    2 - Ψ_u
    fv_ref,  # (BW, 1) f32    2 - Ψ_v
    repu_all_ref,  # (W, K) f32  replica rows of every window edge's u
    repv_all_ref,  # (W, K) f32
    lbal_ref,  # (1, K) f32    λ·B(p)
    allowed_ref,  # (1, K) int32
    out_ref,  # (BW, K) f32
    *,
    use_cs: bool,
):
    # Degree-aware replication score R (Eq. 5) on this tile's rows.
    rows = pl.ds(pl.multiple_of(pl.program_id(0) * BW, BW), BW)
    g = repu_all_ref[rows, :] * fu_ref[...] + repv_all_ref[rows, :] * fv_ref[...]

    if use_cs:
        # Window-local neighbourhood match (CS, Eq. 6) as MXU matmuls.
        w = u_row_ref.shape[1]
        u_i, v_i = u_ref[...], v_ref[...]
        u, v = u_row_ref[...], v_row_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, (BW, w), 1)
        row_gid = jax.lax.broadcasted_iota(jnp.int32, (BW, w), 0) + pl.program_id(0) * BW
        keep = (valid_row_ref[...] > 0) & (col != row_gid)
        af = (((u == u_i) | (u == v_i)) & keep).astype(jnp.float32)
        bf = (((v == u_i) | (v == v_i)) & keep).astype(jnp.float32)
        num = jax.lax.dot(af, repv_all_ref[...], preferred_element_type=jnp.float32)
        num += jax.lax.dot(bf, repu_all_ref[...], preferred_element_type=jnp.float32)
        den = af.sum(axis=1, keepdims=True) + bf.sum(axis=1, keepdims=True)
        g = g + num / jnp.maximum(den, 1.0)

    # Adaptive balance term + validity masking.
    g = g + lbal_ref[...]
    ok = (valid_ref[...] > 0) & (allowed_ref[...] > 0)
    out_ref[...] = jnp.where(ok, g, NEG_INF)


def window_score_pallas(
    win_uv: jax.Array,  # (W, 2) int32
    win_valid: jax.Array,  # (W,) bool
    rep_u: jax.Array,  # (W, K) bool/f32
    rep_v: jax.Array,  # (W, K)
    deg_u: jax.Array,  # (W,) int32
    deg_v: jax.Array,  # (W,) int32
    bal: jax.Array,  # (K,) f32
    allowed: jax.Array,  # (K,) bool
    lam: jax.Array,  # () f32
    max_deg: jax.Array,  # () int32
    *,
    use_cs: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Padded pallas_call wrapper; returns (W, K) f32 score matrix.

    ``interpret=True`` is a debug flag (pure-Python emulation); the default
    lowers for real and raises where the backend cannot (dispatch belongs in
    ``ops.window_score``, which resolves a runnable tier first).
    """
    w, k = rep_u.shape
    w_pad = -(-w // BW) * BW
    k_pad = -(-k // LANE) * LANE

    def pad2(x, rows, cols, fill=0):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])), constant_values=fill)

    def pad_col(x, fill=0):
        return jnp.pad(x, (0, w_pad - x.shape[0]), constant_values=fill)[:, None]

    def pad_row(x, cols, fill=0):
        return jnp.pad(x, (0, cols - x.shape[0]), constant_values=fill)[None, :]

    # Every per-row and per-column factor is formed here with the same f32
    # ops as the oracle, so the kernel only adds and masks.
    denom = 2.0 * jnp.maximum(max_deg, 1).astype(jnp.float32)
    u = pad_col(win_uv[:, 0].astype(jnp.int32), fill=-1)
    v = pad_col(win_uv[:, 1].astype(jnp.int32), fill=-2)
    valid = pad_col(win_valid.astype(jnp.int32))
    fu = pad_col(2.0 - deg_u.astype(jnp.float32) / denom)
    fv = pad_col(2.0 - deg_v.astype(jnp.float32) / denom)
    ru = pad2(rep_u.astype(jnp.float32), w_pad, k_pad)
    rv = pad2(rep_v.astype(jnp.float32), w_pad, k_pad)
    lbal = pad_row(lam.astype(jnp.float32) * bal.astype(jnp.float32), k_pad)
    al = pad_row(allowed.astype(jnp.int32), k_pad)

    full = lambda i: (0, 0)
    tile = lambda i: (i, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, use_cs=use_cs),
        grid=(w_pad // BW,),
        in_specs=[
            pl.BlockSpec((1, w_pad), full),  # u row
            pl.BlockSpec((1, w_pad), full),  # v row
            pl.BlockSpec((1, w_pad), full),  # valid row
            pl.BlockSpec((BW, 1), tile),  # u tile
            pl.BlockSpec((BW, 1), tile),  # v tile
            pl.BlockSpec((BW, 1), tile),  # valid tile
            pl.BlockSpec((BW, 1), tile),  # 2 - Ψ_u tile
            pl.BlockSpec((BW, 1), tile),  # 2 - Ψ_v tile
            pl.BlockSpec((w_pad, k_pad), full),  # rep_u, whole window
            pl.BlockSpec((w_pad, k_pad), full),  # rep_v, whole window
            pl.BlockSpec((1, k_pad), full),  # λ·B
            pl.BlockSpec((1, k_pad), full),  # allowed
        ],
        out_specs=pl.BlockSpec((BW, k_pad), tile),
        out_shape=jax.ShapeDtypeStruct((w_pad, k_pad), jnp.float32),
        interpret=interpret,
    )(u.T, v.T, valid.T, u, v, valid, fu, fv, ru, rv, lbal, al)
    return out[:w, :k]
