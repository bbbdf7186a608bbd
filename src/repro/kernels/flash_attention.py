"""Pallas TPU kernel: blocked causal GQA flash attention (forward).

Used by the LM serving path (prefill) of the assigned architectures. Online-
softmax over KV blocks with VMEM scratch accumulators; GQA is handled by
steering the K/V BlockSpec index map with `q_head // group`.

  grid = (B, Hq, Tq/BQ, Tk/BK)   — KV innermost so the scratch accumulators
                                    carry across the KV loop for a fixed
                                    (batch, head, q-block).

Causality is aligned to the *end* of the KV sequence (q position offset
Tk - Tq), so the same kernel serves full prefill (Tq == Tk) and chunked
prefill / decode append (Tq < Tk). Out-of-causal-range KV blocks are skipped
with @pl.when — the same work-skipping the roofline analysis credits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# TPU scratch memory spaces are unused under interpret=True on CPU.
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BQ = 128
BK = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, causal, tq, tk):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    nk = pl.num_programs(3)
    offset = tk - tq  # causal alignment: q row r has absolute position offset+iq*BQ+r

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_end = offset + (iq + 1) * BQ - 1
    k_start = jk * BK
    live = (q_end >= k_start) if causal else True

    @pl.when(live)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (BQ, Dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BK)
        if causal:
            rows = offset + iq * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]  # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, Tq, Dh)
    k: jax.Array,  # (B, Hkv, Tk, Dh)
    v: jax.Array,  # (B, Hkv, Tk, Dh)
    *,
    causal: bool = True,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hq, tq, dh = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (dh**0.5)
    tq_pad = -(-tq // BQ) * BQ
    tk_pad = -(-tk // BK) * BK
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, tq_pad - tq), (0, 0)))
    # Padded KV must not contribute: causal masking handles the tail when
    # rows < cols; for safety with non-causal, pad K with NEG-biasing zeros and
    # rely on explicit masking below via length check.
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0)))
    # With causal masking against the TRUE (tq, tk) offsets, padded K columns
    # sit at positions ≥ tk which no real query row ever attends; padded Q
    # rows are sliced off the output. Non-causal callers must be BK-aligned.
    if tk_pad != tk:
        assert causal, "non-causal flash requires Tk divisible by BK"

    grid = (b, hq, tq_pad // BQ, tk_pad // BK)
    scratch_shapes = [
        pltpu.VMEM((BQ, dh), jnp.float32),
        pltpu.VMEM((BQ, 1), jnp.float32),
        pltpu.VMEM((BQ, 1), jnp.float32),
    ]

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, tq=tq, tk=tk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, BQ, dh), lambda bb, h, iq, jk: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, BK, dh), lambda bb, h, iq, jk: (bb, h // group, jk, 0)),
            pl.BlockSpec((1, 1, BK, dh), lambda bb, h, iq, jk: (bb, h // group, jk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BQ, dh), lambda bb, h, iq, jk: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tq_pad, dh), q.dtype),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :tq, :]
