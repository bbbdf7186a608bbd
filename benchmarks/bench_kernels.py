"""Kernel micro-benchmarks: wall time per dispatch tier, per op.

Each op is timed at every tier runnable on this host (`xla` everywhere,
`pallas-tpu` / `pallas-cpu` where the backend lowers them) plus explicit
`interpret` where Pallas is importable — interpret is a *debug* tier, timed
here only so the chosen-tier speedup over it stays visible in the perf
trajectory. The `chosen` column is what `repro.kernels.ops.resolve_tier`
picks for the op on this host (autotuned; `$ADWISE_KERNEL_TIER` overrides),
and is never interpret.

CPU wall-times are indicative only (TPU is the target); the structural
metric that transfers is the op count / fusion shape, so we also report the
kernel's VMEM working set per tile.

    PYTHONPATH=src python -m benchmarks.bench_kernels
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.window_score import BW, LANE


def _time(fn, *a, n=3, **kw):
    fn(*a, **kw)  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*a, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _bench_tiers(op: str) -> list[str]:
    """Every runnable tier, plus the explicit interpret debug tier."""
    return [*ops.available_tiers(op), ops.INTERPRET_TIER]


def _row(op: str, shape: str, fn, args, vmem_kb: float) -> dict:
    chosen = ops.resolve_tier(op)
    walls_ms = {t: _time(fn, *args, tier=t) * 1e3 for t in _bench_tiers(op)}
    cols = " ".join(f"{t}={ms:.2f}" for t, ms in walls_ms.items())
    print(f"{op},{shape},chosen={chosen},{cols},vmem_tile_KB={vmem_kb:.0f}")
    return dict(kernel=op, shape=shape, chosen_tier=chosen,
                walls_ms=walls_ms, vmem_tile_kb=round(vmem_kb, 1))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(0)
    print("kernel,shape,chosen,per-tier ms,vmem_tile_KB")
    rows = []

    shapes = [(256, 32), (512, 32)] if args.quick else [(256, 32), (512, 32), (1024, 64)]
    for w, k in shapes:
        uv = rng.integers(0, 10_000, (w, 2)).astype(np.int32)
        valid = np.ones(w, bool)
        repu = rng.random((w, k)) < 0.2
        repv = rng.random((w, k)) < 0.2
        degu = rng.integers(1, 50, w).astype(np.int32)
        degv = rng.integers(1, 50, w).astype(np.int32)
        bal = rng.random(k).astype(np.float32)
        allowed = np.ones(k, bool)
        a = (uv, valid, repu, repv, degu, degv, bal, allowed,
             jnp.float32(1.0), jnp.int32(50))
        w_pad = -(-w // BW) * BW
        k_pad = -(-k // LANE) * LANE
        vmem = (5 * w_pad * 4 + 2 * w_pad * k_pad * 4 + BW * k_pad * 4) / 1024
        rows.append(_row("window_score", f"W{w}xK{k}", ops.window_score, a, vmem))

    for e, d, s in ([(2048, 32, 256)] if args.quick else [(2048, 32, 256), (8192, 64, 1024)]):
        seg = np.sort(rng.integers(0, s, e)).astype(np.int32)
        data = jnp.asarray(rng.normal(size=(e, d)).astype(np.float32))
        rows.append(_row("segment_sum", f"E{e}xD{d}xS{s}",
                         ops.segment_sum_sorted, (data, seg, s),
                         (512 * d * 4 + 128 * d * 4) / 1024))

    for b, hq, hkv, t, dh in ([(1, 4, 2, 256, 64)] if args.quick
                              else [(1, 4, 2, 256, 64), (2, 8, 4, 512, 64)]):
        q = jnp.asarray(rng.normal(size=(b, hq, t, dh)).astype(np.float32))
        kk = jnp.asarray(rng.normal(size=(b, hkv, t, dh)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, hkv, t, dh)).astype(np.float32))
        rows.append(_row("flash_attention", f"B{b}H{hq}T{t}D{dh}",
                         ops.flash_attention, (q, kk, v),
                         (128 * dh * 4 * 3 + 128 * 128 * 4) / 1024))

    # Headline numbers for the BENCH summary: the largest shape of each hot
    # op, billed at its chosen (non-interpret) tier.
    def _head(op: str):
        last = [r for r in rows if r["kernel"] == op][-1]
        return last["chosen_tier"], last["walls_ms"][last["chosen_tier"]] / 1e3

    ws_tier, ws_wall = _head("window_score")
    _, ss_wall = _head("segment_sum")
    return dict(rows=rows, kernel_tier=ws_tier,
                window_score_wall_s=ws_wall, segment_sum_wall_s=ss_wall)


if __name__ == "__main__":
    main()
