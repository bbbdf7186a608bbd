"""Tier-dispatched public wrappers for the Pallas kernels.

Every op dispatches through one :func:`resolve_tier` ladder instead of the
old 'auto'/'pallas'/'ref' impl switch:

  * ``pallas-tpu`` — `pl.pallas_call` lowered for real on a TPU backend.
  * ``pallas-cpu`` — `pl.pallas_call` lowered through JAX's CPU Pallas
    lowering path, on installs whose JAX supports it (probed once in
    `repro.compat.has_pallas_cpu_lowering`). Never interpret mode.
  * ``xla``        — the pure-jnp oracles in `kernels/ref.py`. Always
    available.
  * ``interpret``  — Pallas interpret mode. This is an explicit DEBUG flag
    (``tier='interpret'`` or ``$ADWISE_KERNEL_TIER=interpret``); the
    resolver never lands on it by itself, so the default path is never
    pure-Python emulation on any backend.

When more than one lowered tier is available for an op, the winner is picked
by a one-shot microbenchmark cached per (op, shape-bucket, backend, jax
version) in a small on-disk autotune table (see :func:`autotune_cache_path`;
``$ADWISE_AUTOTUNE_CACHE`` relocates it). ``$ADWISE_KERNEL_TIER`` is the
override/escape hatch: force ``xla`` for bit-stable CI runs, ``interpret``
to step through a kernel.

On a TPU backend nothing falls back: a pallas-tpu candidate that fails
during autotune, or an explicit tier that cannot run, raises — so a wall
reported for a kernel is always that kernel's.
"""
from __future__ import annotations

import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.segment_sum import EB, SB, csr_block_layout, segment_sum_pallas
from repro.kernels.window_score import window_score_pallas

__all__ = [
    "window_score",
    "segment_sum_sorted",
    "flash_attention",
    "KERNEL_TIER_ENV",
    "TIERS",
    "INTERPRET_TIER",
    "available_tiers",
    "resolve_tier",
    "autotune_cache_path",
    "autotune_record",
    "measured_score_cost_s",
    "clear_tier_cache",
]

KERNEL_TIER_ENV = "ADWISE_KERNEL_TIER"
AUTOTUNE_CACHE_ENV = "ADWISE_AUTOTUNE_CACHE"

# Resolvable tiers in preference order (used when timing is unavailable).
TIERS = ("pallas-tpu", "pallas-cpu", "xla")
# Debug-only pseudo-tier: must be requested explicitly, never resolved to.
INTERPRET_TIER = "interpret"

_OPS = ("window_score", "segment_sum", "flash_attention")
# Ops whose pallas kernels need jax.experimental.pallas.tpu surfaces (VMEM
# scratch shapes / PrefetchScalarGridSpec) — those cannot take the CPU
# lowering path even where base pallas_call can.
_NEEDS_TPU_SUPPORT = ("segment_sum", "flash_attention")

_WARNED_DOWNGRADES: set[str] = set()
# In-process tier memo: (op, bucket, backend) -> {"tier": str, "walls_s": {}}.
_TIER_MEMO: dict[tuple, dict] = {}


def _downgrade(op: str, requested: str, actual: str, reason: str) -> str:
    """Requested tier cannot run: degrade loudly so benchmark columns are
    never silently mislabeled."""
    key = f"{op}:{requested}"
    if key not in _WARNED_DOWNGRADES:
        _WARNED_DOWNGRADES.add(key)
        warnings.warn(
            f"{op}: tier='{requested}' requested but {reason}; running "
            f"'{actual}' instead — reported timings are NOT {requested} "
            "timings",
            RuntimeWarning,
            stacklevel=4,
        )
    return actual


def available_tiers(op: str) -> tuple[str, ...]:
    """Tiers this install/backend can genuinely run for ``op`` (no
    interpret), best first. ``xla`` is always present."""
    if op not in _OPS:
        raise ValueError(f"unknown op {op!r}; known: {_OPS}")
    tiers: list[str] = []
    if jax.default_backend() == "tpu":
        tiers.append("pallas-tpu")
    elif op not in _NEEDS_TPU_SUPPORT and compat.has_pallas_cpu_lowering():
        tiers.append("pallas-cpu")
    tiers.append("xla")
    return tuple(tiers)


# ----------------------------------------------------------------------------
# On-disk autotune table
# ----------------------------------------------------------------------------

def autotune_cache_path() -> str:
    """Location of the on-disk autotune table (JSON).

    ``$ADWISE_AUTOTUNE_CACHE`` overrides; default is
    ``~/.cache/adwise/kernel_tiers.json`` (XDG_CACHE_HOME respected).
    """
    env = os.environ.get(AUTOTUNE_CACHE_ENV)
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME") or os.path.expanduser("~/.cache")
    return os.path.join(base, "adwise", "kernel_tiers.json")


def _table_key(op: str, bucket: str, backend: str) -> str:
    return f"{op}|{bucket}|{backend}|jax{jax.__version__}"


def _load_table() -> dict:
    try:
        with open(autotune_cache_path()) as f:
            doc = json.load(f)
        if isinstance(doc, dict) and doc.get("version") == 1:
            return doc.get("entries", {})
    except (OSError, ValueError):
        pass
    return {}


def _store_entry(key: str, entry: dict) -> None:
    """Best-effort persist: autotuning must never fail an op call."""
    path = autotune_cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        entries = _load_table()
        entries[key] = entry
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "entries": entries}, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        pass


def clear_tier_cache(*, disk: bool = False) -> None:
    """Drop the in-process tier memo (tests; env-var changes mid-process).
    ``disk=True`` also removes the on-disk table."""
    _TIER_MEMO.clear()
    _WARNED_DOWNGRADES.clear()
    if disk:
        try:
            os.remove(autotune_cache_path())
        except OSError:
            pass


def _pow2_bucket(*dims: int) -> str:
    """Shape bucket: each dim rounded up to a power of two, so nearby shapes
    share one autotune entry (same discipline as the ring's pow2 Rq)."""
    out = []
    for d in dims:
        d = max(int(d), 1)
        out.append(str(1 << (d - 1).bit_length()))
    return "x".join(out)


def _time_call(fn, n: int = 3) -> float:
    jax.block_until_ready(fn())  # warm: compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def autotune_record(op: str, bucket: str, candidates: dict) -> dict:
    """Time each candidate tier's thunk once, pick the fastest, memoise in
    process and on disk. ``candidates`` maps tier name -> zero-arg callable.

    Returns the table entry ``{"tier": str, "walls_s": {tier: seconds}}``.
    Exposed so `benchmarks/bench_kernels.py` can seed the table from its
    (larger) timed shapes.
    """
    walls: dict[str, float] = {}
    for tier, thunk in candidates.items():
        try:
            walls[tier] = _time_call(thunk)
        except Exception as e:
            if jax.default_backend() == "tpu":
                # On the chip a kernel that fails to lower or run is a bug:
                # timing the XLA tier in its place would hide it.
                raise
            warnings.warn(
                f"{op}: tier '{tier}' failed during autotune ({e!r}); "
                "excluded from selection",
                RuntimeWarning,
                stacklevel=2,
            )
    if not walls:
        raise RuntimeError(f"{op}: no autotune candidate ran")
    best = min(walls, key=lambda t: walls[t])
    entry = {"tier": best, "walls_s": walls}
    key = _table_key(op, bucket, jax.default_backend())
    _TIER_MEMO[(op, bucket, jax.default_backend())] = entry
    _store_entry(key, entry)
    return entry


def _lookup_entry(op: str, bucket: str) -> dict | None:
    memo_key = (op, bucket, jax.default_backend())
    if memo_key in _TIER_MEMO:
        return _TIER_MEMO[memo_key]
    entry = _load_table().get(_table_key(op, bucket, jax.default_backend()))
    if entry is not None:
        _TIER_MEMO[memo_key] = entry
    return entry


def resolve_tier(
    op: str,
    tier: str = "auto",
    *,
    bucket: str = "",
    candidates: dict | None = None,
) -> str:
    """Resolve a requested tier to what actually runs on this install.

    ``'auto'`` (the default everywhere) consults, in order: the
    ``$ADWISE_KERNEL_TIER`` override, the autotune table entry for
    (op, bucket, backend) — microbenchmarking the ``candidates`` thunks once
    and caching the verdict when more than one lowered tier is available —
    and finally the static preference order :data:`TIERS`. ``'interpret'``
    is honoured only as an explicit request (debug); an explicit tier that
    cannot run on this install degrades loudly to the best available one,
    except on a TPU backend, where it raises.
    ``'ref'`` is accepted as a legacy alias of ``'xla'``.
    """
    if tier == "ref":  # legacy alias from the impl= era
        tier = "xla"
    avail = available_tiers(op)
    if tier == "auto":
        env = os.environ.get(KERNEL_TIER_ENV, "").strip()
        if env and env != "auto":
            tier = env
    if tier != "auto":
        if tier == INTERPRET_TIER:
            return INTERPRET_TIER
        if tier not in TIERS:
            raise ValueError(
                f"{op}: unknown kernel tier {tier!r}; expected one of "
                f"{TIERS + (INTERPRET_TIER, 'auto')}"
            )
        if tier in avail:
            return tier
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                f"{op}: tier {tier!r} cannot run on this TPU backend "
                f"(runnable: {avail})"
            )
        return _downgrade(
            op, tier, avail[0], "this install cannot lower it"
        )
    if len(avail) == 1:
        return avail[0]
    entry = _lookup_entry(op, bucket)
    if entry is not None and entry.get("tier") in avail:
        return entry["tier"]
    if candidates:
        usable = {t: f for t, f in candidates.items() if t in avail}
        if len(usable) > 1:
            return autotune_record(op, bucket, usable)["tier"]
    return avail[0]


def measured_score_cost_s() -> float | None:
    """Per-(edge, partition) window-score cost at the *measured* tier.

    Scans the autotune walls recorded for ``window_score`` on the current
    backend and returns the median chosen-tier wall divided by the bucket's
    w·k score count — the constant `engine/latency_model.py` bills compute
    with when a measurement exists. Returns None when nothing has been
    measured on this backend (the model then falls back to its calibrated
    paper constant). Never triggers a microbenchmark itself.
    """
    backend = jax.default_backend()
    prefix = "window_score|"
    suffix = f"|{backend}|jax{jax.__version__}"
    costs: list[float] = []
    entries = dict(_load_table())
    for (op, bucket, be), entry in _TIER_MEMO.items():
        if op == "window_score" and be == backend:
            entries[f"{op}|{bucket}{suffix}"] = entry
    for key, entry in entries.items():
        if not (key.startswith(prefix) and key.endswith(suffix)):
            continue
        bucket = key[len(prefix) : -len(suffix)]
        try:
            w, k = (int(x) for x in bucket.split("x")[:2])
            wall = float(entry["walls_s"][entry["tier"]])
        except (KeyError, TypeError, ValueError):
            continue
        if w * k > 0 and wall > 0:
            costs.append(wall / (w * k))
    if not costs:
        return None
    return float(np.median(costs))


# ----------------------------------------------------------------------------
# Ops
# ----------------------------------------------------------------------------

def window_score(
    win_uv, win_valid, rep_u, rep_v, deg_u, deg_v, bal, allowed, lam, max_deg,
    *, use_cs: bool = True, tier: str = "auto",
):
    w, k = rep_u.shape
    args = (
        win_uv, win_valid, rep_u, rep_v, deg_u, deg_v, bal, allowed,
        jnp.asarray(lam), jnp.asarray(max_deg),
    )

    def _pallas(interpret: bool):
        return window_score_pallas(*args, use_cs=use_cs, interpret=interpret)

    def _xla():
        return _ref.window_score_ref(*args, use_cs=use_cs)

    resolved = resolve_tier(
        "window_score", tier, bucket=_pow2_bucket(w, k),
        candidates={
            "pallas-tpu": lambda: _pallas(False),
            "pallas-cpu": lambda: _pallas(False),
            "xla": _xla,
        },
    )
    if resolved == INTERPRET_TIER:
        return _pallas(True)
    if resolved in ("pallas-tpu", "pallas-cpu"):
        return _pallas(False)
    return _xla()


def segment_sum_sorted(
    data: jax.Array,  # (E, D) — messages sorted by seg id
    seg_ids: np.ndarray,  # (E,) sorted, HOST array (static layout per graph)
    num_segments: int,
    *, tier: str = "auto",
):
    """Segment sum where the segment layout is static (known per graph).

    The pallas tiers run the blocked-CSR kernel over the
    `csr_block_layout` padding; the ``xla`` tier is the plain
    `jax.ops.segment_sum` reference over the raw sorted ids (no layout
    cost).

    Every tier accumulates and returns fp32 regardless of input dtype (the
    blocked kernel's MXU-style mixed precision) — switching tiers never
    changes numeric semantics, only speed.
    """
    e, d = data.shape

    def _pallas(interpret: bool):
        perm, loc, chunk_ptr, nchunks, e_pad = csr_block_layout(
            np.asarray(seg_ids), num_segments, d
        )
        gather = jnp.where(perm[:, None] >= 0, data[jnp.maximum(perm, 0)], 0.0)
        return segment_sum_pallas(
            gather.astype(jnp.float32),
            jnp.asarray(loc),
            jnp.asarray(chunk_ptr),
            jnp.asarray(nchunks),
            num_segments,
            max_chunks=int(nchunks.max()) if len(nchunks) else 1,
            interpret=interpret,
        )

    def _xla():
        return _ref.segment_sum_ref(
            data.astype(jnp.float32), jnp.asarray(seg_ids), num_segments
        )

    resolved = resolve_tier(
        "segment_sum", tier, bucket=_pow2_bucket(e, d, num_segments),
        candidates={"pallas-tpu": lambda: _pallas(False), "xla": _xla},
    )
    if resolved == INTERPRET_TIER:
        return _pallas(True)
    if resolved == "pallas-tpu":
        return _pallas(False)
    return _xla()


def flash_attention(q, k, v, *, causal: bool = True, scale=None, tier: str = "auto"):
    def _pallas(interpret: bool):
        return flash_attention_pallas(
            q, k, v, causal=causal, scale=scale, interpret=interpret
        )

    def _xla():
        return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)

    resolved = resolve_tier(
        "flash_attention", tier,
        bucket=_pow2_bucket(q.shape[0] * q.shape[1], q.shape[2], q.shape[3]),
        candidates={"pallas-tpu": lambda: _pallas(False), "xla": _xla},
    )
    if resolved == INTERPRET_TIER:
        return _pallas(True)
    if resolved == "pallas-tpu":
        return _pallas(False)
    return _xla()
