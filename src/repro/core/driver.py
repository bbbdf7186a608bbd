"""Unified streaming-scan driver: ONE engine behind every ADWISE scan caller.

Before this module, the paper's core loop — adaptive window scan with
per-step latency billing (§III-A) — was implemented four times over:
``partition_stream``, ``partition_stream_batched`` (core/adwise.py), the
out-of-core ADWISE path (core/oocore.py), and the warm-started re-streaming
passes each re-derived ``r_sel``, the capacity caps, budget wiring, carry
initialization, and the chunked stepping loop. :class:`ScanDriver` owns all
of that once, over a *pluggable chunk source*:

* :class:`ResidentSource` — the whole stream is uploaded to device once
  (``streams[z, per, 2]``); scan calls index it directly with ``base=0``
  semantics. This is the in-memory path (`partition_stream`,
  `partition_stream_batched`, every re-streaming pass over a resident
  array).
* :class:`FileSource` — a **device-resident ring buffer**: a donated
  ``(z, B, 2)`` buffer lives on device across scan calls, logical stream row
  ``s`` occupies slot ``s % B``, and each refill ships ONLY the new tail
  rows through ``jax.lax.dynamic_update_slice`` — host→device traffic per
  scan call drops from O(B) (the PR-4 full re-upload) to O(refill). Rows
  are uploaded in quantized spans (multiples of ``Rq``, a power of two) so
  the update kernel compiles for a bounded set of shapes; ``B`` is a
  multiple of ``Rq`` sized so a quantized refill always covers the next
  scan call's worst-case consumption (``window_max + S * assign_batch``
  rows per S-step call — the same cursor-advance bound PR 4 proved).

Both modes run the *same* step function — the per-step math is one trace,
so the file path stays bit-identical to the in-memory path (the
registry-wide parity tests in tests/test_oocore.py are the oracle, plus the
ring-specific property tests in tests/test_driver.py). A single instance
runs it unbatched; z > 1 instances run it vmapped (optionally shard_mapped)
— the ``scan_path`` stat says which.

Step-cores
----------
The per-step math itself is pluggable. A **step-core** (:class:`StepCore`)
is a hashable, frozen description of one streaming strategy that the driver
jit-specializes on. A core implements:

* ``make_step(stream, m_real, allowed, cap, prev_assign) -> step`` — the
  step factory. ``step(carry, _) -> (carry, StepOut)`` is scanned by
  ``jax.lax.scan``; it must read stream rows at ``src % m_pad`` (the ring
  invariant: for a resident source the mod is the identity, for the ring it
  maps logical row ``s`` to slot ``s % B``) and must never read more than
  ``window_rows + rows_per_step`` rows ahead of ``carry.cursor`` in one
  step (the refill bound the :class:`FileSource` sizing proves).
* ``init_carry(budget)`` / ``warm_carry(budget, warm)`` — cold start and
  warm resume from a :class:`~repro.core.types.WarmState`. The carry is any
  pytree obeying the contract in :mod:`repro.core.types` (``.cursor`` and
  ``.assigned`` int32 leaves).
* ``seed_instances(carry, z, ids)`` — batched hook: derive per-instance
  state (e.g. counter-based tie-break seeds ``seed + ids[i]``) after the
  driver stacks z carries; ``ids`` are the caller's global instance
  indices so bucketed sub-batches reproduce the unbucketed streams.
* ``window_rows`` / ``rows_per_step`` — the look-ahead and per-step
  consumption bounds the driver sizes scan calls and the ring with
  (ADWISE: ``window_max`` / ``assign_batch``; single-edge baselines 0 / 1).
* ``counters(carry)`` / ``recalibrate(carry, t0, z)`` / ``set_cost`` —
  stats extraction and the optional latency-budget hooks.

``AdwiseCore`` wraps the adaptive-window math from ``repro.core.adwise``;
``repro.core.baselines`` provides ``HdrfCore`` / ``GreedyCore`` and
``repro.core.restream`` the 2PS-L phase-2 core — all four ride the very
same driver, sources, and h2d accounting.

The double-buffer refill pipeline (prefetch)
--------------------------------------------
With ``prefetch >= 1`` (the default — ``prefetch=0`` is the synchronous
bit-parity escape hatch, also reachable via the ``ADWISE_PREFETCH`` env
var), :class:`FileSource` runs a two-stage pipeline:

1. A host **read-ahead worker** (:class:`_ReadAhead`: one daemon thread +
   a bounded staging queue) reads the stream — and, on re-streaming
   passes, the prior placements — in ``Rq``-row blocks ahead of
   consumption, at most ``prefetch * max_span`` rows past what the scan
   has taken. Refill spans are always whole multiples of ``Rq`` (plus one
   ragged tail ending exactly at ``m_i``), so staged blocks align with
   span consumption exactly — the queue never splits a block.
2. After dispatching scan call k, the driver issues a **speculative
   refill** *before* syncing the ``assigned`` counter, so the
   ``_ring_write`` h2d for span k+1 is enqueued while scan k is still in
   flight. The safe cursor proxy is the guaranteed-progress lower bound
   ``lb = min(assigned_k + S, m)`` (every scan step with a non-empty
   window assigns >= 1 edge — the same bound that proves termination):
   the slots a speculative write recycles held rows ``< lb``, and the
   next scan starts at ``cursor >= assigned_{k+1} >= lb``, so it can
   never read a recycled slot. Because ``_run_scan_ring`` *donates* the
   ring, XLA orders the write after the in-flight scan — the pipeline
   only moves *when* spans are staged and shipped, never *what* they
   contain, which is why bit-parity is geometry-independent.

Cross-pass shared-buffer contract: after a completed ring pass the driver
exposes a :class:`RingHandle` (the final donated ring + upload high-water
marks). A re-streaming pass may adopt it (``FileSource(resume=...)``):
instances whose whole stream fit in the ring without wrapping
(``m_i <= B``) keep their ``uv`` rows device-resident and ship only the
4 B/row ``prev`` placements — restream h2d drops from ``8m + 12m`` bytes
per extra pass to ``8m + 4m``. Wrapped instances fall back to the full
re-ship. The in-memory analogue is :class:`StreamResidency`: re-stream
passes over a :class:`ResidentSource` reuse pass p's uploaded device
stream array and ship only the new ``prev`` table.

Host→device accounting: the driver counts every stream-buffer byte it ships
(``h2d_rows`` / ``h2d_bytes`` / ``h2d_calls``), the measured refill stall
(``h2d_wait_s``: wall spent in non-speculative refills, i.e. staging work
the device had to wait for) and the pipeline hit rate
(``spans_prestaged`` / ``spans_missed``; their sum is ``refill_spans``).
Callers surface the counters in partition stats, and
``repro.engine.latency_model.partition_latency`` bills them — against
:data:`~repro.engine.latency_model.H2D_BW_BPS` when only modeled traffic
is available, overlap-aware (``max(io, h2d, compute)``) when a prefetch
depth and measured stalls are present.

Host-serial accounting (:class:`HostSerial`): the ring loop reports the
host time with no scan call in flight (``host_serial_s``: from each sync's
return to the next dispatch) and the device→host reads it makes
(``host_syncs``: four per call — ``assigned``, ``cursor``, ``sidx``,
``p``). Its spans (``refill``, ``dispatch``, ``refill-spec``, ``sync``,
``emit`` under ``scan-call``) take their timestamps from the same floats.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from functools import partial
from typing import Any, Callable, Deque, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.adwise import Carry, _init_carry, _make_step
from repro.core.types import AdwiseConfig, WarmState
from repro.obs import resolve_tracer

__all__ = [
    "StepCore",
    "AdwiseCore",
    "ResidentSource",
    "FileSource",
    "RingBuf",
    "RingHandle",
    "StreamResidency",
    "ScanDriver",
    "DriveResult",
    "HostSerial",
    "resolve_backend",
    "resolve_prefetch",
    "scan_compile_counts",
    "scan_path",
    "PREFETCH_ENV",
]

PREFETCH_ENV = "ADWISE_PREFETCH"


def resolve_prefetch(prefetch: Optional[int] = None) -> int:
    """Effective read-ahead depth: explicit argument > ``ADWISE_PREFETCH``
    env var > default 2. ``0`` selects the synchronous bit-parity path
    (no worker thread, every span read inline between scan calls)."""
    if prefetch is None:
        raw = os.environ.get(PREFETCH_ENV, "").strip()
        prefetch = int(raw) if raw else 2
    return max(0, int(prefetch))


def resolve_backend(backend: str, z: int) -> tuple[str, int]:
    """(effective backend, n_shards). 'auto' picks shard_map when multiple
    devices are visible; shard_map degrades to vmap when no device count > 1
    divides z."""
    if backend == "auto":
        backend = "shard_map" if jax.device_count() > 1 else "vmap"
    if backend == "vmap":
        return "vmap", 0
    if backend != "shard_map":
        raise ValueError(
            f"backend must be 'auto', 'vmap' or 'shard_map', got {backend!r}"
        )
    nd = min(jax.device_count(), z)
    n_shards = max((d for d in range(1, nd + 1) if z % d == 0), default=1)
    if n_shards <= 1:
        return "vmap", 0
    return "shard_map", n_shards


# ----------------------------------------------------------------------------
# The step-core interface
# ----------------------------------------------------------------------------


class StepCore:
    """Base class for streaming-strategy step-cores (see module docstring).

    Concrete cores are **frozen dataclasses** holding only hashable scalars
    (k, |V|, quantized weights, ...) — the core object is a jit static
    argument, so its identity selects the compiled trace. All per-instance
    *state* (vertex caches, seeds, cursors) lives in the carry, never in the
    core.
    """

    name: str = "core"

    # The sizing contract is read-only by design (concrete cores either
    # derive it from config or shadow it with class attributes), so the base
    # declares properties rather than writable attributes.
    @property
    def window_rows(self) -> int:
        """Look-ahead rows the step may read beyond the last assignment
        (ring sizing adds this to the per-call consumption bound)."""
        return 0

    @property
    def rows_per_step(self) -> int:
        """Max stream rows consumed (and assignments emitted) per step."""
        return 1

    @property
    def r_sel(self) -> int:
        """Lazy-traversal rescore budget (diagnostics; ADWISE-specific)."""
        return 0

    @property
    def has_budget(self) -> bool:
        return False

    # -- required hooks ----------------------------------------------------
    def make_step(
        self, stream: Any, m_real: Any, allowed: Any, cap: Any, prev_assign: Any
    ) -> Callable[[Any, Any], Any]:
        raise NotImplementedError

    def init_carry(self, budget: float) -> Any:
        raise NotImplementedError

    def warm_carry(self, budget: float, warm: WarmState) -> Any:
        raise NotImplementedError(f"{self.name} does not support warm starts")

    # -- optional hooks ----------------------------------------------------
    def cap_value(self, m: int, n_allowed: int) -> int:
        """Hard per-partition capacity for an instance streaming m edges."""
        return int(np.iinfo(np.int32).max)

    def seed_instances(
        self, carry: Any, z: int, ids: Optional[np.ndarray] = None
    ) -> Any:
        """Derive per-instance carry state after batching (default: none).

        ``ids`` are the caller's *global* instance indices for the z batch
        positions (defaults to ``arange(z)``). Seed-deriving cores must key
        on ``ids`` — never on the batch position — so length-bucketed
        batching, which permutes instances across sub-batches, reproduces
        the exact per-instance streams of the unbucketed layout.
        """
        return carry

    def set_cost(self, carry: Any, cost_per_score: float, z: int) -> Any:
        raise ValueError(f"{self.name} core does not model per-score cost")

    def recalibrate(self, carry: Any, t0: float, z: int) -> Any:
        """Between-chunks budget recalibration (no-op unless has_budget)."""
        return carry

    def counters(self, carry: Any) -> dict:
        """Final per-instance counters for :class:`DriveResult` (each (z,))."""
        assigned = np.asarray(carry.assigned)
        z = assigned.shape[0]
        return dict(
            score_rows=assigned.astype(np.int64),
            final_w=np.ones((z,), np.int64),
            lam=np.zeros((z,), np.float32),
            cost_per_score=np.zeros((z,), np.float32),
        )


@dataclasses.dataclass(frozen=True)
class AdwiseCore(StepCore):
    """ADWISE adaptive-window scan as a step-core (math in core/adwise.py)."""

    cfg: AdwiseConfig
    num_vertices: int
    update_deg: bool = True  # False on warm passes: degrees already final

    name = "adwise"

    @property
    def k(self) -> int:
        return self.cfg.k

    @property
    def window_rows(self) -> int:
        return self.cfg.window_max

    @property
    def rows_per_step(self) -> int:
        return self.cfg.assign_batch

    @property
    def r_sel(self) -> int:
        return self.cfg.resolve_r_sel()

    @property
    def has_budget(self) -> bool:
        return self.cfg.latency_budget is not None

    def cap_value(self, m: int, n_allowed: int) -> int:
        return self.cfg.cap_value(m, n_allowed)

    def make_step(
        self, stream: Any, m_real: Any, allowed: Any, cap: Any, prev_assign: Any
    ) -> Callable[[Any, Any], Any]:
        return _make_step(
            self.cfg, self.num_vertices, self.r_sel, stream, m_real, allowed,
            cap, self.has_budget, prev_assign, self.update_deg,
        )

    def init_carry(self, budget: float) -> Carry:
        return _init_carry(self.cfg, self.num_vertices, budget)

    def warm_carry(self, budget: float, warm: WarmState) -> Carry:
        return Carry.warm_start(
            self.cfg, self.num_vertices, budget,
            replicas=warm.replicas, deg=warm.deg, sizes=warm.sizes,
        )

    def set_cost(self, carry: Any, cost_per_score: float, z: int) -> Any:
        return carry._replace(
            cost_per_score=jnp.full((z,), cost_per_score, jnp.float32)
        )

    def recalibrate(self, carry: Any, t0: float, z: int) -> Any:
        budget = self.cfg.latency_budget
        assert budget is not None  # only called when has_budget
        # Recalibrate the modeled cost against measured wall between scan
        # calls: one program runs all instances, so the shared per-row cost
        # comes from the batched wall over the total row count.
        # staticcheck: disable=SC003 budget recalibration MEASURES wall clock — the sync is the measurement (§III-B latency budget)
        jax.block_until_ready(carry.score_rows)
        wall = time.perf_counter() - t0
        # staticcheck: disable=SC003 score_rows drives the measured cost; already synced by the block above
        rows = max(int(np.asarray(carry.score_rows).sum()), 1)
        return carry._replace(
            cost_per_score=jnp.full(
                (z,), wall / (rows * self.cfg.k), jnp.float32
            ),
            budget_left=jnp.full((z,), budget - wall, jnp.float32),
        )

    def counters(self, carry: Any) -> dict:
        return dict(
            score_rows=np.asarray(carry.score_rows),
            final_w=np.asarray(carry.w_cap),
            lam=np.asarray(carry.lam),
            cost_per_score=np.asarray(carry.cost_per_score),
        )


# ----------------------------------------------------------------------------
# Scan executors: one program for all z instances, resident or ring
# ----------------------------------------------------------------------------


class RingBuf(NamedTuple):
    """Device-resident stream ring: slot ``s % B`` holds logical row ``s``.

    Threaded through every ring-mode scan call as part of the donated carry,
    so XLA aliases it in place — only the refill spans ever cross the
    host→device boundary.
    """

    uv: jax.Array  # (B, 2) int32 per instance (batched: (z, B, 2))
    prev: jax.Array  # (B,) int32 prior-pass assignment, -1 = none


def _shard_over_instances(
    fn: Callable[..., Any], n_shards: int, n_args: int
) -> Callable[..., Any]:
    mesh = compat.make_mesh(
        (n_shards,), ("instances",),
        devices=np.array(jax.devices()[:n_shards]),
    )
    return compat.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P("instances"),) * n_args,
        out_specs=P("instances"),
        check_replication=False,
    )


def scan_path(z: int, n_shards: int) -> str:
    """How the scan executors run z instances over ``n_shards`` devices:
    ``"single"`` steps a lone unsharded instance unbatched, ``"vmap"``
    batches the instances (shard_mapped when ``n_shards > 1``)."""
    return "single" if z == 1 and n_shards <= 1 else "vmap"


def _batched(one: Callable[..., Any], n_shards: int, *args: Any) -> Any:
    """``one`` vmapped over the leading instance axis of every leaf of
    ``args``, shard_mapped over ``n_shards`` devices when > 1."""
    batched = jax.vmap(one)
    if n_shards > 1:
        batched = _shard_over_instances(batched, n_shards, len(args))
    return batched(*args)


def _over_instances(one: Callable[..., Any], n_shards: int, *args: Any) -> Any:
    """Run ``one`` per instance along the leading axis of ``args``.

    A lone instance runs ``one`` on the bare instance: the scan then
    carries its V-sized tables in the layout its scatters write, and the
    unit axis is dropped and restored once per call instead of on every
    step of the loop. Otherwise the instances run :func:`_batched`.
    """
    z = jax.tree.leaves(args)[0].shape[0]
    if scan_path(z, n_shards) == "vmap":
        return _batched(one, n_shards, *args)
    out = one(*jax.tree.map(lambda x: x[0], args))
    return jax.tree.map(lambda x: x[None], out)


def _scan_resident_one(
    carry: Any, stream: Any, m_real: Any, allowed: Any, cap: Any, prev: Any,
    *, core: StepCore, n_steps: int,
) -> Any:
    """One instance's scan over its resident stream."""
    step = core.make_step(stream, m_real, allowed, cap, prev)
    return jax.lax.scan(step, carry, None, length=n_steps)


def _scan_ring_one(
    carry_buf: Any, m_real: Any, allowed: Any, cap: Any,
    *, core: StepCore, n_steps: int,
) -> Any:
    """One instance's scan over its ring; the ring is returned untouched."""
    carry, buf = carry_buf
    step = core.make_step(buf.uv, m_real, allowed, cap, buf.prev)
    carry, outs = jax.lax.scan(step, carry, None, length=n_steps)
    return (carry, buf), outs


@partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("core", "n_steps", "n_shards"),
)
def _run_scan_resident(
    carry: Any,  # core carry; every leaf carries a leading (z,) instance axis
    streams: jax.Array,  # (z, per, 2) int32
    m_real: jax.Array,  # (z,) int32
    allowed: jax.Array,  # (z, K) bool
    cap: jax.Array,  # (z,) int32
    prev_assign: jax.Array,  # (z, per) int32
    *,
    core: StepCore,
    n_steps: int,
    n_shards: int = 0,
) -> Any:
    """All z instance scans as ONE program over a fully resident stream."""
    one = partial(_scan_resident_one, core=core, n_steps=n_steps)
    return _over_instances(
        one, n_shards, carry, streams, m_real, allowed, cap, prev_assign
    )


@partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("core", "n_steps", "n_shards"),
)
def _run_scan_ring(
    carry_buf: tuple,  # (carry, RingBuf), each leaf with a leading (z,) axis
    m_real: jax.Array,  # (z,) int32
    allowed: jax.Array,  # (z, K) bool
    cap: jax.Array,  # (z,) int32
    *,
    core: StepCore,
    n_steps: int,
    n_shards: int = 0,
) -> Any:
    """Ring-mode scan: the stream buffer rides in the donated carry and is
    returned untouched, so XLA aliases it across calls (zero copies, zero
    re-upload)."""
    one = partial(_scan_ring_one, core=core, n_steps=n_steps)
    return _over_instances(one, n_shards, carry_buf, m_real, allowed, cap)


@partial(
    jax.jit, donate_argnums=(0,), static_argnames=("with_uv", "with_prev")
)
def _ring_write(
    buf: RingBuf,
    uv_rows: jax.Array,  # (c, 2) int32 — the ONLY stream bytes shipped h2d
    prev_rows: jax.Array,  # (c,) int32 (dummy empty when with_prev=False)
    instance: jax.Array,  # () int32
    slot: jax.Array,  # () int32 — c never wraps past B (spans pre-split)
    *,
    with_prev: bool,
    with_uv: bool = True,  # False on cross-pass resumed instances: uv rows
    # are already device-resident, only prev ships (dummy empty uv_rows)
) -> RingBuf:
    if with_uv:
        uv = jax.lax.dynamic_update_slice(
            buf.uv, uv_rows[None], (instance, slot, jnp.int32(0))
        )
    else:
        uv = buf.uv
    if with_prev:
        prev = jax.lax.dynamic_update_slice(
            buf.prev, prev_rows[None], (instance, slot)
        )
    else:
        prev = buf.prev
    return RingBuf(uv, prev)


def scan_compile_counts() -> dict:
    """Live jit-cache sizes of the three driver kernels — the retrace
    budget the pow2-``Rq`` quantization exists to bound.

    ``_run_scan_resident`` / ``_run_scan_ring`` compile once per distinct
    (core static config, n_steps, carry/stream shapes); ``_ring_write``
    once per distinct refill-span shape, which quantization keeps to the
    multiples of ``Rq`` up to ``max_span`` plus at most one ragged
    final-tail span per instance. tests/test_compile_budget.py asserts the
    bound over random geometries; benchmarks/run.py emits the counts into
    ``BENCH_<n>.json`` so retrace regressions show up in the perf
    trajectory. Returns zeros if the jax version hides ``_cache_size``.
    """
    return {
        name: int(getattr(fn, "_cache_size", lambda: 0)())
        for name, fn in (
            ("run_scan_resident", _run_scan_resident),
            ("run_scan_ring", _run_scan_ring),
            ("ring_write", _ring_write),
        )
    }


# ----------------------------------------------------------------------------
# Chunk sources
# ----------------------------------------------------------------------------


class RingHandle(NamedTuple):
    """Cross-pass hand-off of a completed ring pass (file mode).

    Produced by :class:`ScanDriver` after a ring drive finishes; a
    re-streaming pass with identical geometry may adopt it via
    ``FileSource(resume=...)`` so instances whose whole stream fit in the
    ring without wrapping keep their uv rows device-resident and ship only
    prev placements. The handle is single-use: the adopting pass donates
    the buffer back into its own scan calls.
    """

    buf: RingBuf  # final donated ring (valid until the next pass donates it)
    hi: np.ndarray  # (z,) per-instance upload high-water marks at pass end
    B: int  # ring rows per instance
    z: int
    m_per: np.ndarray  # (z,) real stream lengths the pass ran over


class StreamResidency:
    """Cross-pass device residency for resident (in-memory) sources.

    A re-streaming caller creates one holder and threads it through every
    pass; pass p publishes its uploaded ``(z, per, 2)`` device stream
    array(s) here and pass p+1 reuses them, shipping only the new ``prev``
    table. Length-bucketed batching (`partition_stream_batched`) uploads one
    array per pow2 bucket, so the holder keys residency by shape — every
    bucket of the next pass finds its own resident array. Caller contract:
    every pass must stream the SAME edge content in the same instance
    layout — only the shape is cheap to verify, so the holder must never be
    shared across different streams.
    """

    __slots__ = ("_by_shape",)

    def __init__(self) -> None:
        self._by_shape: dict[Tuple[int, ...], jax.Array] = {}

    def publish(self, streams: jax.Array, shape: Tuple[int, ...]) -> None:
        self._by_shape[tuple(shape)] = streams

    def lookup(self, shape: Tuple[int, ...]) -> Optional[jax.Array]:
        return self._by_shape.get(tuple(shape))


# One staged block: (start_row, row_count, uv rows or None, prev rows or
# None). uv is None for cross-pass resumed instances (prev-only refills).
_Block = Tuple[int, int, Optional[np.ndarray], Optional[np.ndarray]]


class _ReadAhead:
    """Host read-ahead worker: stage stream/prev rows while the scan runs.

    One daemon thread services all z instances round-robin, reading
    ``Rq``-row blocks (final ragged tail ends exactly at ``m_i``) into a
    bounded per-instance staging deque, at most ``depth_rows`` rows past
    what :meth:`take` has consumed. Every refill span is a whole number of
    Rq blocks (or ends exactly at ``m_i`` — see the FileSource sizing), so
    ``take`` always pops whole blocks and never splits one.

    Disk reads happen OUTSIDE the lock (the lock only guards the deques and
    the progress counters); worker exceptions are captured and re-raised in
    the consumer's next ``take``. ``close`` is idempotent and joins the
    thread — safe on every exception path.
    """

    def __init__(self, source: "FileSource", depth_rows: int) -> None:
        self._src = source
        self._depth = int(depth_rows)
        self._cv = threading.Condition()
        z = source.z
        self._staged: List[Deque[_Block]] = [
            collections.deque() for _ in range(z)
        ]
        # Worker-side read position and consumer-side pop position per
        # instance; both only ever advance.
        self._next = np.zeros((z,), np.int64)
        self._taken = np.zeros((z,), np.int64)
        self._exc: Optional[BaseException] = None
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, name="adwise-readahead", daemon=True
        )
        self._thread.start()

    # -- worker side -------------------------------------------------------
    def _pick(self) -> Optional[int]:
        """Least-staged eligible instance, or None (caller holds the lock)."""
        src = self._src
        best, best_lag = None, 0
        for i in range(src.z):
            if self._next[i] >= src.m_per[i]:
                continue  # instance fully staged
            lag = int(self._next[i] - self._taken[i])
            if lag >= self._depth:
                continue  # at the bound: wait for the consumer
            if best is None or lag < best_lag:
                best, best_lag = i, lag
        return best

    def _loop(self) -> None:
        src = self._src
        try:
            while True:
                with self._cv:
                    while True:
                        if self._stop:
                            return
                        i = self._pick()
                        if i is not None:
                            break
                        if (self._next >= src.m_per).all():
                            return  # everything staged; worker retires
                        self._cv.wait()
                    start = int(self._next[i])
                    c = min(src.Rq, int(src.m_per[i]) - start)
                # Reads outside the lock: the consumer keeps popping while
                # the worker is on disk.
                trace = src.trace
                t_stage = time.perf_counter()
                # Opened from the worker thread, so the span lands on the
                # `adwise-readahead` track.
                stage = trace.span("stage", "stage").open(t_stage)
                uv: Optional[np.ndarray] = None
                if not src.uv_resident[i]:
                    uv = np.ascontiguousarray(
                        src.readers[i].read(start, c), np.int32
                    )
                    assert len(uv) == c, (
                        f"instance {i}: reader returned {len(uv)} of {c} "
                        f"rows at offset {start}"
                    )
                prev: Optional[np.ndarray] = None
                if src.prev_read is not None:
                    prev = np.ascontiguousarray(
                        src.prev_read[i](start, c), np.int32
                    )
                    assert len(prev) == c, (
                        f"instance {i}: prev_read returned {len(prev)} of "
                        f"{c} rows at offset {start}"
                    )
                t_staged = time.perf_counter()
                if trace.enabled:
                    stage.set(instance=i, start=start, rows=c,
                              prev=prev is not None)
                stage.close(t_staged)
                with self._cv:
                    # Worker-side staging wall: the blind spot h2d_wait_s
                    # (blocking refills only) cannot see. Accumulated even
                    # when untraced so overlap_efficiency is always measured.
                    src.prestage_wall_s += t_staged - t_stage
                    self._staged[i].append((start, c, uv, prev))
                    self._next[i] = start + c
                    if trace.enabled:
                        depth = int((self._next - self._taken).sum())
                    self._cv.notify_all()
                if trace.enabled:
                    trace.gauge("readahead_staged_rows", depth)
        except BaseException as e:  # surfaced via take(); thread must not die silently
            with self._cv:
                self._exc = e
                self._cv.notify_all()

    # -- consumer side -----------------------------------------------------
    def take(
        self, i: int, start: int, count: int
    ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray], bool]:
        """Pop ``count`` staged rows of instance i beginning at ``start``.

        Returns ``(uv_rows, prev_rows, waited)`` — ``waited`` is True when
        the consumer had to block on the worker (a pipeline miss).
        """
        end = start + count
        uv_parts: List[np.ndarray] = []
        prev_parts: List[np.ndarray] = []
        waited = False
        with self._cv:
            assert start == int(self._taken[i]), (
                f"instance {i}: take at {start}, staged position is "
                f"{int(self._taken[i])}"
            )
            while self._taken[i] < end:
                if self._exc is not None:
                    raise RuntimeError(
                        "read-ahead worker failed"
                    ) from self._exc
                if self._staged[i]:
                    b_start, c, uv, prev = self._staged[i].popleft()
                    assert b_start == int(self._taken[i])
                    assert b_start + c <= end, (
                        f"instance {i}: staged block [{b_start}, "
                        f"{b_start + c}) straddles take end {end} — "
                        "span/block alignment broken"
                    )
                    if uv is not None:
                        uv_parts.append(uv)
                    if prev is not None:
                        prev_parts.append(prev)
                    self._taken[i] = b_start + c
                    self._cv.notify_all()  # freed depth: wake the worker
                else:
                    waited = True
                    self._cv.wait()
        uv_all = (
            uv_parts[0] if len(uv_parts) == 1
            else np.concatenate(uv_parts) if uv_parts else None
        )
        prev_all = (
            prev_parts[0] if len(prev_parts) == 1
            else np.concatenate(prev_parts) if prev_parts else None
        )
        return uv_all, prev_all, waited

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=30.0)


class ResidentSource:
    """Whole stream resident on device: ONE upload for the entire run.

    ``streams`` is the (z, per, 2) padded instance layout
    (:meth:`repro.graph.stream.EdgeStream.split_padded`); ``m_per[i]`` is the
    real (un-padded) length of instance i's stream. z == 1 wraps a plain
    (m, 2) stream as (1, m, 2).

    ``residency`` (optional :class:`StreamResidency`) lets re-streaming
    passes over the same stream reuse the previous pass's uploaded device
    array: when the holder already has a matching-shape array, the driver
    skips the stream upload and ships only the new ``prev`` table.
    """

    resident = True

    def __init__(
        self,
        streams: np.ndarray,
        m_per: np.ndarray,
        *,
        residency: Optional[StreamResidency] = None,
    ) -> None:
        streams = np.ascontiguousarray(streams, np.int32)
        assert streams.ndim == 3 and streams.shape[2] == 2, streams.shape
        self.z, self.per = int(streams.shape[0]), int(streams.shape[1])
        self.m_per = np.asarray(m_per, np.int64)
        assert self.m_per.shape == (self.z,)
        assert (self.m_per <= self.per).all()
        self.streams = streams
        self.residency = residency

    @property
    def upload_rows(self) -> int:
        return self.z * self.per


class FileSource:
    """Bounded device-resident ring buffer over per-instance stream readers.

    ``readers[i]`` is instance i's locally addressed stream (an
    ``EdgeFileReader`` / sub-reader, or anything with ``num_edges`` and
    ``read(start, count)``); ``prev_read[i](start, count)`` optionally
    supplies the prior pass's placements for buffered re-streaming
    revocation.

    Sizing (strategy-agnostic, driven by the step-core's look-ahead and
    consumption bounds ``W = core.window_rows``, ``b = core.rows_per_step``
    — ADWISE: ``window_max`` / ``assign_batch``, single-edge baselines
    0 / 1): ``S = (B0 - W) // b`` scan steps per call consume at most
    ``F = W + S · b`` rows (look-ahead refill ceiling + per-step
    assignments — the PR-4 cursor-advance bound), where
    ``B0 = max(chunk_edges, W + b)``. Refills are quantized to spans that
    are multiples of ``Rq`` (a power of two, so the `dynamic_update_slice`
    kernel compiles for a bounded shape set); the ring holds
    ``B = (⌈F/Rq⌉ + 2) · Rq`` rows, so a quantized refill always leaves
    ≥ F uploaded-but-unread rows ahead of the cursor while never
    overwriting a live slot (row ``s`` may land in slot ``s % B`` only once
    row ``s − B`` is behind the cursor).

    Invariants (checked): ``cursor ≤ hi ≤ cursor + B`` and ``hi`` advances
    monotonically — every stream row is read from disk and shipped to the
    device exactly once per pass.

    ``prefetch >= 1`` enables the double-buffer pipeline (module docstring):
    a :class:`_ReadAhead` worker stages up to ``prefetch * max_span`` rows
    ahead of consumption, and the driver issues a speculative refill before
    its per-call counter sync. ``prefetch=0`` is the synchronous bit-parity
    path. ``resume`` adopts a previous pass's :class:`RingHandle` —
    matching-geometry instances that never wrapped ship prev-only spans
    (4 B/row instead of 12 B/row).
    """

    resident = False

    def __init__(
        self,
        readers: Sequence,
        *,
        chunk_edges: int,
        cfg: Optional[AdwiseConfig] = None,
        core: Optional[StepCore] = None,
        prev_read: Optional[List[Callable[[int, int], np.ndarray]]] = None,
        prefetch: Optional[int] = None,
        resume: Optional[RingHandle] = None,
        trace: Any = None,
    ) -> None:
        self.trace = resolve_tracer(trace)
        self.readers = list(readers)
        self.z = len(self.readers)
        self.m_per = np.array([r.num_edges for r in self.readers], np.int64)
        self.prev_read = prev_read
        if core is not None:
            w_max, b = core.window_rows, core.rows_per_step
        else:
            assert cfg is not None, "FileSource needs a cfg or a step-core"
            w_max, b = cfg.window_max, cfg.assign_batch
        b0 = int(max(chunk_edges, w_max + b))
        self.scan_steps = max(1, (b0 - w_max) // b)
        f = w_max + self.scan_steps * b  # worst-case rows consumed per call
        self.Rq = 1 << max(2, (max(f // 8, 1)).bit_length())
        self.B = (-(-f // self.Rq) + 2) * self.Rq
        # Single disk reads (and update-kernel spans) stay within the
        # b0 = max(chunk_edges, window_max + assign_batch) bound even though
        # the ring is slightly larger; kept a multiple of Rq so span shapes
        # stay quantized.
        self.max_span = max(self.Rq, (b0 // self.Rq) * self.Rq)
        # Host-side high-water mark: rows [0, hi) are on device.
        self.hi = np.zeros((self.z,), np.int64)
        self.h2d_rows = 0
        self.h2d_bytes = 0
        self.h2d_calls = 0
        self.h2d_wait_s = 0.0
        self.prestage_wall_s = 0.0
        self.refill_spans = 0
        self.spans_prestaged = 0
        self.spans_missed = 0
        self.prefetch = resolve_prefetch(prefetch)
        # uv_resident[i]: instance i's uv rows survive from the adopted
        # previous-pass ring — refills ship prev-only spans.
        self.uv_resident = np.zeros((self.z,), bool)
        self._resume_buf: Optional[RingBuf] = None
        if resume is not None:
            self._adopt(resume)
        self._worker: Optional[_ReadAhead] = None
        self._worker_started = False

    def _adopt(self, resume: RingHandle) -> None:
        """Adopt a previous pass's ring under the cross-pass contract:
        same geometry (B, z, per-instance m), and only instances whose
        whole stream fit without wrapping (``m_i <= B`` and the pass
        uploaded all of it) keep uv residency."""
        assert self.prev_read is not None, (
            "resuming a ring without prev_read would re-run the same pass; "
            "cross-pass adoption is for re-streaming revocation only"
        )
        if (
            resume.B != self.B
            or resume.z != self.z
            or not (np.asarray(resume.m_per) == self.m_per).all()
        ):
            return  # geometry changed (re-chunked): full re-ship fallback
        fits = (self.m_per <= resume.B) & (np.asarray(resume.hi) >= self.m_per)
        if fits.any():
            self.uv_resident = fits
            self._resume_buf = resume.buf
            if self.trace.enabled:
                self.trace.instant(
                    "ring-adopt", "refill",
                    resident_instances=int(fits.sum()), z=self.z, B=self.B,
                )

    def alloc(self) -> RingBuf:
        """Device ring for this pass: the adopted previous-pass buffer when
        resuming (single-use — it is donated back into this pass's scan
        calls), else a fresh one: uv zeros, prev all -1 (= no prior
        placement — 0 would be a real partition id and would trigger false
        revocation). Stale prev rows in an adopted ring are harmless: hi
        restarts at 0, so every row's prev is re-shipped before the cursor
        can reach it."""
        if self._resume_buf is not None:
            buf = self._resume_buf
            self._resume_buf = None
            return buf
        return RingBuf(
            uv=jnp.zeros((self.z, self.B, 2), jnp.int32),
            prev=jnp.full((self.z, self.B), -1, jnp.int32),
        )

    def _fetch(
        self, i: int, start: int, c: int
    ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray], bool]:
        """One span's host rows: from the staging queue when pipelined,
        read inline otherwise. Lazily starts the worker so sizing-only
        FileSource uses never spawn a thread."""
        if self.prefetch > 0 and not self._worker_started:
            self._worker_started = True
            self._worker = _ReadAhead(
                self, max(1, self.prefetch) * self.max_span
            )
        if self._worker is not None:
            return self._worker.take(i, start, c)
        uv: Optional[np.ndarray] = None
        if not self.uv_resident[i]:
            uv = np.ascontiguousarray(self.readers[i].read(start, c), np.int32)
            assert len(uv) == c, (
                f"instance {i}: reader returned {len(uv)} of {c} rows "
                f"at offset {start}"
            )
        prev: Optional[np.ndarray] = None
        if self.prev_read is not None:
            prev = np.ascontiguousarray(self.prev_read[i](start, c), np.int32)
        # The synchronous path stalls on every span by construction.
        return uv, prev, True

    def refill(
        self, buf: RingBuf, cursors: np.ndarray, *, speculative: bool = False
    ) -> RingBuf:
        """Ship the new tail rows for every instance; returns the new ring.

        ``cursors[i]`` is instance i's scan cursor — rows behind it are dead
        and their slots are free to overwrite. A ``speculative`` refill
        passes the guaranteed-progress lower bound instead of the true
        cursor (see the module docstring) and is excluded from the measured
        ``h2d_wait_s`` stall: its staging work overlaps the in-flight scan.
        """
        self.h2d_calls += 1
        trace = self.trace
        traced = trace.enabled
        name = "refill-spec" if speculative else "refill"
        t_start = time.perf_counter()
        span = trace.span(name, name).open(t_start)
        shipped_rows = 0
        call_spans = 0
        call_missed = 0
        with_prev = self.prev_read is not None
        dummy_uv = np.zeros((0, 2), np.int32)
        dummy_prev = np.zeros((0,), np.int32)
        for i in range(self.z):
            cur = int(cursors[i])
            m_i = int(self.m_per[i])
            hi = int(self.hi[i])
            assert cur <= hi, (
                f"instance {i}: scan cursor {cur} overran the uploaded "
                f"high-water mark {hi} — ring refill bound violated"
            )
            target = min(cur + self.B, m_i)
            if target <= hi:
                continue
            span_total = target - hi
            if target < m_i:
                # Quantize to Rq blocks (bounded kernel-shape set); the
                # remainder is covered because B ≥ F + 2·Rq keeps ≥ F rows
                # ahead of the cursor even after flooring.
                span_total -= span_total % self.Rq
            end = hi + span_total
            ship_uv = not bool(self.uv_resident[i])
            while hi < end:
                slot = hi % self.B
                # Never wrap inside a write; never exceed the chunk bound.
                c = min(end - hi, self.B - slot, self.max_span)
                with trace.span("fetch", "fetch") as fetch:
                    rows, prows, waited = self._fetch(i, hi, c)
                    if traced:
                        fetch.set(instance=i, start=hi, rows=c,
                                  prestaged=not waited)
                self.refill_spans += 1
                call_spans += 1
                if waited:
                    self.spans_missed += 1
                    call_missed += 1
                else:
                    self.spans_prestaged += 1
                buf = _ring_write(
                    buf,
                    rows if rows is not None else dummy_uv,
                    prows if prows is not None else dummy_prev,
                    np.int32(i),
                    np.int32(slot),
                    with_prev=with_prev,
                    with_uv=ship_uv,
                )
                if ship_uv:
                    self.h2d_rows += c
                    self.h2d_bytes += c * 8
                if with_prev:
                    self.h2d_bytes += c * 4
                shipped_rows += c
                hi += c
            self.hi[i] = hi
        if traced:
            span.set(rows=shipped_rows, spans=call_spans, missed=call_missed,
                     Rq=self.Rq)
        t_end = time.perf_counter()
        # Same (t_start, t_end) floats that feed h2d_wait_s: the `refill`
        # category total reconciles with it exactly. A speculative refill
        # overlaps the in-flight scan and is not a stall.
        span.close(t_end)
        if not speculative:
            self.h2d_wait_s += t_end - t_start
        return buf

    def close(self) -> None:
        """Join the read-ahead worker (idempotent; safe on exception paths).
        After close, further refills fall back to synchronous reads."""
        if self._worker is not None:
            self._worker.close()
            self._worker = None

    def __enter__(self) -> "FileSource":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


# ----------------------------------------------------------------------------
# The driver
# ----------------------------------------------------------------------------


class DriveResult(NamedTuple):
    """Raw outcome of one driven scan; callers assemble their stats shapes."""

    # Per-instance step outputs, concatenated over every scan call — only
    # collected in resident mode (the file path streams them to `on_assign`
    # to stay O(chunk)): (z, T·b) / (z, T·b) / (z, T).
    sidx: Optional[np.ndarray]
    p: Optional[np.ndarray]
    w_trace: Optional[np.ndarray]
    # Final carry counters, one row per instance.
    assigned: np.ndarray  # (z,) int
    score_rows: np.ndarray  # (z,) int
    final_w: np.ndarray  # (z,) int
    lam: np.ndarray  # (z,) f32
    cost_per_score: np.ndarray  # (z,) f32
    # Run-level accounting.
    wall_time_s: float
    r_sel: int
    backend: str
    n_shards: int
    scan_path: str  # "single" (one unbatched instance) or "vmap"
    scan_calls: int
    h2d_rows: int
    h2d_bytes: int
    buffer_rows: int  # ring B (file mode) / per (resident mode)
    scan_steps_per_call: int
    # Refill-pipeline accounting (file mode; zeros for resident sources).
    h2d_wait_s: float = 0.0  # wall spent in non-speculative (blocking) refills
    prefetch_depth: int = 0
    refill_spans: int = 0
    spans_prestaged: int = 0
    spans_missed: int = 0
    # Worker-side staging wall (read-ahead thread): the time spent reading
    # and preparing spans the blocking h2d_wait_s stall cannot see.
    prestage_wall_s: float = 0.0


class HostSerial:
    """Host time with no scan call in flight, and the device→host reads
    of the stepping loop, over one caller's run (one ``partition_file``
    call, over all its ring passes).

    Stretches run from ``t_entry`` to the first scan dispatch — the
    ``init`` span of ``trace``, opened here when a tracer is given — from
    each sync's return to the next dispatch, and from the last sync's
    return to :meth:`finish`. The driver passes the same floats to its
    spans.
    """

    __slots__ = ("serial_s", "syncs", "_free", "_init")

    def __init__(self, trace: Any = None, t_entry: Optional[float] = None) -> None:
        t = time.perf_counter() if t_entry is None else t_entry
        self.serial_s = 0.0
        self.syncs = 0
        self._free = t
        self._init: Any = (
            None if trace is None else trace.span("init", "phase").open(t)
        )

    def dispatch(self, t: float) -> None:
        """A scan call is dispatched at ``t``: the host stretch ends."""
        if self._init is not None:
            self._init.close(t)
            self._init = None
        self.serial_s += t - self._free

    def synced(self, t: float, reads: int) -> None:
        """``reads`` device→host reads returned at ``t``: a stretch starts."""
        self.syncs += reads
        self._free = t

    finish = dispatch


def _stack_instances(*xs: Any) -> jax.Array:
    """One carry leaf of z instances on a leading axis: host leaves (a
    warm carry's vertex tables) stack on the host and upload once."""
    if all(isinstance(x, (np.ndarray, np.generic)) for x in xs):
        return jnp.asarray(np.stack(xs))
    return jnp.stack(xs)


class ScanDriver:
    """One streaming-scan engine for every step-core strategy.

    Owns carry initialization (cold or warm-started from per-instance
    :class:`~repro.core.types.WarmState`), capacity-cap resolution,
    latency-budget wiring (including the between-chunks wall-clock
    recalibration of the modeled cost), backend/shard resolution, and the
    chunked stepping loop over the given source. Callers stay thin: they
    build a source and a step-core (or pass an :class:`AdwiseConfig`, which
    wraps into an :class:`AdwiseCore`), run the driver, and format stats.
    """

    def __init__(
        self,
        source: Any,  # a ResidentSource or FileSource (anything source-shaped)
        core: Any,  # a StepCore, or an AdwiseConfig (compat: wraps AdwiseCore)
        num_vertices: Optional[int] = None,
        *,
        allowed: Optional[np.ndarray] = None,  # (z, k) bool
        warm: Optional[Sequence[WarmState]] = None,
        cost_per_score: Optional[float] = None,
        backend: str = "vmap",
        trace: Any = None,
        instance_ids: Optional[np.ndarray] = None,
    ) -> None:
        self.trace = resolve_tracer(trace)
        # A traced driver over an untraced FileSource adopts the driver's
        # tracer, so refill/stage spans land in the same timeline without
        # every caller having to thread trace= twice.
        src_trace = getattr(source, "trace", None)
        if self.trace.enabled and src_trace is not None and not src_trace.enabled:
            source.trace = self.trace
        self.source = source
        if isinstance(core, AdwiseConfig):
            assert num_vertices is not None, "AdwiseConfig path needs |V|"
            self.cfg: Optional[AdwiseConfig] = core
            core = AdwiseCore(
                cfg=core, num_vertices=num_vertices, update_deg=warm is None
            )
        else:
            self.cfg = getattr(core, "cfg", None)
        self.core = core
        self.num_vertices = num_vertices
        z, k = source.z, core.k
        self.z = z
        self.m_per = source.m_per
        self.r_sel = core.r_sel

        if allowed is None:
            allowed_np = np.ones((z, k), bool)
        else:
            allowed_np = np.asarray(allowed, bool)
            assert allowed_np.shape == (z, k), (allowed_np.shape, (z, k))
        caps = np.array(
            [
                core.cap_value(int(self.m_per[i]), max(int(allowed_np[i].sum()), 1))
                for i in range(z)
            ],
            np.int32,
        )

        self.has_budget = bool(core.has_budget)
        budget = 0.0
        if self.has_budget and self.cfg is not None:
            budget = self.cfg.latency_budget or 0.0
        self.warm = warm is not None
        per = int(getattr(source, "per", 0))
        prev_np: Optional[np.ndarray] = (
            np.full((z, per), -1, np.int32) if source.resident else None
        )
        if warm is None:
            base = core.init_carry(budget)
            carry = jax.tree.map(lambda x: jnp.broadcast_to(x, (z,) + x.shape), base)
        else:
            assert len(warm) == z, f"need one WarmState per instance, got {len(warm)}"
            has_prev = [w.prev_assign is not None for w in warm]
            assert all(has_prev) or not any(has_prev), (
                "all instances must agree on whether prev_assign is provided"
            )
            # File mode feeds prior placements through the source's
            # prev_read range reads, never through resident prev arrays —
            # silently dropping them would skip revocation.
            assert source.resident or not any(has_prev), (
                "file-mode warm states must not carry prev_assign; pass "
                "prev_read to the FileSource instead"
            )
            carries = [core.warm_carry(budget, w) for w in warm]
            carry = jax.tree.map(_stack_instances, *carries)
            if prev_np is not None and all(has_prev):
                for i, w in enumerate(warm):
                    assert w.prev_assign is not None  # all(has_prev) above
                    pa = np.asarray(w.prev_assign, np.int32)
                    assert pa.shape == (int(self.m_per[i]),), (
                        f"instance {i}: prev_assign must align with its stream"
                    )
                    prev_np[i, : len(pa)] = pa
        if instance_ids is None:
            ids = np.arange(z)
        else:
            ids = np.asarray(instance_ids)
            assert ids.shape == (z,), (ids.shape, z)
        carry = core.seed_instances(carry, z, ids)
        # One instance's V-sized tables, as the carry holds them.
        v1 = getattr(core, "num_vertices", num_vertices) + 1
        self.vertex_table_bytes = sum(
            x.nbytes for x in jax.tree.leaves(carry)
            if x.ndim >= 2 and x.shape[1] == v1
        ) // z
        self.fixed_cost = cost_per_score is not None
        if cost_per_score is not None:
            carry = core.set_cost(carry, cost_per_score, z)
        self.carry = carry
        self.backend, self.n_shards = resolve_backend(backend, z)
        self.scan_path = scan_path(z, self.n_shards)
        self._m_real_j = jnp.asarray(self.m_per.astype(np.int32))
        self._allowed_j = jnp.asarray(allowed_np)
        self._caps_j = jnp.asarray(caps)
        self._prev_np = prev_np
        # Set after a completed ring drive: the cross-pass hand-off a
        # re-streaming pass may adopt (FileSource(resume=...)).
        self.ring_handle: Optional[RingHandle] = None

    # -- budget recalibration (shared by both modes) -----------------------
    def _recalibrate(self, carry: Any, t0: float) -> Any:
        if not (self.has_budget and not self.fixed_cost):
            return carry
        return self.core.recalibrate(carry, t0, self.z)

    # -- resident mode -----------------------------------------------------
    def _run_resident(self, n_chunks: int) -> DriveResult:
        src, core = self.source, self.core
        z, b = self.z, core.rows_per_step
        m_max = int(self.m_per.max())
        # Scan-step provisioning sized by the largest instance (smaller ones
        # idle); the drain below covers top-b pick stalls (star graphs with
        # rows_per_step > 1 assign one edge per step, not b — each step with
        # a non-empty window assigns >= 1 edge, so ceil(m/chunk_steps) extra
        # chunks always finish).
        steps_total = -(-m_max // b) + -(-core.window_rows // b) + 2
        n_chunks = max(1, min(n_chunks, steps_total))
        chunk_steps = -(-steps_total // n_chunks)
        n_chunks = -(-steps_total // chunk_steps)

        prev_np = self._prev_np
        assert prev_np is not None  # resident mode always builds prev table
        residency: Optional[StreamResidency] = getattr(src, "residency", None)
        resident_streams = (
            residency.lookup(src.streams.shape) if residency is not None
            else None
        )
        if resident_streams is not None:
            # Cross-pass residency: the stream array is already on device
            # from the previous pass — only the new prev table ships.
            streams_j = resident_streams
            h2d_rows = 0
            h2d_bytes = prev_np.size * 4
        else:
            streams_j = jnp.asarray(src.streams)
            h2d_rows = src.upload_rows
            h2d_bytes = src.upload_rows * 8 + prev_np.size * 4
        if residency is not None:
            residency.publish(streams_j, src.streams.shape)
        prev_j = jnp.asarray(prev_np)
        carry = self.carry

        def run_chunk(carry: Any) -> Any:
            return _run_scan_resident(
                carry, streams_j, self._m_real_j, self._allowed_j,
                self._caps_j, prev_j,
                core=core, n_steps=chunk_steps, n_shards=self.n_shards,
            )

        trace = self.trace
        traced = trace.enabled
        outs = []
        calls = 0
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            if traced:
                t_call = time.perf_counter()
                cc0 = scan_compile_counts()["run_scan_resident"]
            carry, out = run_chunk(carry)
            calls += 1
            # Device handles only — materializing here would sync the host
            # to every chunk and serialize dispatch (SC003); the transfer
            # happens once, after the stepping loop.
            outs.append(out)
            if traced:
                # Dispatch-only span: the provisioned loop never syncs, so
                # this measures trace/compile/enqueue time, not device wall.
                trace.add_span(
                    "scan-call", "scan", t_call, time.perf_counter(),
                    attrs=dict(call=calls, steps=chunk_steps, mode="dispatch",
                               compiled=scan_compile_counts()[
                                   "run_scan_resident"] > cc0),
                )
            carry = self._recalibrate(carry, t0)
        drain_left = -(-m_max // chunk_steps) + 2
        # staticcheck: disable=SC003 drain termination must observe `assigned`; one sync per extra call, none in the provisioned loop
        while (np.asarray(carry.assigned) < self.m_per).any() and drain_left > 0:
            if traced:
                t_call = time.perf_counter()
            carry, out = run_chunk(carry)
            calls += 1
            outs.append(out)
            if traced:
                trace.add_span(
                    "scan-call", "scan", t_call, time.perf_counter(),
                    attrs=dict(call=calls, steps=chunk_steps, mode="drain"),
                )
            drain_left -= 1
        if traced:
            t_mat = time.perf_counter()
        outs = [jax.tree.map(np.asarray, o) for o in outs]
        if traced:
            trace.add_span(
                "materialize", "host", t_mat, time.perf_counter(),
                attrs=dict(calls=calls),
            )
        wall = time.perf_counter() - t0
        self.carry = carry
        return self._result(
            carry, wall,
            sidx=np.concatenate([o.sidx.reshape(z, -1) for o in outs], axis=1),
            p=np.concatenate([o.p.reshape(z, -1) for o in outs], axis=1),
            w_trace=np.concatenate([o.w_cap.reshape(z, -1) for o in outs], axis=1),
            scan_calls=calls, h2d_rows=h2d_rows, h2d_bytes=h2d_bytes,
            buffer_rows=src.per, steps_per_call=chunk_steps,
        )

    # -- ring (file) mode --------------------------------------------------
    def _run_ring(
        self,
        on_assign: Callable[[int, np.ndarray, np.ndarray], None],
        host: Optional[HostSerial] = None,
    ) -> DriveResult:
        src, core = self.source, self.core
        z = self.z
        m_max = int(self.m_per.max())
        S = src.scan_steps
        pipelined = src.prefetch > 0
        carry = self.carry
        iters = 0
        # Every step with a non-empty window assigns >= 1 edge per instance
        # (capacity caps sum to > m, so an allowed partition below cap always
        # exists), so total steps are bounded by m_max plus the window
        # build-up.
        max_iters = -(-(m_max + core.window_rows) // S) + 8
        # Host mirrors of the synced counters, one sync per scan call. The
        # loop body is ordered for the pipeline: top-up refill (true cursor)
        # -> dispatch scan k -> SPECULATIVE refill for call k+1 (the
        # guaranteed-progress lower bound, enqueued before the sync so the
        # h2d overlaps scan k) -> the one assigned/cursor sync -> emit.
        # At prefetch=0 the speculative refill is skipped and the sequence
        # of refills/scans is identical to the classic synchronous loop.
        assigned = np.zeros((z,), np.int64)
        cursors = np.zeros((z,), np.int64)
        trace = self.trace
        traced = trace.enabled
        host = HostSerial() if host is None else host
        # The recalibration reads the device's score-row counter.
        recal_reads = int(self.has_budget and not self.fixed_cost)
        done_before = 0
        try:
            buf = src.alloc()
            t0 = time.perf_counter()
            while not (assigned >= self.m_per).all():
                iters += 1
                assert iters <= max_iters, (
                    f"streaming scan failed to converge: {assigned} of "
                    f"{self.m_per} assigned after {iters} calls"
                )
                buf = src.refill(buf, cursors)
                # Dispatch -> speculative refill -> the per-call sync ->
                # emit: the whole host wait for scan call k.
                t_call = time.perf_counter()
                host.dispatch(t_call)
                call = trace.span("scan-call", "scan").open(t_call)
                if traced:
                    cc0 = scan_compile_counts()["run_scan_ring"]
                dispatch = trace.span("dispatch", "dispatch").open(t_call)
                (carry, buf), out = _run_scan_ring(
                    (carry, buf), self._m_real_j, self._allowed_j,
                    self._caps_j,
                    core=core, n_steps=S, n_shards=self.n_shards,
                )
                dispatch.close()
                if pipelined:
                    # Safe without syncing: the in-flight call advances
                    # every unfinished instance by >= S assignments, so rows
                    # below lb are dead for every future scan; the donated
                    # ring orders this write after the in-flight scan.
                    lb = np.minimum(assigned + S, self.m_per)
                    buf = src.refill(buf, lb, speculative=True)
                sync = trace.span("sync", "sync").open()
                # staticcheck: disable=SC003 ring-mode termination: ONE assigned-counter sync per scan call, amortized over S steps
                assigned = np.asarray(carry.assigned).astype(np.int64)
                # staticcheck: disable=SC003 next refill needs the host cursor to size disk reads; same single sync point per call
                cursors = np.asarray(carry.cursor).astype(np.int64)
                # staticcheck: disable=SC003 file mode streams placements to on_assign to stay O(chunk) — per-call materialization is the design
                sidx = np.asarray(out.sidx).reshape(z, -1)
                # staticcheck: disable=SC003 same spill materialization as sidx above
                pout = np.asarray(out.p).reshape(z, -1)
                t_synced = time.perf_counter()
                sync.close(t_synced)
                host.synced(t_synced, 4)
                emit = trace.span("emit", "emit").open(t_synced)
                for i in range(z):
                    live = sidx[i] >= 0
                    if live.any():
                        on_assign(
                            i, sidx[i][live].astype(np.int64), pout[i][live]
                        )
                emit.close()
                if traced:
                    # `rows` stays an np scalar (no int() on synced mirrors
                    # on this hot path); the exporter unwraps it.
                    done = assigned.sum()
                    call.set(call=iters, steps=S, rows=done - done_before,
                             compiled=scan_compile_counts()[
                                 "run_scan_ring"] > cc0)
                    done_before = done
                call.close()
                carry = self._recalibrate(carry, t0)
                host.syncs += recal_reads
            assert (cursors <= src.hi).all(), (
                f"scan cursors {cursors} overran uploaded rows {src.hi}"
            )
            wall = time.perf_counter() - t0
        finally:
            src.close()
        self.carry = carry
        self.ring_handle = RingHandle(
            buf=buf, hi=src.hi.copy(), B=src.B, z=z, m_per=self.m_per.copy()
        )
        # The final counters are device→host reads too, after the loop.
        with trace.span("result", "host"):
            return self._result(
                carry, wall, sidx=None, p=None, w_trace=None,
                scan_calls=iters, h2d_rows=src.h2d_rows,
                h2d_bytes=src.h2d_bytes, buffer_rows=src.B, steps_per_call=S,
                h2d_wait_s=src.h2d_wait_s, prefetch_depth=src.prefetch,
                refill_spans=src.refill_spans,
                spans_prestaged=src.spans_prestaged,
                spans_missed=src.spans_missed,
                prestage_wall_s=src.prestage_wall_s,
            )

    def _result(
        self,
        carry: Any,
        wall: float,
        *,
        sidx: Optional[np.ndarray],
        p: Optional[np.ndarray],
        w_trace: Optional[np.ndarray],
        scan_calls: int,
        h2d_rows: int,
        h2d_bytes: int,
        buffer_rows: int,
        steps_per_call: int,
        h2d_wait_s: float = 0.0,
        prefetch_depth: int = 0,
        refill_spans: int = 0,
        spans_prestaged: int = 0,
        spans_missed: int = 0,
        prestage_wall_s: float = 0.0,
    ) -> DriveResult:
        cnt = self.core.counters(carry)
        return DriveResult(
            sidx=sidx,
            p=p,
            w_trace=w_trace,
            assigned=np.asarray(carry.assigned),
            score_rows=np.asarray(cnt["score_rows"]),
            final_w=np.asarray(cnt["final_w"]),
            lam=np.asarray(cnt["lam"]),
            cost_per_score=np.asarray(cnt["cost_per_score"]),
            wall_time_s=wall,
            r_sel=self.r_sel,
            backend=self.backend,
            n_shards=self.n_shards,
            scan_path=self.scan_path,
            scan_calls=scan_calls,
            h2d_rows=int(h2d_rows),
            h2d_bytes=int(h2d_bytes),
            buffer_rows=int(buffer_rows),
            scan_steps_per_call=int(steps_per_call),
            h2d_wait_s=float(h2d_wait_s),
            prefetch_depth=int(prefetch_depth),
            refill_spans=int(refill_spans),
            spans_prestaged=int(spans_prestaged),
            spans_missed=int(spans_missed),
            prestage_wall_s=float(prestage_wall_s),
        )

    def run(
        self,
        *,
        n_chunks: int = 8,
        on_assign: Optional[Callable[[int, np.ndarray, np.ndarray], None]] = None,
        host: Optional[HostSerial] = None,
    ) -> DriveResult:
        """Drive the scan to completion.

        Resident sources step through ``n_chunks`` provisioned scan calls
        (+ drain) and return the collected step outputs; file sources loop
        refill→scan until every instance has assigned its stream, emitting
        finished placements through ``on_assign(i, local_idx, p)`` (required
        — the file path never holds O(m) outputs) and accounting host-serial
        time and syncs into ``host``.
        """
        if self.source.resident:
            return self._run_resident(n_chunks)
        assert on_assign is not None, "file-mode driving requires on_assign"
        return self._run_ring(on_assign, host)

    def stats_base(self, res: DriveResult, instance: int) -> dict:
        """The shared per-instance stat fields every caller reports."""
        return dict(
            k=self.core.k,
            name=self.core.name,
            wall_time_s=res.wall_time_s,
            score_rows=int(res.score_rows[instance]),
            score_count=int(res.score_rows[instance]) * self.core.k,
            final_w=int(res.final_w[instance]),
            lam_final=float(res.lam[instance]),
            assigned=int(res.assigned[instance]),
            warm=self.warm,
            r_sel=res.r_sel,
            modeled_cost_per_score=float(res.cost_per_score[instance]),
            scan_calls=res.scan_calls,
            scan_path=res.scan_path,
            h2d_rows=res.h2d_rows,
            h2d_bytes=res.h2d_bytes,
            buffer_rows=res.buffer_rows,
            scan_steps_per_call=res.scan_steps_per_call,
            h2d_wait_s=res.h2d_wait_s,
            prefetch_depth=res.prefetch_depth,
            refill_spans=res.refill_spans,
            spans_prestaged=res.spans_prestaged,
            spans_missed=res.spans_missed,
            prestage_wall_s=res.prestage_wall_s,
            vertex_table_bytes=self.vertex_table_bytes,
        )
