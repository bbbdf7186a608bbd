"""Reduce a ``jax.profiler`` trace to the numbers the benchmark reports.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Planes named ``/device:TPU:<i>`` hold
the chips' timelines: the line ``XLA Ops`` has one event per operation run
and ``XLA Modules`` one per program run. Host planes hold the
benchmark's ``bench.*`` annotations (``jax.profiler.TraceAnnotation``) on
the same clock. From them:

- ``window_s``: the length of the ``bench.window`` annotation;
- ``busy_s``: the union of operation intervals inside the window, averaged
  over the chips that ran any operation;
- ``modules``: device seconds per program name (its ``XLA Modules`` events
  inside the window), averaged over those chips, and ``module_runs``;
- ``device_ops``: the ten operations with the most device time;
- ``idle_gaps``: the ten longest gaps between operations on the first chip,
  each named by the innermost ``bench.*`` annotation around its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HLO = re.compile(r"^(%[\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """``%fusion.3 fusion s32[1,4194305]{..}`` from an HLO op's full text:
    its name, opcode and result type (cut to 80 characters)."""
    m = HLO.match(name)
    if not m:
        return name[:120]
    return f"{m.group(1)} {m.group(3)} {m.group(2)[:80]}"


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_length(intervals: list) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: list, lo: float, hi: float) -> list:
    """(start, end) of every stretch of [lo, hi] that no interval covers."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def reduce_profile(pd, n_chips: int = 1) -> dict:
    """The summary of one ``ProfileData`` (times in seconds)."""
    spans = []  # (start_ns, end_ns, name) of bench.* host annotations
    devices = []  # per chip: (op events, program events)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name) for ev in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            devices.append((lines.get(OPS_LINE, []),
                            lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name))
    windows = [(s, e) for s, e, name in spans if name == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9

    busy, modules, runs = [], defaultdict(float), defaultdict(int)
    op_time = defaultdict(float)
    first_ops = None
    active = 0
    for ops, mods in devices[:n_chips]:
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in ops
                  if e > lo and s < hi]
        if not inside:
            continue
        active += 1
        intervals = [(s, e) for s, e, _ in inside]
        if first_ops is None:
            first_ops = intervals
        busy.append(union_length(intervals))
        for s, e, name in inside:
            op_time[op_label(name)] += e - s
        for s, e, name in mods:
            if e > lo and s < hi:
                modules[name] += min(e, hi) - max(s, lo)
                runs[name] += 1
    active = max(active, 1)
    events = sum(len(ops) + len(mods) for ops, mods in devices)
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]

    def label(t: float) -> str:
        around = [(e - s, name) for s, e, name in spans
                  if s <= t <= e and name != "bench.window"]
        return min(around)[1] if around else "bench.window"

    idle = sorted(gaps(first_ops or [], lo, hi), key=lambda g: g[0] - g[1])[:10]
    return dict(
        window_s=window_s,
        device_events=events,
        busy_s=sum(busy) / active * 1e-9,
        modules={name: t / active * 1e-9 for name, t in modules.items()},
        module_runs={name: n // active for name, n in runs.items()},
        device_ops=[[name, t / active * 1e-9] for name, t in ranked],
        idle_gaps=[[label((s + e) / 2), (e - s) * 1e-9] for s, e in idle],
    )


def reduce_file(path, n_chips: int = 1) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), n_chips)


def module_time(summary: dict, pattern: str) -> tuple:
    """(device seconds, runs) of the programs whose name matches
    ``pattern`` (a regular expression): (0, 0) in a trace with no device
    operations. Where the device ran programs and none matches, the
    program was renamed or left the path; that raises ``LookupError``, so a
    metric does not fall silent."""
    rx = re.compile(pattern)
    names = [n for n in summary["modules"] if rx.search(n)]
    if not names and summary["device_events"]:
        raise LookupError(
            f"no program matching {pattern!r} ran in the traced window; "
            f"programs that ran: {sorted(summary['modules'])}")
    return (sum(summary["modules"][n] for n in names),
            sum(summary["module_runs"][n] for n in names))
