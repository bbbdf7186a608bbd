"""Device time by the program's own phase names, host gaps by its spans,
and its counters, from the traced window's ``jax.profiler`` trace.

The program names its device phases with ``jax.named_scope`` (the ADWISE
step's ``adwise.window``/``score``/``pick``/``apply``, the engine's
``engine.gather``/``combine``/``apply``) and its host work with
``repro.<span>`` annotations (``repro.obs``). The names reach the trace
as follows:

- a device op's scope: its event metadata's ``tf_op`` stat, a path such as
  ``jit(_run_scan_ring)/vmap()/while/body/closed_call/adwise.score/sort:``;
  the op's scope is the innermost ``adwise.*``/``engine.*`` component, or
  none. ``jax.profiler.ProfileData`` does not expose metadata stats, so
  :func:`op_metadata` decodes those few fields of the xplane protobuf
  itself (field numbers of ``tsl/profiler/protobuf/xplane.proto``);
- an op's *self* time: its interval in the window less the ops nested in
  it on the ``XLA Ops`` line (a ``while`` encloses its body's ops);
- an idle gap's label: the innermost ``repro.*`` or ``bench.*`` annotation
  around its midpoint on the host thread that holds ``bench.window``;
- the program's counters: the metadata of the ``repro.partition_file``
  annotations (``host_serial_s``, ``host_syncs``, ``scan_calls``).

The readers in ``bench/metrics`` call :func:`summary`, which finds the
run's trace file beside the cell's work directory and reduces it once per
run. A program that names no phases (or writes no counters) reads as
nothing: the metrics that need the names are left out of its line.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from pathlib import Path

from bench.trace import op_label

ROOT = Path(__file__).resolve().parent.parent
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE = re.compile(r"(?:^|/)((?:adwise|engine)\.[A-Za-z_]+)(?=/|:|$)")
PROGRAM_ID = re.compile(r"\((\d+)\)$")
SCAN = r"_run_scan_ring"
SUPERSTEP = r"jit_step"

# xplane.proto field numbers (tensorflow/tsl/profiler/protobuf/xplane.proto)
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
XEVENT_METADATA_NAME, XEVENT_METADATA_STATS = 2, 5
XSTAT_METADATA_ID, XSTAT_UINT64, XSTAT_INT64, XSTAT_STR, XSTAT_REF = 1, 3, 4, 5, 7
XSTAT_METADATA_NAME = 2


# ----------------------------------------------------------------------------
# The protobuf wire format, for the few fields the profiler API leaves out
# ----------------------------------------------------------------------------


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of each field of the message in
    ``buf[start:end]``: an int for a varint or fixed-width field, a
    ``(start, end)`` pair for a length-delimited one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _text(buf, span: tuple) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_metadata(path) -> dict:
    """``{plane name: {(op name, program id): tf_op}}`` for the device
    planes of an xplane file: the program id and ``tf_op`` stats of each
    event metadata entry (the name the ``XLA Ops`` events carry)."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != XSPACE_PLANES:
            continue
        name, entries, stat_names = "", [], {}
        for field, value in _fields(buf, *plane):
            if field == XPLANE_NAME:
                name = _text(buf, value)
            elif field == XPLANE_EVENT_METADATA:
                entries.append(value)
            elif field == XPLANE_STAT_METADATA:
                for key, entry in _fields(buf, *value):
                    if key == MAP_VALUE:
                        meta = dict(_fields(buf, *entry))
                        stat_names[meta.get(XSTAT_METADATA_ID, 0)] = _text(
                            buf, meta.get(XSTAT_METADATA_NAME, (0, 0)))
        if not DEVICE_PLANE.match(name):
            continue
        ops = {}
        for entry in entries:
            op, stats = "", []
            for key, value in _fields(buf, *entry):
                if key != MAP_VALUE:
                    continue
                for field, v in _fields(buf, *value):
                    if field == XEVENT_METADATA_NAME:
                        op = _text(buf, v)
                    elif field == XEVENT_METADATA_STATS:
                        stats.append(dict(_fields(buf, *v)))
            program, tf_op = None, None
            for stat in stats:
                kind = stat_names.get(stat.get(XSTAT_METADATA_ID))
                if kind == "program_id":
                    program = stat.get(XSTAT_UINT64, stat.get(XSTAT_INT64))
                elif kind == "tf_op":
                    if XSTAT_STR in stat:
                        tf_op = _text(buf, stat[XSTAT_STR])
                    elif XSTAT_REF in stat:
                        tf_op = stat_names.get(stat[XSTAT_REF])
            if tf_op is not None:
                ops[(op, program)] = tf_op
        out[name] = ops
    return out


def scope_of(tf_op) -> str | None:
    """The innermost ``adwise.*``/``engine.*`` component of a ``tf_op``
    path, or None."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else None


# ----------------------------------------------------------------------------
# The reduction
# ----------------------------------------------------------------------------


def self_times(intervals: list) -> list:
    """Each interval's length less the intervals nested directly in it
    (``intervals`` sorted by start, then by end descending)."""
    own = [e - s for s, e in intervals]
    stack: list = []  # (end, index) of the open enclosing intervals
    for j, (s, e) in enumerate(intervals):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            end, parent = stack[-1]
            own[parent] -= min(e, end) - s
        stack.append((e, j))
    return own


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


def reduce_profile(pd, meta: dict) -> dict:
    """The summary of one ``ProfileData`` and its :func:`op_metadata`
    (times in seconds, device times averaged over the chips that ran an
    operation in the window)."""
    window, marks, counters = None, [], []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name) for ev in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            devices.append((plane.name, lines.get(OPS_LINE, []),
                            lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                events = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name, ev) for ev in line.events
                          if ev.name.startswith(("bench.", "repro."))]
                held = [(s, e) for s, e, name, _ in events
                        if name == "bench.window"]
                if held and window is None:
                    window = held[0]
                    marks = [(s, e, name) for s, e, name, _ in events
                             if name != "bench.window"]
                counters += [(s, e, dict(ev.stats)) for s, e, name, ev in events
                             if name == "repro.partition_file"]
    if window is None:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = window

    modules, scopes = defaultdict(float), defaultdict(lambda: defaultdict(float))
    unscoped = defaultdict(lambda: defaultdict(float))
    first_ops, active, events = None, 0, 0
    for plane, ops, mods in devices:
        events += len(ops) + len(mods)
        inside = sorted(((max(s, lo), min(e, hi), name) for s, e, name in ops
                         if e > lo and s < hi), key=lambda o: (o[0], -o[1]))
        if not inside:
            continue
        active += 1
        intervals = [(s, e) for s, e, _ in inside]
        if first_ops is None:
            first_ops = intervals
        runs = sorted((max(s, lo), min(e, hi), name) for s, e, name in mods
                      if e > lo and s < hi)
        for s, e, name in runs:
            modules[name] += e - s
        pids = [PROGRAM_ID.search(name) for _, _, name in runs]
        pids = [int(pid.group(1)) if pid else None for pid in pids]
        names = meta.get(plane, {})
        m = 0
        for (s, e, op), own in zip(inside, self_times(intervals)):
            while m < len(runs) and runs[m][1] <= s:
                m += 1
            if m == len(runs) or runs[m][0] > s:
                continue  # an op outside every program run
            scope = scope_of(names.get((op, pids[m])))
            scopes[runs[m][2]][scope] += own
            if scope is None:
                unscoped[runs[m][2]][op] += own
    active = max(active, 1)

    def label(t: float) -> str:
        around = [(e - s, name) for s, e, name in marks if s <= t <= e]
        return min(around)[1] if around else "bench.window"

    idle = sorted(gaps(first_ops or [], lo, hi), key=lambda g: g[0] - g[1])[:10]
    return dict(
        window_s=(hi - lo) * 1e-9,
        device_events=events,
        modules={name: t / active * 1e-9 for name, t in modules.items()},
        scopes={program: {scope: t / active * 1e-9 for scope, t in by.items()}
                for program, by in scopes.items()},
        unscoped={program: [[op_label(op), t / active * 1e-9] for op, t in
                            sorted(by.items(), key=lambda kv: -kv[1])[:8]]
                  for program, by in unscoped.items()},
        idle_gaps=[[label((s + e) / 2), (e - s) * 1e-9] for s, e in idle],
        counters=[stats for s, e, stats in counters if s >= lo and e <= hi],
    )


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), op_metadata(path))


def describe(s: dict) -> str:
    """Three log lines: device time by scope in each program that names
    its phases, the longest ops under none of them, the labelled gaps."""
    programs = [p for p, by in s["scopes"].items() if any(by)]
    scoped = "; ".join(f"{p} " + ", ".join(
        f"{k or '(none)'} {t:.6f} s"
        for k, t in sorted(s["scopes"][p].items(), key=lambda kv: -kv[1]))
        for p in programs)
    unscoped = "; ".join(f"{p} " + ", ".join(
        f"{op} {t:.6f} s" for op, t in s["unscoped"].get(p, []))
        for p in programs)
    gaps = ", ".join(f"{name} {t:.6f} s" for name, t in s["idle_gaps"])
    return f"scopes: {scoped}\nunscoped ops: {unscoped}\nidle gaps: {gaps}"


def summary(ctx, root: Path | None = None) -> dict | None:
    """The summary of the trace of the run whose metrics are being read:
    the newest trace file under ``<root>/runs/bench/*/trace``, where its
    window is the one ``ctx["trace"]`` reports; None where there is none.
    Reduced once per run (kept in ``ctx``), when it prints its scopes, the
    longest ops under none of them, and the labelled idle gaps."""
    if "scopes" not in ctx:
        root = ROOT if root is None else Path(root)
        paths = glob.glob(str(root / "runs" / "bench" / "*" / "trace" / "**"
                              / "*.xplane.pb"), recursive=True)
        s = reduce_file(max(paths, key=os.path.getmtime)) if paths else None
        if s is not None and abs(s["window_s"]
                                 - ctx["trace"]["window_s"]) > 1e-9:
            s = None  # another run's trace
        if s is not None:
            print(describe(s), flush=True)
        ctx["scopes"] = s
    return ctx["scopes"]


def named(s: dict, program: str, family: str) -> bool:
    """Whether any op of the programs matching ``program`` carries a scope
    of ``family`` (``"adwise."``, ``"engine."``)."""
    rx = re.compile(program)
    return any(scope and scope.startswith(family)
               for name, by in s["scopes"].items() if rx.search(name)
               for scope in by)


def scope_time(s: dict, program: str, scope: str | None) -> float:
    """Device self-seconds of the ops under ``scope`` (None: under no
    scope) in the programs whose name matches ``program``. Where the
    programs did not run, or ran and no op carries the scope, the name was
    changed or left the path: that raises ``LookupError``, as
    ``bench.trace.module_time`` does, so a metric does not fall silent."""
    rx = re.compile(program)
    names = [n for n in s["scopes"] if rx.search(n)]
    if not names:
        raise LookupError(
            f"no program matching {program!r} ran in the traced window; "
            f"programs that ran: {sorted(s['scopes'])}")
    times = [s["scopes"][n][scope] for n in names if scope in s["scopes"][n]]
    if not times:
        raise LookupError(
            f"no op of {names} carries the scope {scope!r}; scopes: "
            f"{sorted({k or '' for n in names for k in s['scopes'][n]})}")
    return sum(times)


# ----------------------------------------------------------------------------
# What the readers share
# ----------------------------------------------------------------------------


def scan_us_per_step(ctx, scope: str | None) -> float | None:
    """Device self-time of the ring scan under ``scope`` per scan step."""
    s = summary(ctx)
    if s is None or not s["device_events"] or not named(s, SCAN, "adwise."):
        return None
    steps = sum(r["stats"]["scan_calls"] * r["stats"]["scan_steps_per_call"]
                for r in ctx["results"])
    return scope_time(s, SCAN, scope) / steps * 1e6 if steps else None


def superstep_ms(ctx, scope: str) -> float | None:
    """Device self-time of the engine superstep under ``scope`` per
    superstep."""
    s = summary(ctx)
    if s is None or not s["device_events"] or not named(s, SUPERSTEP,
                                                        "engine."):
        return None
    steps = sum(r["work"] for r in ctx["results"])
    return scope_time(s, SUPERSTEP, scope) / steps * 1e3 if steps else None


def counters(ctx) -> list | None:
    """The program's counters of each ``partition_file`` call in the
    window. Read, like the device metrics beside them, only from a trace
    with a device timeline."""
    s = summary(ctx)
    if s is None or not s["device_events"]:
        return None
    found = [c for c in s["counters"] if "host_serial_s" in c]
    return found or None
