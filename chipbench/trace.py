"""From a profiler trace to busy time, per-program device time, top operations
and idle gaps.

Two stages, so that the arithmetic can be checked on a recorded fixture:

- ``load_xplane(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
  (``jax.profiler.ProfileData``, nothing else) into plain lists: device
  operations ``[name, program, start_s, dur_s, device]``, program executions
  and host spans.  ``save_raw`` / ``load_raw`` keep that as gzipped JSON.
- ``reduce(raw)`` is interval arithmetic over those lists and imports nothing.

The interval functions are a copy of ``accelerate_tpu/telemetry/timeline.py``'s
(``merge_intervals``, ``intervals_total``, ``clip_intervals``), kept here so
that a later change there cannot move a metric.

A device operation is an event on the ``XLA Ops`` line of a ``/device:`` plane
(TPU), or any event that carries an ``hlo_op`` stat (the CPU backend, where the
tests record).  Its program is the ``XLA Modules`` event around it (TPU) or its
``hlo_module`` stat (CPU).  The traced window is the benchmark's own
``chipbench.traced`` span; host spans are the benchmark's other
``TraceAnnotation``s.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys

TRACED_SPAN = "chipbench.traced"
TOP_N = 10
SHORT_GAP_S = 20e-6  # gaps shorter than this lie between operations of one program
SHORT_GAPS = "(gaps under 20 us)"
NO_SPAN = "(no span)"


# ---------------------------------------------------------------------------
# interval arithmetic (copied from telemetry/timeline.py)
# ---------------------------------------------------------------------------


def merge_intervals(intervals: list) -> list:
    """Union of possibly-overlapping intervals, sorted and disjoint."""
    out: list = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def intervals_total(intervals: list) -> float:
    """Total covered length of a DISJOINT (merged) interval list."""
    return sum(end - start for start, end in intervals)


def clip_intervals(intervals: list, start: float, end: float) -> list:
    """Restrict a merged interval list to a window."""
    out = []
    for s, e in intervals:
        s2, e2 = max(s, start), min(e, end)
        if e2 > s2:
            out.append((s2, e2))
    return out


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def program_name(module_event: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``: executions of one program share a name."""
    return re.sub(r"\(\d+\)$", "", module_event)


MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
MOSAIC_MARK = " [tpu_custom_call]"


def short_name(event_name: str) -> str:
    """The TPU trace names an operation by its whole HLO line: keep the
    instruction's own name, and mark a Mosaic (Pallas) kernel as one."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return head + MOSAIC_MARK if MOSAIC_TARGET in event_name else head


def device_ops(events: list, modules: list, device: str) -> list:
    """``events``: (start_s, dur_s, full name) of one device's ``XLA Ops`` line,
    where a loop's event spans the events of its body.  Returns
    ``[name, program, start_s, dur_s, device, self_s]`` with ``self_s`` the
    part of the operation's time not covered by operations nested in it."""
    modules = sorted(modules)
    out, stack, j = [], [], 0
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while j + 1 < len(modules) and modules[j + 1][0] <= start:
            j += 1
        inside = modules and modules[j][0] <= start < modules[j][0] + modules[j][1]
        while stack and stack[-1][2] + stack[-1][3] <= start:
            stack.pop()
        if stack:
            stack[-1][5] -= min(dur, stack[-1][2] + stack[-1][3] - start)
        op = [short_name(name), modules[j][2] if inside else "?", start, dur, device, dur]
        out.append(op)
        stack.append(op)
    for op in out:
        op[5] = max(op[5], 0.0)
    return out


def load_xplane(path: str, span_names=()) -> dict:
    """Plain lists from one ``.xplane.pb``.  Times in seconds from the trace's
    own zero.  ``span_names``: host annotations to keep besides the traced span."""
    from jax.profiler import ProfileData

    keep = set(span_names) | {TRACED_SPAN}
    ops, programs, spans = [], [], []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name if plane.name.startswith("/device:") else None
        modules, plane_ops = [], []
        for line in plane.lines:
            if device is not None and line.name == "XLA Modules":
                modules = [(e.start_ns * 1e-9, e.duration_ns * 1e-9, program_name(e.name)) for e in line.events]
            elif device is not None and line.name == "XLA Ops":
                plane_ops = [(e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name) for e in line.events]
            elif device is None:
                for e in line.events:
                    if e.name in keep:
                        spans.append([e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9])
                    elif e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:  # the CPU backend: flat, so self time = duration
                            dur = e.duration_ns * 1e-9
                            ops.append([e.name, str(stats.get("hlo_module", "?")), e.start_ns * 1e-9, dur, "host", dur])
        if device is None:
            continue
        programs += [[name, start, dur, device] for start, dur, name in sorted(modules)]
        ops += device_ops(plane_ops, modules, device)
    if not programs:  # CPU: one "execution" per program name, for the readers' sake
        seen = {}
        for name, prog, start, dur, dev, _ in ops:
            s, e = seen.get(prog, (start, start + dur))
            seen[prog] = (min(s, start), max(e, start + dur))
        programs = [[prog, s, e - s, "host"] for prog, (s, e) in seen.items()]
    return {"ops": ops, "programs": programs, "spans": spans}


def save_raw(raw: dict, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(raw, f)


def load_raw(path: str) -> dict:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def reduce(raw: dict) -> dict:
    """Everything the readers and the result line take from a trace.

    ``window_s``: the ``chipbench.traced`` span.  ``busy_s``: the union of the
    device operations' intervals inside it, averaged over devices.
    ``program_s``: by program, the union of its operations' intervals (averaged
    over devices); ``executions``: how often it started in the window.
    ``op_s``: self time by operation name.
    ``idle_gaps``: the longest gaps between device operations, each charged to
    the host span that covers most of it.
    """
    traced = [s for s in raw["spans"] if s[0] == TRACED_SPAN]
    if not traced:
        return {}
    t0 = min(s[1] for s in traced)
    t1 = max(s[1] + s[2] for s in traced)
    devices = sorted({op[4] for op in raw["ops"]})
    if not devices or t1 <= t0:
        return {}
    n = len(devices)
    busy, program_s, op_s, gaps = 0.0, {}, {}, []
    for dev in devices:
        mine = [op for op in raw["ops"] if op[4] == dev and op[2] < t1 and op[2] + op[3] > t0]
        merged = clip_intervals(merge_intervals([(op[2], op[2] + op[3]) for op in mine]), t0, t1)
        busy += intervals_total(merged)
        by_program = {}
        for name, prog, start, dur, _, self_s in mine:
            by_program.setdefault(prog, []).append((start, start + dur))
            op_s[name] = op_s.get(name, 0.0) + self_s
        for prog, intervals in by_program.items():
            held = intervals_total(clip_intervals(merge_intervals(intervals), t0, t1))
            program_s[prog] = program_s.get(prog, 0.0) + held
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    executions = {}
    for name, start, dur, dev in raw["programs"]:
        if t0 <= start < t1:
            executions[name] = executions.get(name, 0) + 1
    spans = sorted((s[1], s[1] + s[2], s[0]) for s in raw["spans"] if s[0] != TRACED_SPAN)
    starts = [s[0] for s in spans]
    by_blame = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_S:
            best = SHORT_GAPS
        else:
            best, cover = NO_SPAN, 0.0
            i = bisect.bisect_left(starts, g1)
            for start, end, name in reversed(spans[max(0, i - 64) : i]):
                c = min(g1, end) - max(g0, start)
                if c > cover:
                    best, cover = name, c
        by_blame[best] = by_blame.get(best, 0.0) + (g1 - g0)
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]]  # noqa: E731
    return {
        "window_s": t1 - t0,
        "busy_s": busy / n,
        "devices": n,
        "program_s": {k: v / n for k, v in program_s.items()},
        "executions": {k: v / n for k, v in executions.items()},
        "op_s": {k: v / n for k, v in op_s.items()},
        "device_ops": top({k: v / n for k, v in op_s.items()}),
        "idle_gaps": top({k: v / n for k, v in by_blame.items()}),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gaps), default=0.0),
    }


def dump(path: str, limit: int = 12) -> None:
    """What a trace holds, for a look by hand: planes, lines, first events."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            by_name = {}
            for e in events:
                c, t = by_name.get(e.name, (0, 0.0))
                by_name[e.name] = (c + 1, t + e.duration_ns * 1e-9)
            for name, (c, t) in sorted(by_name.items(), key=lambda kv: -kv[1][1])[:limit]:
                print(f"    {t:10.6f} s  x{c:<6d} {name[:120]}")
            for e in events[:2]:
                print(f"    first: {e.name[:80]!r} start={e.start_ns} dur={e.duration_ns} stats={dict(e.stats)}")


if __name__ == "__main__":
    target = sys.argv[1]
    dump(find_xplane(target) if os.path.isdir(target) else target)
