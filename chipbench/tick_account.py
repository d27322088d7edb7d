"""The host's half of a serving tick, and what each tick computed, from the
program's own spans in a profiler trace (PR 36).

    python chipbench/tick_account.py <trace dir | .xplane.pb | .program.json.gz>

prints one row a tick and the means: the start-to-start period, where the host
spent it (admit, the builds, the tables, the launch, the booking of what was
sent, the blocking read, the emits, publish, the driver between ticks) and what
the dispatch held.  With a ``.xplane.pb`` (or trace.py's raw lists beside a
``.program.json.gz``, as ``CHIPBENCH_KEEP_RAW`` leaves them) it also brackets
the offset between the device's clock and the host's in that trace.

What the engine writes (``accelerate_tpu/serving/engine.py``):

- ``serving.tick`` carries what the tick's dispatch held, as integers:
  ``rows_live``, ``rows_computed``, ``width``, ``width_lanes``, ``mixed``,
  ``pipelined``, ``settles``; the times are the spans' own;
- inside the ``wait`` span lie ``serving.tick.launch`` (the jitted call alone)
  and ``serving.tick.read`` (the blocking read; ``of``: the tick that is read);
  the ``wait`` opens at the launch: the tables are filled before it under no
  span of their own, so they are the tick's self time;
- the emit span that yields first tokens carries their account as sums:
  ``first_tokens``, ``held_ticks``, ``own_ticks``.

``serve.row_fill`` and ``serve.turn_wait_share`` are samples: the traced span
is 4 s of a 45 s window, 230-480 ticks and 27-38 first tokens in the cells of
PR 36 (``PERF.md`` section 3 has the spread they showed from run to run).

``ticks(path)`` gives the rows; the five functions below it are the readers'
(``readers/serve.host_busy_share.py`` and its four neighbours).  Each returns
``None`` on a trace whose ``serving.tick`` spans lack the record: a program
older than PR 36, the four older fixtures.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TICK = "serving.tick"
READ, LAUNCH = TICK + ".read", TICK + ".launch"
RECORD = ("rows_live", "rows_computed", "width", "width_lanes", "pipelined")
FIRST_TOKENS = ("first_tokens", "held_ticks", "own_ticks")
FEWEST_FIRST_TOKENS = 16  # under this many first tokens in the traced span their account is not a reading
# the table's columns that sum spans: column, then the spans (tables: the tick's self time; booking: the wait's)
COLUMNS = (
    ("admit", ("admit",)), ("build", ("prefill.build", "decode.build")), ("launch", ("launch",)), ("read", ("read",)),
    ("emit", ("prefill.emit", "decode.emit")), ("publish", ("publish",)),
)


def program_trace():
    """``chipbench/program_trace.py``, loaded by path as ``run.py:load_module`` loads."""
    name = "chipbench__program_trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "program_trace.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def ticks(path: str):
    """One row for each ``serving.tick`` span that starts inside the traced
    span and has a tick after it in the trace: its stats, ``start``, ``end``,
    ``period_s`` (start to the next tick's start: the driver's ``submit`` and
    ``pop_finished`` between two ticks are in it, they run on the same thread),
    ``read_s`` (the ``read`` spans that start in the period) and ``phase_s``
    (seconds by span name without the prefix, of the spans that start in the
    period; ``wait``: the two ``wait`` spans less their children, which is the
    booking of what was sent; ``tick``: the tick's span less the children that
    start in it, which is the tables and what else runs under no span of its
    own).  ``None`` where the spans carry no record."""
    pt = program_trace()
    window = pt.traced_window(path)
    spans = pt.host_spans(path, TICK)
    tick_spans = [s for s in spans if s[0] == TICK]
    inside = [s for s in tick_spans if window is not None and window[0] <= s[1] < window[1]]
    if not inside or any(key not in inside[0][3] for key in RECORD):
        return None
    parts = [s for s in spans if s[0] != TICK]
    rows, at = [], 0
    for (_, start, end, meta), after in zip(tick_spans, tick_spans[1:]):
        if not window[0] <= start < window[1]:
            continue
        phase_s, children = {}, 0.0
        while at < len(parts) and parts[at][1] < start:
            at += 1
        while at < len(parts) and parts[at][1] < after[1]:
            name, t0, t1, _ = parts[at]
            phase_s[name[len(TICK) + 1 :]] = phase_s.get(name[len(TICK) + 1 :], 0.0) + t1 - t0
            children += t1 - t0 if t0 < end and name not in (LAUNCH, READ) else 0.0  # the two lie inside a wait
            at += 1
        waits = phase_s.pop("prefill.wait", 0.0) + phase_s.pop("decode.wait", 0.0)
        phase_s["wait"] = waits - phase_s.get("launch", 0.0) - phase_s.get("read", 0.0)
        phase_s["tick"] = end - start - children
        rows.append(dict(meta, start=start, end=end, period_s=after[1] - start, read_s=phase_s.get("read", 0.0), phase_s=phase_s))
    return rows or None


def host_busy_share(path: str):
    """Percent of the ticks' periods that the host's thread was not blocked in
    a ``read``: at 100 the device waits for the host."""
    rows = ticks(path)
    if not rows:
        return None
    return 100.0 * sum(r["period_s"] - r["read_s"] for r in rows) / sum(r["period_s"] for r in rows)


def row_fill(path: str):
    """Percent of the rows the dispatches computed that belonged to a request."""
    rows = ticks(path)
    computed = sum(r["rows_computed"] for r in rows or ())
    return 100.0 * sum(r["rows_live"] for r in rows) / computed if computed else None


def width_forced_share(path: str):
    """Percent of the ticks with decoding lanes whose table was wider than the lanes alone need: the chunk forced it."""
    with_lanes = [r for r in ticks(path) or () if r["width_lanes"] > 0]
    return 100.0 * sum(r["width"] > r["width_lanes"] for r in with_lanes) / len(with_lanes) if with_lanes else None


def pipelined_share(path: str):
    """Percent of the ticks that dispatched whose dispatch was made with the tick before it unread."""
    dispatched = [r for r in ticks(path) or () if r["rows_computed"] > 0]
    return 100.0 * sum(r["pipelined"] for r in dispatched) / len(dispatched) if dispatched else None


def first_tokens(path: str):
    """The first tokens' account summed over the emit spans that start inside
    the traced span: ``{"first_tokens", "held_ticks", "own_ticks"}``; ``None``
    where no span carries one."""
    pt = program_trace()
    window = pt.traced_window(path)
    if window is None:
        return None
    out = {}
    for _, start, _, meta in pt.host_spans(path, TICK + "."):
        if "first_tokens" in meta and window[0] <= start < window[1]:
            for key in FIRST_TOKENS:
                out[key] = out.get(key, 0) + meta[key]
    return out or None


def turn_wait_share(path: str):
    """Of the ticks a request was held from its admission to the tick that
    gave its first token, the percent in which no row of the dispatch was its
    own: it waited for its turn behind other slots' chunks."""
    account = first_tokens(path)
    if not account or account["first_tokens"] < FEWEST_FIRST_TOKENS or not account["held_ticks"]:
        return None
    return 100.0 * (1.0 - account["own_ticks"] / account["held_ticks"])


def read(run: dict, reading):
    """For the five readers: ``reading`` of the traced run's trace; ``None``
    without one, or without a device plane in it (a CPU run, as for the two
    idle readers: how much of a tick the host is blocked says nothing where the
    program's operations run on the host's own threads)."""
    path = run["traced"].get("raw_path")
    if not path or not program_trace().load(path)["ops"]:
        return None
    return reading(path)


# ---------------------------------------------------------------------------
# the two clocks of one trace
# ---------------------------------------------------------------------------


def executions(path: str) -> list:
    """``(start_s, end_s)`` of every execution of a ``jit_decode*`` program on
    the first device, by start: from the ``XLA Modules`` line of a
    ``.xplane.pb``, or from trace.py's raw lists kept beside a
    ``.program.json.gz`` (``CHIPBENCH_KEEP_RAW``)."""
    pt = program_trace()
    trace = pt.trace_module()
    if path.endswith(".program.json.gz"):
        raw = path[: -len(".program.json.gz")]
        programs = trace.load_raw(raw)["programs"] if os.path.isfile(raw) else []
    else:
        programs = []
        for plane, lines, event_names, _, _ in pt.read_planes(path):
            if plane.startswith("/device:"):
                programs += [[trace.program_name(event_names[mid]), start, dur, plane]
                             for name, events in lines if name == "XLA Modules" for mid, start, dur, _ in events]
    if not programs:
        return []
    device = min(p[3] for p in programs)
    return sorted((p[1], p[1] + p[2]) for p in programs if p[3] == device and p[0].startswith("jit_decode"))


def clock_bracket(path: str):
    """Bounds on ``offset`` = the device's clock less the host's in this trace,
    in seconds: ``(low, high, ticks)``.  A program's first operation cannot
    start before its ``launch`` span starts (``offset <= execution start -
    launch start``, the least over the ticks) and a ``read`` span cannot end
    before the program it reads has ended (``offset >= execution end - read
    end``, the largest).  Executions are matched to launches in order, one a
    launch, anchored at the first tick of the trace that has both spans: its
    execution is the one whose end lies nearest its ``read``'s end (the host
    is blocked in that read until the program ends; right while the offset is
    under half a tick).  ``None`` without executions or spans."""
    pt = program_trace()
    runs = executions(path)
    launches = sorted((s[3]["tick"], s) for s in pt.host_spans(path, LAUNCH))
    reads = {s[3]["of"]: s for s in pt.host_spans(path, READ)}
    anchor = next((rank for rank, (tick, _) in enumerate(launches) if tick in reads), None)
    if not runs or anchor is None:
        return None
    read_end = reads[launches[anchor][0]][2]
    shift = min(range(len(runs)), key=lambda i: abs(runs[i][1] - read_end)) - anchor
    low, high, matched = float("-inf"), float("inf"), 0
    for rank, (tick, launch) in enumerate(launches):
        if not 0 <= rank + shift < len(runs):
            continue
        start, end = runs[rank + shift]
        matched += 1
        high = min(high, start - launch[1])
        if tick in reads:
            low = max(low, end - reads[tick][2])
    return low, high, matched


# ---------------------------------------------------------------------------
# a look by hand
# ---------------------------------------------------------------------------


def table(path: str) -> None:
    rows = ticks(path)
    if not rows:
        print("no serving.tick span with the tick's record (a program older than PR 36, or no traced span)")
        return
    head = ["tick", "period", "host", "admit", "build", "tables", "launch", "booking", "read", "emit", "publish", "between"]
    print(" ".join(f"{h:>8s}" for h in head) + "  rows live/computed  width/lanes  mixed pipelined settles   (ms)")
    sums = dict.fromkeys(head[1:], 0.0)
    for r in rows:
        ms = {c: 1e3 * sum(r["phase_s"].get(p, 0.0) for p in parts) for c, parts in COLUMNS}
        ms.update(period=1e3 * r["period_s"], host=1e3 * (r["period_s"] - r["read_s"]), tables=1e3 * r["phase_s"]["tick"],
                  booking=1e3 * r["phase_s"]["wait"], between=1e3 * (r["period_s"] - (r["end"] - r["start"])))
        for key in sums:
            sums[key] += ms[key]
        print(f"{r['tick']:8d} " + " ".join(f"{ms[h]:8.3f}" for h in head[1:])
              + f"  {r['rows_live']:9d}/{r['rows_computed']:<8d}  {r['width']:5d}/{r['width_lanes']:<5d}"
              + f"  {r['mixed']:5d} {r['pipelined']:9d} {r['settles']:7d}")
    print(f"{'mean':>8s} " + " ".join(f"{sums[h] / len(rows):8.3f}" for h in head[1:]) + f"  over {len(rows)} ticks")
    readings = [("serve.host_busy_share", host_busy_share), ("serve.row_fill", row_fill), ("serve.width_forced_share", width_forced_share),
                ("serve.turn_wait_share", turn_wait_share), ("serve.pipelined_share", pipelined_share)]
    for name, reading in readings:
        print(f"{name} = {reading(path)}")
    print(f"first tokens: {first_tokens(path)}")
    bracket = clock_bracket(path)
    if bracket:
        low, high, matched = bracket
        print(f"device clock less host clock: between {1e6 * low:.1f} and {1e6 * high:.1f} us over {matched} ticks")


if __name__ == "__main__":
    target = sys.argv[1]
    table(program_trace().trace_module().find_xplane(target) if os.path.isdir(target) else target)
