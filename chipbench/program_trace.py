"""What the program says of itself in a profiler trace: its host spans
(``serving.tick.*``, through ``accelerate_tpu.telemetry.annotate``) and the
``jax.named_scope``s in the ``op_name`` of its device operations.

    python chipbench/program_trace.py <trace dir | .xplane.pb | .program.json.gz>

prints device time by scope and device-idle time by span, for a look by hand.

``load(path)`` reads the ``.xplane.pb`` itself (``chipbench/trace.py`` keeps
host spans by the benchmark's names only and cuts operation names short; why
not through ``jax.profiler.ProfileData`` is said above ``read_planes``) into
plain lists, or a ``.program.json.gz`` that ``save`` wrote.  With ``CHIPBENCH_KEEP_RAW=<path>`` set, as for ``run.py``'s
own raw lists, a traced run's lists are kept as ``<path>.program.json.gz``: how
``fixtures/chat_closed16.tpu_v5e.program.json.gz`` was recorded.  The three
functions the readers use take the same path:

- ``host_spans(path, prefix)``: name, start, end, metadata of the host spans
  whose name starts with ``prefix``;
- ``scope_seconds(path, program)``: self time of the device operations of the
  programs whose name starts with ``program``, by the innermost known scope of
  their ``op_name``, the part under ``rematted_computation`` beside it;
- ``idle_under(path, span_names)``: device-idle time inside the
  ``chipbench.traced`` span that lies under the named host spans.

Each returns nothing to read (``[]``, ``None``, ``None``) on a trace without
such spans or scopes: a CPU run, the parent of the PR that added them.
Interval arithmetic and the nesting of device operations are ``trace.py``'s.
"""

from __future__ import annotations

import functools
import gzip
import importlib.util
import json
import os
import re
import struct
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

# jax.named_scope names the program uses (PERF.md section 3), outermost first where they nest
SCOPES = (
    "loss_grad", "clip", "optimizer", "embed", "layers", "head_loss", "head",
    "attn", "attn.qkv", "attn.core", "attn.out", "mlp", "kv_pool", "kv_pool.gather", "kv_pool.write",
)
REMATTED = "rematted_computation"
NO_SCOPE = "(no scope)"
TICK_SPAN = "serving.tick"


def trace_module():
    """``chipbench/trace.py`` under the module name ``run.py:load_module`` gives it."""
    name = "chipbench__trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "trace.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
#
# On the v5e (libtpu 0.0.34) an operation's event is named by its HLO line
# without the ``metadata={...}`` part, and the ``op_name`` is the ``tf_op`` stat
# of the event's *metadata* record (``XEventMetadata.stats``), which
# ``jax.profiler.ProfileData`` does not give out: its ``event.stats`` are the
# event's own (``device_offset_ps``, ``device_duration_ps``).  So the file is
# read here by protobuf's wire format, with the field numbers of
# ``tsl/profiler/protobuf/xplane.proto``, and nothing is imported for it.

SPAN_NAME = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
OP_NAME_STAT = "tf_op"


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a memoryview
    for a length-delimited field, the raw 8 or 4 bytes for a fixed one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i : i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """XStat -> (name, value); a ``ref_value`` names another stat record."""
    name, value = None, None
    for field, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif field == 5:
            value = _text(v)
        elif field == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def read_planes(path: str):
    """Each XPlane of the file as ``(name, lines, event_names, event_ops,
    stat_names)``: ``lines`` = ``[(line name, [(metadata id, start_s, dur_s,
    the event's own stats, undecoded)])]``; ``event_names`` and ``event_ops``
    (the ``OP_NAME_STAT`` of the metadata record) by metadata id;
    ``stat_names`` by stat id, for ``_stat``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, raw_lines, raw_events, stat_names = "", [], [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 3:
                raw_lines.append(v)
            elif pf == 4:
                raw_events.append(_map_entry(v)[1])
            elif pf == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next((_text(x) for f2, x in _fields(meta) if f2 == 2), "")
        event_names, event_ops = {}, {}
        for meta in raw_events:
            mid, mname = None, ""
            for mf, v in _fields(meta):
                if mf == 1:
                    mid = v
                elif mf == 2:
                    mname = _text(v)
                elif mf == 5:
                    stat_name, value = _stat(v, stat_names)
                    if stat_name == OP_NAME_STAT:
                        event_ops[mid] = str(value)
            event_names[mid] = mname
        lines = []
        for line in raw_lines:
            lname, t0_ns, events = "", 0, []
            for lf, v in _fields(line):
                if lf == 2:
                    lname = _text(v)
                elif lf == 3:
                    t0_ns = v
                elif lf == 4:
                    events.append(v)
            decoded = []
            for event in events:
                mid, offset_ps, dur_ps, stats = None, 0, 0, []
                for ef, v in _fields(event):
                    if ef == 1:
                        mid = v
                    elif ef == 2:
                        offset_ps = v
                    elif ef == 3:
                        dur_ps = v
                    elif ef == 4:
                        stats.append(v)
                decoded.append((mid, t0_ns * 1e-9 + offset_ps * 1e-12, dur_ps * 1e-12, stats))
            lines.append((lname, decoded))
        yield name, lines, event_names, event_ops, stat_names


def load_xplane(path: str) -> dict:
    """``{"ops": [[name, program, start_s, dur_s, device, self_s, op_name]],
    "spans": [[name, start_s, end_s, meta]]}``: every device operation with
    its ``op_name`` (a trailing colon cut), every host event named in dotted
    lower-case words (the program's spans and the benchmark's own traced
    span; the profiler's Python events start with ``$``) with its keywords."""
    trace = trace_module()
    ops, spans = [], []
    for plane, lines, event_names, event_ops, stat_names in read_planes(path):
        if not plane.startswith("/device:"):
            for _, events in lines:
                for mid, start, dur, stats in events:
                    name = event_names.get(mid, "")
                    if dur > 0 and SPAN_NAME.match(name):
                        spans.append([name, start, start + dur, dict(_stat(s, stat_names) for s in stats)])
            continue
        modules, events = [], []
        for lname, line_events in lines:
            if lname == "XLA Modules":
                modules = [(start, dur, trace.program_name(event_names[mid])) for mid, start, dur, _ in line_events]
            elif lname == "XLA Ops":
                events = [(start, dur, event_names[mid], event_ops.get(mid, "").rstrip(":")) for mid, start, dur, _ in line_events]
        events.sort(key=lambda e: (e[0], -e[1]))  # device_ops' own order (a stable sort), so the two zip
        nested = trace.device_ops([e[:3] for e in events], modules, plane)
        ops += [op + [e[3]] for op, e in zip(nested, events)]
    return {"ops": ops, "spans": spans}


def save(data: dict, path: str) -> None:
    """Gzipped JSON, the ``op_name``s once each."""
    names = sorted({op[6] for op in data["ops"]})
    index = {n: i for i, n in enumerate(names)}
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump({"op_names": names, "ops": [op[:6] + [index[op[6]]] for op in data["ops"]], "spans": data["spans"]}, f)


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt", encoding="utf-8") as f:
            stored = json.load(f)
        if "op_names" not in stored:  # trace.py's own raw lists: no op_name, spans as [name, start_s, dur_s]
            return {"ops": [op + [""] for op in stored["ops"]], "spans": [[n, t, t + d, {}] for n, t, d in stored["spans"]]}
        names = stored["op_names"]
        return {"ops": [op[:6] + [names[op[6]]] for op in stored["ops"]], "spans": stored["spans"]}
    data = load_xplane(path)
    keep = os.environ.get("CHIPBENCH_KEEP_RAW")
    if keep:
        save(data, keep + ".program.json.gz")
    return data


# ---------------------------------------------------------------------------
# what the readers use
# ---------------------------------------------------------------------------


def host_spans(path: str, prefix: str) -> list:
    """``[name, start_s, end_s, meta]`` of the host spans whose name starts with ``prefix``, by start."""
    return sorted((s for s in load(path)["spans"] if s[0].startswith(prefix)), key=lambda s: s[1])


def traced_window(path: str):
    """(start, end) of the benchmark's ``chipbench.traced`` span, or ``None``."""
    traced = host_spans(path, trace_module().TRACED_SPAN)
    if not traced:
        return None
    return min(s[1] for s in traced), max(s[2] for s in traced)


def innermost_scope(op_name: str) -> str:
    """The last part of ``op_name`` that is one of ``SCOPES``; autodiff writes
    a scope as ``jvp(layers)`` or ``transpose(jvp(layers))``."""
    for part in reversed(op_name.split("/")):
        words = re.findall(r"[A-Za-z_][\w.]*", part)
        if words and words[-1] in SCOPES:
            return words[-1]
    return NO_SCOPE


def scope_seconds(path: str, program: str):
    """``{scope: [self_s, rematted_self_s]}`` over the device operations of the
    programs named ``program*`` that touch the traced span, the same ones
    ``trace.py:reduce`` counts in ``op_s``; ``None`` where none carries a scope."""
    window = traced_window(path)
    if window is None:
        return None
    t0, t1 = window
    out = {}
    for _, prog, start, dur, _, self_s, op_name in load(path)["ops"]:
        if not prog.startswith(program) or start >= t1 or start + dur <= t0:
            continue
        row = out.setdefault(innermost_scope(op_name), [0.0, 0.0])
        row[0] += self_s
        if REMATTED in op_name:
            row[1] += self_s
    return out if set(out) - {NO_SCOPE} else None


def idle_intervals(path: str) -> list:
    """The gaps between device operations inside the traced span (one device)."""
    trace = trace_module()
    window = traced_window(path)
    ops = load(path)["ops"]
    if window is None or not ops:
        return []
    t0, t1 = window
    device = min(op[4] for op in ops)
    busy = trace.clip_intervals(trace.merge_intervals([(op[2], op[2] + op[3]) for op in ops if op[4] == device]), t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]


def idle_under(path: str, span_names) -> float | None:
    """Seconds of device idle inside the traced span that lie under the host
    spans of these names.  Plain intersection: inside a ``wait`` span the idle
    is what passes before the program's first operation starts and after its
    last one ends.  ``None`` where the trace holds no such span, or no
    device plane (a CPU run: its operations run on host threads)."""
    trace = trace_module()
    names = set(span_names)
    data = load(path)
    covered = trace.merge_intervals([(s[1], s[2]) for s in data["spans"] if s[0] in names])
    if not covered or not data["ops"]:
        return None
    return sum(trace.intervals_total(trace.clip_intervals(covered, g0, g1)) for g0, g1 in idle_intervals(path))


def ticks_in_window(path: str) -> int:
    """``serving.tick`` spans that start inside the traced span."""
    window = traced_window(path)
    if window is None:
        return 0
    return sum(1 for s in host_spans(path, TICK_SPAN) if s[0] == TICK_SPAN and window[0] <= s[1] < window[1])


def idle_ms_a_tick(run: dict, phases) -> float | None:
    """For the two idle readers: ``idle_under`` the tick's ``phases``, in ms a tick."""
    path = run["traced"].get("raw_path")
    if not path:
        return None
    ticks = ticks_in_window(path)
    idle = idle_under(path, [f"{TICK_SPAN}.{p}" for p in phases])
    if not ticks or idle is None:
        return None
    return 1e3 * idle / ticks


def scope_share(run: dict, programs, wanted) -> float | None:
    """For the three scope readers: percent of the traced span's ``busy_s``
    that is self time of ``programs``' operations for which ``wanted(scope,
    row)`` gives seconds (``row`` = [self_s, rematted_self_s])."""
    path = run["traced"].get("raw_path")
    busy = (run["traced"].get("trace") or {}).get("busy_s")
    if not path or not busy:
        return None
    total, found = 0.0, False
    for program in programs:
        by_scope = scope_seconds(path, program)
        if by_scope is not None:
            found = True
            total += sum(wanted(scope, row) for scope, row in by_scope.items())
    return 100.0 * total / busy if found else None


# ---------------------------------------------------------------------------
# a look by hand
# ---------------------------------------------------------------------------


def table(path: str) -> None:
    window = traced_window(path)
    if window is None:
        print("no chipbench.traced span")
        return
    data = load(path)
    gaps = idle_intervals(path)
    idle = sum(g1 - g0 for g0, g1 in gaps)
    print(f"traced span {window[1] - window[0]:.6f} s, device idle {idle:.6f} s, {ticks_in_window(path)} ticks")
    for program in sorted({op[1] for op in data["ops"]}):
        by_scope = scope_seconds(path, program)
        if by_scope is None:
            continue
        total = sum(row[0] for row in by_scope.values())
        print(f"PROGRAM {program}: {total:.6f} s of self time")
        for scope, (s, rematted) in sorted(by_scope.items(), key=lambda kv: -kv[1][0]):
            print(f"  {s:10.6f} s {100 * s / total:6.2f}%  rematted {rematted:10.6f} s  {scope}")
    names = sorted({s[0] for s in data["spans"]})
    print("IDLE under host spans (a span's children lie under it too)")
    for name in names:
        under = idle_under(path, [name])
        print(f"  {under:10.6f} s {100 * under / idle if idle else 0:6.2f}%  x{sum(1 for s in data['spans'] if s[0] == name):<5d} {name}")


if __name__ == "__main__":
    target = sys.argv[1]
    table(trace_module().find_xplane(target) if os.path.isdir(target) else target)
