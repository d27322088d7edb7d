"""One run of one cell of BENCHMARK.json.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it finds the cell's files by the names in ``BENCHMARK.json`` (see
``chipbench/README.md``), fails at once without the chips the cell asks for,
lets the cell's driver set up and warm the program, measures one window, reads
the device's memory peak, frees the program, checks what the window produced
against the plain reference, and prints one JSON object as its last line.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()
SETUP_START = PROCESS_START  # main() moves it to the moment the chip is open

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")  # inside the checkout, git-ignored


EARLY_MARKS = {}  # set-up marks made before a driver exists (build_driver carries them on)


def early_mark(name: str) -> None:
    EARLY_MARKS[name] = round(time.perf_counter() - PROCESS_START, 3)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` by file name (names may hold dots); ``kind``
    "" is the benchmark's own directory."""
    path = os.path.join(HERE, kind, name + ".py")
    modname = "chipbench_" + "".join(c if c.isalnum() else "_" for c in f"{kind}_{name}")
    if modname in sys.modules:
        return sys.modules[modname]
    if not os.path.isfile(path):
        raise SystemExit(f"chipbench: no file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(workload: str, bench_path: str | None = None) -> dict:
    """Everything a cell is, found by the names in BENCHMARK.json.  Data files
    (configs, traffic, limits) lie beside the benchmark file that names them,
    under its first ``paths`` entry; tests bring a benchmark file of their own."""
    bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_path)
    root = os.path.dirname(os.path.abspath(bench_path))
    data = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in {bench_path} (have {sorted(cells)})")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    limits_path = os.path.join(data, "limits", workload + ".json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": cell["chips"],
        "run_seconds": bench["run_seconds"],
        "config": load_json(os.path.join(root, config_entry["file"])),
        "traffic": load_json(os.path.join(data, "traffic", cell["traffic"] + ".json")),
        "limits": load_json(limits_path)["limits"] if os.path.isfile(limits_path) else {},
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "peaks": load_json(os.path.join(HERE, "peaks.json")),
    }


def find_device(chips: int, peaks: dict) -> dict:
    """The chips the cell asks for, or no run: no fall-back to a CPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found platform {dev.platform!r} ({dev.device_kind!r}): no result")
    if dev.device_kind not in peaks:
        raise SystemExit(f"chipbench: device_kind {dev.device_kind!r} is not in chipbench/peaks.json: no result")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX found {len(devices)}: no result")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks))


class Probe:
    """The traced part of a window: the last ``trace_seconds`` of it.  The
    driver calls ``tick`` once a loop turn with the seconds since the window
    opened and a function that snapshots its counters."""

    def __init__(self, on: bool, seconds: float, trace_seconds: float, trace_dir: str):
        self.on, self.trace_dir = on, trace_dir
        self.start_at = max(0.0, seconds - trace_seconds)
        self.started = self.span = None
        self.counters0 = self.counters1 = None

    def tick(self, elapsed: float, snapshot) -> None:
        if self.on and self.started is None and elapsed >= self.start_at:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
            self.span = jax.profiler.TraceAnnotation(load_module("", "trace").TRACED_SPAN)
            self.span.__enter__()
            self.started = time.perf_counter()
            self.counters0 = snapshot()

    def close(self, snapshot) -> dict | None:
        """Called by the driver when its window has closed (device drained)."""
        if self.started is None:
            return None
        import jax

        self.counters1 = snapshot()
        self.span.__exit__(None, None, None)
        seconds = time.perf_counter() - self.started
        jax.profiler.stop_trace()
        return {"seconds": seconds, "counters": delta(self.counters0, self.counters1)}


def delta(a: dict, b: dict) -> dict:
    """b - a for the numeric counters; the others as b has them."""
    out = {}
    for k, v in b.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool) and isinstance(a.get(k), (int, float)):
            out[k] = v - a[k]
        else:
            out[k] = v
    return out


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def judge(checks: dict) -> bool:
    """``correct``: every number compared lies at or under its limit."""
    return bool(checks) and all(c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())


def build_driver(cell: dict, seed: int, watcher):
    """The cell's driver module, its family module and a driver for one seed, not yet set up."""
    driver_mod = load_module("drivers", cell["traffic"]["driver"])
    family = load_module("families", cell["config"]["family"])
    marks = dict(EARLY_MARKS)  # where set-up's time goes: seconds from the process's start at which each phase was done, on the host
    ctx = {
        "cell": cell, "cfg": cell["config"], "traffic": cell["traffic"], "limits": cell["limits"],
        "family": family, "seed": seed, "watcher": watcher, "span": span, "delta": delta, "chips": cell["chips"],
        "marks": marks, "mark": lambda name: marks.__setitem__(name, round(time.perf_counter() - PROCESS_START, 3)),
    }
    return driver_mod, family, driver_mod.Driver(ctx)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, device: dict) -> dict:
    trace_mod = load_module("", "trace")

    from accelerate_tpu.pipeline.compile_cache import enable_compile_cache
    from accelerate_tpu.telemetry import CompileWatcher

    cache_dir = enable_compile_cache()
    watcher = CompileWatcher()
    driver_mod, family, driver = build_driver(cell, seed, watcher)
    peak = cell["peaks"][device["kind"]]
    driver.setup()
    setup_s = time.perf_counter() - SETUP_START
    probe = Probe(trace, seconds, float(cell["traffic"].get("trace_seconds", 5)), os.path.join(TRACE_DIR, cell["name"]))
    compiles0 = watcher.count
    window = driver.window(seconds, probe)  # {"seconds", "counters", "traced"}
    window["counters"]["compiles"] = watcher.count - compiles0
    memory_peak = memory_peak_bytes()
    e2e = driver.end_to_end()
    e2e["values"]["setup_s"] = setup_s
    driver.release()
    t_check = time.perf_counter()
    checked = driver.check()  # {"checks": {name: {value, limit}}, "readings": {...}}
    checks = checked["checks"]
    check_s = time.perf_counter() - t_check
    watcher.stop()

    out_device = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": judge(checks), "attempted": e2e["attempted"], "failed": e2e["failed"]}
    facts = {
        "seed": seed, "setup_s": setup_s, "window_s": window["seconds"], "check_s": check_s, "compile_cache": cache_dir,
        "chip_open_s": SETUP_START - PROCESS_START,
        "compile_requests": watcher.count, "compile_s": watcher.total_ms / 1e3,
        "compiles_in_window": window["counters"]["compiles"], "setup_marks": driver.ctx["marks"],
        "persistent_cache_hits": watcher.cache_hits, "readings": checked.get("readings", {}), **e2e.get("facts", {}),
    }
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e["values"][m["name"]], "unit": m["unit"]} for m in cell["end_to_end"]}
    else:
        traced = window.get("traced")
        reduced, raw_path = {}, None
        if traced is not None:
            raw_path = trace_mod.find_xplane(probe.trace_dir)
            raw = trace_mod.load_xplane(raw_path, driver_mod.SPANS)
            reduced = trace_mod.reduce(raw)
            keep = os.environ.get("CHIPBENCH_KEEP_RAW")
            if keep:  # for a look by hand and for the fixture: the plain lists, gzipped, and a dump
                trace_mod.save_raw(raw, keep)
                with open(keep + ".dump.txt", "w") as f, contextlib.redirect_stdout(f):
                    trace_mod.dump(raw_path)
        run = {
            "cfg": cell["config"], "traffic": cell["traffic"], "family": family, "chips": cell["chips"],
            "peak_flops": peak["flops_per_s"], "peak_bytes": peak["bytes_per_s"],
            "window": window, "traced": dict(traced or {}, trace=reduced, raw_path=raw_path),
        }
        metrics = {}
        for m in cell["per_layer"]:
            value = load_module("readers", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if reduced:
            out_device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
            facts["programs"] = {k: [reduced["executions"].get(k, 0), v] for k, v in reduced["program_s"].items()}
        shutil.rmtree(probe.trace_dir, ignore_errors=True)
    result["device"] = out_device
    result["facts"] = facts
    result["checks"] = checks  # last: each number compared beside its limit
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None else cell["run_seconds"]
    sys.path.insert(0, ROOT)
    if importlib.util.find_spec("accelerate_tpu") is None:
        raise SystemExit("chipbench: the program is not in this checkout (no accelerate_tpu): no result")
    import jax  # noqa: F401

    early_mark("jax_imported")
    device = find_device(cell["chips"], cell["peaks"])
    # Set-up's clock starts here, once the runtime has opened the chip: libtpu's own start-up (it maps 4 GiB of
    # host memory) took 7.3-10.9 s in a bare ``jax.devices()`` on one machine within minutes (PERF.md §2), more
    # than the 10% that setup_s may move, with nothing of the benchmark or the program in it.  All that either
    # of them does, the program's import first, lies after it and counts.
    early_mark("chip_open")
    global SETUP_START
    SETUP_START = time.perf_counter()
    import accelerate_tpu  # noqa: F401

    early_mark("program_imported")
    result = run_cell(cell, args.seed, seconds, bool(args.trace), device)
    for name, c in result["checks"].items():
        print(f"chipbench check: {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"chipbench: correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
