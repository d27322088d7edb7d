"""The readings a cell's limits are set from, on the chip, many seeds in one process.

    chiprun -- python chipbench/tests/chip_readings.py --workload <name> \
        --seeds 101,102,... --control 3 --seconds 10

For each seed: the cell's own driver sets up, runs a short window at the cell's
load and is checked against the reference, exactly as a benchmark run does;
the first ``--control`` seeds also read the control (the reference computed in
fp8 in the program's place) and, for training, the half-batch fault.  One JSON
line a seed on standard output and in ``chiprun_out/readings.<workload>.jsonl``.
The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program", default="{}", help="JSON merged into the configuration's `program` group: "
                    'the program\'s own lower-precision path as the control, e.g. \'{"fp8": true}\'')
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    cell["config"]["program"] = dict(cell["config"].get("program", {}), **json.loads(args.program))
    run.find_device(cell["chips"], cell["peaks"])
    from accelerate_tpu.pipeline.compile_cache import enable_compile_cache
    from accelerate_tpu.telemetry import CompileWatcher

    enable_compile_cache()
    watcher = CompileWatcher()
    os.makedirs("chiprun_out", exist_ok=True)
    out_path = os.path.join("chiprun_out", f"readings.{args.workload}.jsonl")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        _, _, driver = run.build_driver(cell, seed, watcher)
        driver.setup()
        t1 = time.perf_counter()
        driver.window(args.seconds, run.Probe(False, args.seconds, 0, ""))
        e2e = driver.end_to_end()
        driver.release()
        t2 = time.perf_counter()
        checked = driver.check(control=i < args.control)
        line = {
            "tag": args.tag, "seed": seed, "setup_s": t1 - t0, "check_s": time.perf_counter() - t2, "e2e": e2e["values"],
            "attempted": e2e["attempted"], "failed": e2e["failed"], "readings": checked["readings"],
            "control": checked.get("control"), "half_batch": checked.get("half_batch"), "detail": checked.get("detail"),
        }
        del driver, checked
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
