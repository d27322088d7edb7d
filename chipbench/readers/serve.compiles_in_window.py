"""Compile requests (telemetry.CompileWatcher) between the window's open and close."""


def read(run):
    return float(run["window"]["counters"]["compiles"])
