"""1 - (union of the device operations' intervals) / (traced window), from the trace."""


def read(run):
    trace = run["traced"].get("trace") or {}
    if not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
