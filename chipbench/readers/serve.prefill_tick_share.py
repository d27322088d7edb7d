"""How often the one prefill turn a tick has is taken: prefill dispatches over ticks."""


def read(run):
    c = run["window"]["counters"]
    if not c.get("ticks"):
        return None
    return 100.0 * c["prefill_dispatches"] / c["ticks"]
