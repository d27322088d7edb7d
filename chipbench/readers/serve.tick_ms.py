"""Window over engine ticks in the window: the pace every token of every slot waits on."""


def read(run):
    w = run["window"]
    if not w["counters"].get("ticks"):
        return None
    return 1e3 * w["seconds"] / w["counters"]["ticks"]
