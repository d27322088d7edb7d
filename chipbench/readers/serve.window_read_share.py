"""Rows the sliding layers attended over, as a share of the rows they would
have attended over had every layer read the whole context: the engine's
``window_rows_read`` over its ``context_rows``, both summed over the decode
dispatches, decoding lanes and sliding layers of the traced span.  100 while
every context lies inside its window; what the two kinds of table spare the
sliding layers past it.  Nothing to read where the program has no such
counters (no window leaves)."""


def read(run):
    counters = run["traced"].get("counters") or {}
    context = counters.get("context_rows")
    if not context or "window_rows_read" not in counters:
        return None
    return 100.0 * counters["window_rows_read"] / context
