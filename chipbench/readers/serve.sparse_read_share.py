"""K/V rows the queries attended over, as a share of the rows a full causal
mask would have admitted: the engine's ``sparse_rows_read`` (``min(p + 1,
topk)`` a query at position ``p``) over its ``context_rows`` (``p + 1``), both
summed over the dispatches, lanes and layers of the traced span.  100 while
every context lies within the top-k; what the indexer's selection spares past
it.  Both counters are counted from positions alone, so the share describes
the traffic (how far past the top-k the contexts lie) and is no witness that a
lane read only its selection: ``tests/test_tpu_compile.py``'s ``sparse_step``
case holds the program to that.  Nothing to read where the program has no
such counter (no indexer)."""


def read(run):
    counters = run["traced"].get("counters") or {}
    context = counters.get("context_rows")
    if not context or "sparse_rows_read" not in counters:
        return None
    return 100.0 * counters["sparse_rows_read"] / context
