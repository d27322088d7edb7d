"""Least time of the traced span's decode dispatches over the device time of
the decode program's executions in the trace.

Least time of one dispatch: (weight bytes + K/V bytes of the blocks of the live
slots) / peak bytes/s: decode is bound by bytes.  Blocks come from the engine's
``decode_gather_bytes`` counter, bytes a row from ``families/<family>.py``.
Counts the work the algorithm needs, whatever implements it.
"""

PROGRAM = "jit_decode"


def read(run):
    traced = run["traced"]
    trace, c = traced.get("trace") or {}, traced.get("counters") or {}
    device_s = sum(s for name, s in trace.get("program_s", {}).items() if name.startswith(PROGRAM))
    if not c.get("decode_dispatches") or device_s <= 0:
        return None
    fam, cfg = run["family"], run["cfg"]
    kv = c["decode_gather_blocks"] * c["block_size"] * fam.kv_row_bytes(cfg)
    least = (c["decode_dispatches"] * fam.weight_bytes(cfg) + kv) / run["peak_bytes"]
    return 100.0 * least / device_s
