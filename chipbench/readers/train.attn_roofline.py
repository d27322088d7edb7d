"""Least time of the flash forward and backward at the cell's shapes over the
device time of the step's Mosaic custom calls in the trace.

Least time: the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, from
``families/<family>.py:flash_cost`` (recomputation not counted), times the
steps the traced span ran.  The train program's only custom calls are the flash
kernels; the trace reduction marks an operation whose HLO line says
``custom_call_target="tpu_custom_call"``, and the reader returns nothing when
the trace holds none.  The recomputed forward kernel's time counts (it is time
the step spends in attention kernels); its work does not.
"""

MOSAIC_MARK = " [tpu_custom_call]"  # chipbench/trace.py:short_name marks Mosaic kernels so


def read(run):
    trace = run["traced"].get("trace") or {}
    steps = (run["traced"].get("counters") or {}).get("steps", 0)
    kernel_s = sum(s for name, s in trace.get("op_s", {}).items() if name.endswith(MOSAIC_MARK))
    if not steps or kernel_s <= 0:
        return None
    t = run["traffic"]
    cost = run["family"].flash_cost(run["cfg"], t["batch"], t["seq_len"])
    least = max(cost["flops"] / run["peak_flops"], cost["bytes"] / run["peak_bytes"])
    return 100.0 * steps * least / kernel_s
