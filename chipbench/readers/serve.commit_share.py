"""The share of lane-ticks over the window that were commit passes (a finished
block's final tokens run once more to write its rows: they unmask nothing), for
a family generated by diffusion over blocks: ``commit_slot_ticks`` over
``decode_slot_ticks``.  Nothing to read where the program has no such counter."""


def read(run):
    c = run["window"]["counters"]
    if "commit_slot_ticks" not in c or not c.get("decode_slot_ticks"):
        return None
    return 100.0 * c["commit_slot_ticks"] / c["decode_slot_ticks"]
