"""Slots that emitted a token per decode dispatch, over the slots a dispatch has."""


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_dispatches"):
        return None
    return 100.0 * c["decode_slot_ticks"] / (c["decode_dispatches"] * c["max_slots"])
