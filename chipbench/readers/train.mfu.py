"""Model FLOPs of the window's steps (forward + backward, matmuls and causal
attention, recomputation not counted) over window x chips x peak FLOP/s."""


def read(run):
    w, t = run["window"], run["traffic"]
    steps = w["counters"].get("steps", 0)
    if not steps:
        return None
    flops = steps * run["family"].train_flops_per_step(run["cfg"], t["batch"], t["seq_len"])
    return 100.0 * flops / (w["seconds"] * run["chips"] * run["peak_flops"])
