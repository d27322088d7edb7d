"""Forward FLOPs of every token the window processed (new tokens emitted in it,
and the prompt of every request whose first token fell in it), 2 x matmul
parameters a token plus causal attention over the rows each attended, over
window x chips x peak FLOP/s."""


def read(run):
    w = run["window"]
    tokens = w["counters"].get("tokens", 0)
    if not tokens:
        return None
    flops = run["family"].serve_flops(run["cfg"], tokens, w["counters"]["pairs"])
    return 100.0 * flops / (w["seconds"] * run["chips"] * run["peak_flops"])
