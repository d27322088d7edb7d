"""New tokens emitted per lane-tick over the window, for a family generated by
diffusion over blocks: ``block_tokens_emitted`` over ``decode_slot_ticks`` (every
lane that denoised or committed in a dispatch).  One token a lane-tick is an
autoregressive decode; a block of ``B`` over ``T`` denoising passes and a commit
gives ``B / (T + 1)``, less the dropped tails.  Nothing to read where the program
has no such counter."""


def read(run):
    c = run["window"]["counters"]
    if "block_tokens_emitted" not in c or not c.get("decode_slot_ticks"):
        return None
    return c["block_tokens_emitted"] / c["decode_slot_ticks"]
