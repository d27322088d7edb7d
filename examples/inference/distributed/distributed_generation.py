"""Distributed batch generation (reference
``examples/inference/distributed/phi2.py`` pattern): shard a prompt list
across processes with ``split_between_processes``, generate on each slice
with the one-jit KV-cache decode loop, gather the results.

On a single host this degenerates to one slice; under a multi-host launch
(``accelerate-tpu launch --num_machines N ...``) each host generates its
share and ``gather_object`` reassembles the full list on every rank.
"""

import os

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax

from accelerate_tpu import PartialState
from accelerate_tpu.models import llama
from accelerate_tpu.utils import gather_object


def main():
    state = PartialState()
    cfg = llama.LlamaConfig.tiny(num_layers=2)
    params = llama.init_params(cfg, jax.random.key(0))

    # 8 synthetic "prompts" (token id arrays — a tokenizer would produce these).
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=8).tolist() for _ in range(8)]

    completions = []
    with state.split_between_processes(prompts) as my_prompts:
        if my_prompts:
            ids = np.asarray(my_prompts, np.int32)
            out = llama.generate(params, ids, cfg, max_new_tokens=8)
            completions = np.asarray(out).tolist()

    all_completions = gather_object(completions)
    state.print(f"{len(all_completions)} completions from {state.num_processes} process(es); "
                f"first: {all_completions[0]}")


if __name__ == "__main__":
    main()
