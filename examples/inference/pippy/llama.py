"""Pipeline-parallel llama inference (reference
``examples/inference/pippy/llama.py``): split the decoder stack over the
``pp`` mesh axis and run one jit-compiled GPipe schedule."""

import os

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax

from accelerate_tpu import AcceleratorState, ParallelismConfig
from accelerate_tpu.inference import prepare_pippy
from accelerate_tpu.models import llama
from accelerate_tpu.parallel.sharding import data_sharding, shard_params


def main():
    n = jax.device_count()
    if n < 2:
        raise SystemExit(
            "This example needs >=2 devices for a pp axis. On one machine run it "
            "on the virtual CPU mesh:  JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 python " + __file__
        )
    pp = 4 if n % 4 == 0 else 2
    state = AcceleratorState(parallelism_config=ParallelismConfig(pp=pp, dp=n // pp))

    cfg = llama.LlamaConfig.tiny(num_layers=4)
    params = shard_params(
        llama.init_params(cfg, jax.random.key(0)), state.mesh, llama.param_specs(cfg)
    )
    forward = prepare_pippy(params, cfg)

    ids = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32),
        data_sharding(state.mesh),
    )
    logits = forward(ids)
    jax.block_until_ready(logits)
    print(f"pipelined llama forward over pp={pp}: logits {logits.shape}")


if __name__ == "__main__":
    main()
