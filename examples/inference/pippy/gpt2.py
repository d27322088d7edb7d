"""Pipeline-parallel GPT-2 inference (reference
``examples/inference/pippy/gpt2.py``): the generic ``stage_fn`` path —
stack the block params into pp-sharded stages and scan each stage's layers
with causal masking inside the stage body."""

import os

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
import jax.numpy as jnp

from accelerate_tpu import AcceleratorState, ParallelismConfig
from accelerate_tpu.models import gpt2
from accelerate_tpu.parallel import pipeline as pl
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

    cfg = gpt2.GPT2Config.tiny(num_layers=4)
    params = shard_params(
        gpt2.init_params(cfg, jax.random.key(0)), state.mesh, gpt2.param_specs(cfg)
    )
    stage_layers = pl.stack_pipeline_stages(params["layers"], pp)

    def stage_fn(lp, h):
        mb, s, _ = h.shape
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (mb, s, s))

        def body(carry, one_layer):
            return gpt2._layer(carry, one_layer, c=cfg, mask=mask, act_spec=None)

        h, _ = jax.lax.scan(body, h, lp)
        return h

    @jax.jit
    def forward(input_ids):
        s = input_ids.shape[1]
        x = params["wte"].astype(cfg.dtype)[input_ids] + params["wpe"].astype(cfg.dtype)[:s][None]
        x = pl.pipeline_apply(stage_fn, stage_layers, x, num_micro_batches=2)
        x = gpt2._layer_norm(x, params["final_ln_scale"], params["final_ln_bias"], cfg.layer_norm_eps)
        return (x @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)

    ids = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32),
        data_sharding(state.mesh),
    )
    logits = forward(ids)
    # Parity check vs the dense forward.
    dense = gpt2.apply(params, ids, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(dense), atol=5e-2, rtol=1e-2)
    print(f"pipelined gpt2 forward over pp={pp}: logits {logits.shape} (matches dense)")


if __name__ == "__main__":
    main()
