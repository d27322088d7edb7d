"""Pipeline-parallel T5 inference (reference
``examples/inference/pippy/t5.py``): pipeline the ENCODER stack over ``pp``
(the relative-position bias is shared across layers, so it closes over every
stage identically); the decoder runs dense against the pipelined encoder
output."""

import os

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
import jax.numpy as jnp

from accelerate_tpu import AcceleratorState, ParallelismConfig
from accelerate_tpu.models import t5
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

    cfg = t5.T5Config.tiny(num_layers=4)
    params = shard_params(
        t5.init_params(cfg, jax.random.key(0)), state.mesh, t5.param_specs(cfg)
    )
    stage_layers = pl.stack_pipeline_stages(params["encoder"], pp)

    s = 32

    @jax.jit
    def encode_pipelined(input_ids):
        enc_bias = t5._rel_bias(
            params["enc_rel_bias"].astype(jnp.float32), s, s, cfg, bidirectional=True
        )

        def stage_fn(lp, h):
            def body(carry, one_layer):
                return t5._enc_layer(carry, one_layer, c=cfg, bias=enc_bias, mask=None, act_spec=None)

            h, _ = jax.lax.scan(body, h, lp)
            return h

        x = params["shared_embed"].astype(cfg.dtype)[input_ids]
        x = pl.pipeline_apply(stage_fn, stage_layers, x, num_micro_batches=2)
        return t5._rms_norm(x, params["enc_final_ln"], cfg.rms_eps)

    ids = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (8, s)).astype(np.int32),
        data_sharding(state.mesh),
    )
    enc_out = encode_pipelined(ids)
    dense = t5.encode(params, ids, cfg)
    np.testing.assert_allclose(np.asarray(enc_out), np.asarray(dense), atol=5e-2, rtol=1e-2)
    print(f"pipelined t5 encoder over pp={pp}: {enc_out.shape} (matches dense)")


if __name__ == "__main__":
    main()
