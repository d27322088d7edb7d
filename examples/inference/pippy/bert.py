"""Pipeline-parallel BERT inference (reference
``examples/inference/pippy/bert.py``): generic ``stage_fn`` path with
bidirectional masking inside the stage body."""

import os

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
import jax.numpy as jnp

from accelerate_tpu import AcceleratorState, ParallelismConfig
from accelerate_tpu.models import bert
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

    cfg = bert.BertConfig.tiny(num_layers=4)
    params = shard_params(
        bert.init_params(cfg, jax.random.key(0)), state.mesh, bert.param_specs(cfg)
    )
    stage_layers = pl.stack_pipeline_stages(params["layers"], pp)

    def stage_fn(lp, h):
        mb, s, _ = h.shape
        mask = jnp.ones((mb, s, s), bool)

        def body(carry, one_layer):
            return bert._layer(carry, one_layer, c=cfg, mask=mask, act_spec=None)

        h, _ = jax.lax.scan(body, h, lp)
        return h

    @jax.jit
    def encode(input_ids):
        s = input_ids.shape[1]
        e = params["embeddings"]
        x = (
            e["word"].astype(cfg.dtype)[input_ids]
            + e["position"].astype(cfg.dtype)[:s][None]
            + e["token_type"].astype(cfg.dtype)[jnp.zeros_like(input_ids)]
        )
        x = bert._layer_norm(x, e["ln_scale"], e["ln_bias"], cfg.layer_norm_eps)
        x = pl.pipeline_apply(stage_fn, stage_layers, x, num_micro_batches=2)
        pooled = jnp.tanh(
            x[:, 0].astype(jnp.float32) @ params["pooler"]["w"].astype(jnp.float32)
            + params["pooler"]["b"]
        )
        return x, pooled

    ids = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32),
        data_sharding(state.mesh),
    )
    seq_out, pooled = encode(ids)
    dense_seq, dense_pooled = bert.apply(params, ids, cfg)
    np.testing.assert_allclose(np.asarray(pooled), np.asarray(dense_pooled), atol=5e-2, rtol=1e-2)
    print(f"pipelined bert encoder over pp={pp}: pooled {pooled.shape} (matches dense)")


if __name__ == "__main__":
    main()
