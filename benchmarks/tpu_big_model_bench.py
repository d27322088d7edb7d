"""Big-model inference at the scale the subsystem exists for (VERDICT r3
item 3): >= 6B params on one TPU chip.

Two rungs, matching the reference's ``benchmarks/big_model_inference`` frame
(GPT-J-6B resident fp16 = 0.05 s/token; OPT-30B cpu-offload fp16 = 2.37
s/token on a Titan RTX):

1. ``resident-6.7b`` — llama2-7b geometry (d4096/f11008/L32 MHA, 6.74B
   params, 13.5 GB bf16) fully HBM-resident; the whole decode loop is one
   compiled lax.scan.  This is the row to put against GPT-J-6B's 0.05 s/token.
2. ``streamed-8.5b`` — L40 (8.36B params, 16.7 GB bf16): does NOT fit the
   15.75 GB chip.  Layer params live in host RAM; the decode loop streams
   them through two device buffers with the next layer's H2D in flight while
   the current layer computes (double-buffered prefetch).  Reports s/token
   and the fraction of H2D time hidden by compute.

Prints one JSON line per rung.  Run:  python benchmarks/tpu_big_model_bench.py
[--rung resident|streamed|both]
"""

from __future__ import annotations

import argparse
import json
import time

import _bootstrap  # noqa: F401  (repo path + platform-env handling)

import numpy as np


def _sync(x):
    """Wait for EVERY leaf — syncing only the first would stop the clock
    while the big weight matrices are still in flight."""
    import jax

    return jax.block_until_ready(x)


def resident_rung(prompt_len: int = 128, new_tokens: int = 32, batch: int = 1, tiny: bool = False):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import llama

    geom = (
        dict(hidden_size=256, intermediate_size=512, num_layers=4,
             num_heads=4, num_kv_heads=4, vocab_size=512)
        if tiny
        else dict(hidden_size=4096, intermediate_size=11008, num_layers=32,
                  num_heads=32, num_kv_heads=32, vocab_size=32000)  # llama2-7b MHA
    )
    cfg = llama.LlamaConfig(
        max_seq_len=prompt_len + new_tokens,
        param_dtype=jnp.bfloat16,
        **geom,
    )
    t0 = time.perf_counter()
    # Jit the whole init: eagerly, every leaf materializes an fp32
    # truncated-normal (the embedding alone is two 524 MB temps) before the
    # bf16 cast — at 13.5 GB of final params that transient overflows the
    # ~15.3 GB chip.  Under jit XLA fuses rng->scale->cast per leaf and
    # writes bf16 directly.
    params = jax.jit(lambda k: llama.init_params(cfg, k))(jax.random.key(0))
    _sync(params)
    load_s = time.perf_counter() - t0

    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, prompt_len))
    ids = np.asarray(ids, np.int32)

    # Warm up (compile prefill + decode scan), then measure.
    out = llama.generate(params, ids, cfg, max_new_tokens=new_tokens)
    _sync(out)
    t0 = time.perf_counter()
    out = llama.generate(params, ids, cfg, max_new_tokens=new_tokens)
    _sync(out)
    dt = time.perf_counter() - t0
    return {
        "metric": "big_model_inference_tpu",
        "round": 5,
        "rung": "resident-6.7b",
        "params": cfg.num_params(),
        "dtype": "bf16",
        "batch": batch,
        "load_s": round(load_s, 2),
        "s_per_token": round(dt / new_tokens, 4),
        "s_per_token_per_seq": round(dt / new_tokens / batch, 4),
        "reference_frame": "GPT-J-6B resident fp16: 0.05 s/token (Titan RTX)",
    }


def int8_resident_rung(prompt_len: int = 128, new_tokens: int = 32, batch: int = 1,
                       tiny: bool = False, layers: int = 40, real_weights: bool = False):
    """>HBM-in-bf16 model resident in int8: the L40 8.36B geometry (16.7 GB
    bf16, does NOT fit the ~15.3 GB chip) quantized blockwise to ~8.9 GB and
    decoded with per-layer dequant fused into the scan body
    (``llama.quantize_weights``).  This is the single-chip TPU answer to the
    reference's cpu/disk-offload tiers (OPT-30B 2.37 s/token) when the
    host link cannot stream fast enough.

    ``real_weights`` (fits-in-HBM geometries only) initializes real bf16
    params and quantizes on device; otherwise codes are synthesized directly
    at full scale (values don't affect throughput)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import llama
    from accelerate_tpu.utils.quantization import QuantizedArray

    geom = (
        dict(hidden_size=256, intermediate_size=512, num_layers=4,
             num_heads=4, num_kv_heads=4, vocab_size=512)
        if tiny
        else dict(hidden_size=4096, intermediate_size=11008, num_layers=layers,
                  num_heads=32, num_kv_heads=32, vocab_size=32000)
    )
    cfg = llama.LlamaConfig(
        max_seq_len=prompt_len + new_tokens, param_dtype=jnp.bfloat16, **geom
    )
    block = 64

    t0 = time.perf_counter()
    if real_weights:
        params = jax.jit(
            lambda k: llama.quantize_weights(llama.init_params(cfg, k), block)
        )(jax.random.key(0))
    else:
        shapes = llama._param_shapes(cfg)

        @jax.jit
        def synth():
            out = {
                "embed": jnp.zeros(shapes["embed"], jnp.bfloat16),
                "final_norm": jnp.ones(shapes["final_norm"], jnp.bfloat16),
                "layers": {},
            }
            if "lm_head" in shapes:
                out["lm_head"] = jnp.zeros(shapes["lm_head"], jnp.bfloat16)
            for k, shp in shapes["layers"].items():
                L, rest = shp[0], shp[1:]
                if len(rest) < 2:
                    out["layers"][k] = jnp.ones(shp, jnp.bfloat16)
                    continue
                n = int(np.prod(rest))
                nblk = (n + block - 1) // block
                out["layers"][k] = QuantizedArray(
                    jnp.zeros((L, nblk, block), jnp.int8),
                    jnp.ones((L, nblk), jnp.float32),
                    tuple(rest), "int8", block, jnp.bfloat16,
                )
            return out

        params = synth()
    _sync(params)
    load_s = time.perf_counter() - t0

    stored = sum(
        np.prod(l.shape) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params)
    )

    ids = np.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, prompt_len)),
        np.int32,
    )
    out = llama.generate(params, ids, cfg, max_new_tokens=new_tokens)
    _sync(out)
    t0 = time.perf_counter()
    out = llama.generate(params, ids, cfg, max_new_tokens=new_tokens)
    _sync(out)
    dt = time.perf_counter() - t0
    return {
        "metric": "big_model_inference_tpu",
        "round": 5,
        "rung": f"int8-resident-{cfg.num_params() / 1e9:.1f}b",
        "params": cfg.num_params(),
        "dtype": "int8-weights (bf16 embed/head/norms)",
        "stored_gb": round(stored / 2**30, 2),
        "bf16_equiv_gb": round(cfg.num_params() * 2 / 2**30, 2),
        "batch": batch,
        "load_s": round(load_s, 2),
        "s_per_token": round(dt / new_tokens, 4),
        "s_per_token_per_seq": round(dt / new_tokens / batch, 4),
        "synthetic_weights": not real_weights,
        "reference_frame": "OPT-30B cpu-offload fp16: 2.37 s/token (Titan RTX)",
    }


def streamed_rung(new_tokens: int = 8, batch: int = 8, max_len: int = 64, tiny: bool = False):
    """8.36B params streamed from host RAM through double device buffers."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from accelerate_tpu.models import llama

    geom = (
        dict(hidden_size=256, intermediate_size=512, num_layers=6,
             num_heads=4, num_kv_heads=4, vocab_size=512)
        if tiny
        else dict(hidden_size=4096, intermediate_size=11008, num_layers=40,
                  num_heads=32, num_kv_heads=32, vocab_size=32000)
    )
    cfg = llama.LlamaConfig(max_seq_len=max_len, param_dtype=jnp.bfloat16, **geom)
    L, d, f, hd = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
    K = cfg.num_kv_heads
    n_params = cfg.num_params()
    if not tiny:
        assert n_params * 2 > 15.75e9, "streamed rung must NOT fit HBM"

    # Host-resident per-layer params.  Values are irrelevant to throughput;
    # zeros avoid NaN propagation and calloc makes 16 GB instant.
    bf16 = ml_dtypes.bfloat16

    def host_layer():
        return {
            "wq": np.zeros((d, cfg.num_heads * hd), bf16),
            "wk": np.zeros((d, K * hd), bf16),
            "wv": np.zeros((d, K * hd), bf16),
            "wo": np.zeros((cfg.num_heads * hd, d), bf16),
            "w_gate": np.zeros((d, f), bf16),
            "w_up": np.zeros((d, f), bf16),
            "w_down": np.zeros((f, d), bf16),
            "ln_attn": np.ones((d,), bf16),
            "ln_mlp": np.ones((d,), bf16),
        }

    t0 = time.perf_counter()
    host_layers = [host_layer() for _ in range(L)]
    embed = jax.device_put(np.zeros((cfg.vocab_size, d), bf16))
    final_norm = jax.device_put(np.ones((d,), bf16))
    lm_head = jax.device_put(np.zeros((cfg.vocab_size, d), bf16))
    caches = [
        {
            "k": jax.device_put(jnp.zeros((batch, max_len, K, hd), jnp.bfloat16)),
            "v": jax.device_put(jnp.zeros((batch, max_len, K, hd), jnp.bfloat16)),
        }
        for _ in range(L)
    ]
    load_s = time.perf_counter() - t0

    @jax.jit
    def embed_step(table, ids):
        return table[ids].astype(jnp.bfloat16)

    import functools

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def layer_step(lp, x, ck, cv, index, positions):
        y, ck, cv = llama._attention_block_cached(x, lp, cfg, ck, cv, index, positions)
        h = llama._rms_norm(y, lp["ln_mlp"], cfg.rms_eps)
        gate = jax.nn.silu(llama._mm(h, lp["w_gate"], cfg))
        up = llama._mm(h, lp["w_up"], cfg)
        return y + llama._mm(gate * up, lp["w_down"], cfg), ck, cv

    @jax.jit
    def head_step(x, norm_scale, head_w):
        h = llama._rms_norm(x, norm_scale, cfg.rms_eps)
        return jnp.argmax((h @ head_w.T.astype(jnp.bfloat16)).astype(jnp.float32), -1)

    def one_token(ids, index):
        """One decode step: stream every layer, next layer's H2D in flight
        while the current layer computes."""
        positions = jnp.broadcast_to(
            jnp.asarray(index + np.arange(ids.shape[1])), ids.shape
        )
        x = embed_step(embed, jnp.asarray(ids))
        pending = jax.device_put(host_layers[0])  # async: transfer in flight
        for i in range(L):
            current = pending
            if i + 1 < L:
                pending = jax.device_put(host_layers[i + 1])  # prefetch next
            ck, cv = caches[i]["k"], caches[i]["v"]
            x, caches[i]["k"], caches[i]["v"] = layer_step(
                current, x, ck, cv, index, positions
            )
        return head_step(x, final_norm, lm_head)

    idx = 0
    ids = np.zeros((batch, 1), np.int32)
    nxt = one_token(ids, idx)  # warm-up/compile
    _sync(nxt)
    idx += 1

    t0 = time.perf_counter()
    for _ in range(new_tokens):
        # head_step returns [B, 1] already — keep the ids rank fixed or every
        # jitted fn would recompile per token inside the timed region.
        nxt = one_token(np.asarray(nxt).reshape(batch, 1).astype(np.int32), idx)
        idx += 1
    _sync(nxt)
    dt = (time.perf_counter() - t0) / new_tokens

    # Decomposition for the overlap fraction: transfers alone, compute alone.
    t0 = time.perf_counter()
    for i in range(L):
        _sync(jax.device_put(host_layers[i]))
    t_transfer = time.perf_counter() - t0
    resident = jax.device_put(host_layers[0])
    positions = jnp.zeros((batch, 1), jnp.int32) + idx
    ck = jax.device_put(jnp.zeros((batch, max_len, K, hd), jnp.bfloat16))
    cv = jax.device_put(jnp.zeros((batch, max_len, K, hd), jnp.bfloat16))
    x = embed_step(embed, jnp.asarray(ids))
    x, ck, cv = layer_step(resident, x, ck, cv, idx, positions)  # compile
    _sync(x)
    t0 = time.perf_counter()
    for _ in range(L):
        x, ck, cv = layer_step(resident, x, ck, cv, idx, positions)
    _sync(x)
    t_compute = time.perf_counter() - t0
    hidden = max(0.0, t_transfer + t_compute - dt)
    overlap = hidden / t_transfer if t_transfer > 0 else 0.0

    return {
        "metric": "big_model_inference_tpu",
        "round": 5,
        "rung": "streamed-8.5b",
        "params": n_params,
        "dtype": "bf16",
        "batch": batch,
        "load_s": round(load_s, 2),
        "s_per_token": round(dt, 3),
        "s_per_token_per_seq": round(dt / batch, 3),
        "h2d_alone_s": round(t_transfer, 3),
        "compute_alone_s": round(t_compute, 3),
        "h2d_hidden_fraction": round(min(overlap, 1.0), 3),
        "reference_frame": "OPT-30B cpu-offload fp16: 2.37 s/token (Titan RTX)",
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rung", choices=("resident", "streamed", "int8", "both", "all"),
                        default="both")
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--new", type=int, default=None)
    parser.add_argument("--layers", type=int, default=40,
                        help="int8 rung depth (40 = 8.36B, >HBM in bf16)")
    parser.add_argument("--real_weights", action="store_true",
                        help="int8 rung: init real bf16 weights on device and "
                             "quantize (must fit HBM in bf16)")
    parser.add_argument("--tiny", action="store_true",
                        help="CPU shakedown geometry (validates the code path only)")
    args = parser.parse_args()
    kw = {}
    if args.batch:
        kw["batch"] = args.batch
    if args.new:
        kw["new_tokens"] = args.new
    if args.rung in ("resident", "both", "all"):
        print(json.dumps(resident_rung(tiny=args.tiny, **kw)), flush=True)
    if args.rung in ("int8", "all"):
        print(json.dumps(int8_resident_rung(
            tiny=args.tiny, layers=args.layers, real_weights=args.real_weights, **kw
        )), flush=True)
    if args.rung in ("streamed", "both", "all"):
        print(json.dumps(streamed_rung(tiny=args.tiny, **kw)), flush=True)


if __name__ == "__main__":
    main()
