"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at the
published widths of Llama-3.2-1B with seeded random weights:

- kernels, on one device: the compiled flash kernel under a padding mask
  against the einsum path;
- trainer: ``Accelerator.prepare`` + ``make_train_step`` at s=2048, a fixed
  batch repeated (loss must fall), then one step on a right-padded batch;
- server: ``Accelerator.prepare_serving`` -> ``ServingEngine`` under a dozen
  interleaved requests, one of them checked against ``llama.generate``.

On several chips the same file shards the model with FSDP over all of them and
checks the spread.  It needs a TPU whose ``device_kind`` is in the peak table,
exits non-zero at once without one, and prints one JSON object as its last
line.  Wall time, compile seconds and cache hits are set-up facts, not
performance.  Everything runs in this one process.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import math
import sys
import time

SEED = 0
SEQ_LEN = 2048
TRAIN_STEPS = 8
# Reference top-2 logits closer than this are a bf16 rounding tie, not a
# disagreement: |logit| reaches ~4 under the seeded init, where bf16 resolves
# 2^-6 ~ 0.016, and a handful of such roundings stack up along the stack.
LOGIT_TIE = 0.05


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class Phase:
    """Prints one line of set-up facts for the enclosed phase."""

    def __init__(self, name: str, watcher):
        self.name, self.watcher, self.facts = name, watcher, {}

    def __enter__(self):
        w = self.watcher
        self._t0 = time.perf_counter()
        self._mark = (w.count, w.total_ms, w.cache_hits)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            w = self.watcher
            line = {
                "phase": self.name,
                "wall_s": round(time.perf_counter() - self._t0, 1),
                "compile_requests": w.count - self._mark[0],
                "compile_s": round((w.total_ms - self._mark[1]) / 1e3, 1),
                "persistent_cache_hits": w.cache_hits - self._mark[2],
                **self.facts,
            }
            print(json.dumps(line), flush=True)


def seeded_params(cfg, seed: int):
    import jax

    from accelerate_tpu.models import llama

    return jax.jit(llama.init_params, static_argnums=0)(cfg, jax.random.key(seed))


def check_spread(params, n: int) -> dict:
    """Every sharded parameter leaf has ``n`` shards on ``n`` distinct devices
    and no device holds more than ~1.2/n of the parameter bytes."""
    import jax

    per_device, total, sharded = {}, 0, 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        total += leaf.nbytes
        if any(axis is not None for axis in leaf.sharding.spec):
            sharded += 1
            devices = {s.device for s in leaf.addressable_shards}
            check(
                len(leaf.addressable_shards) == n and len(devices) == n,
                f"{jax.tree_util.keystr(path)} is sharded {leaf.sharding.spec} but "
                f"lives on {len(devices)} of {n} devices",
            )
        for s in leaf.addressable_shards:
            per_device[s.device] = per_device.get(s.device, 0) + s.data.nbytes
    worst = max(per_device.values()) / total
    check(sharded > 0, "no parameter leaf is sharded")
    check(len(per_device) == n, f"parameters touch {len(per_device)} of {n} devices")
    check(worst <= 1.2 / n, f"one device holds {worst:.3f} of the parameter bytes (> 1.2/{n})")
    return {"sharded_leaves": sharded, "worst_device_share": round(worst, 4)}


def trainer_phase(acc, cfg, phase, *, seq_len, steps, expect_mosaic) -> None:
    import numpy as np
    import optax

    import jax

    from accelerate_tpu import JaxModel
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.sharding import data_sharding
    from accelerate_tpu.telemetry.hlo_scan import scan_hlo

    n = jax.device_count()

    def apply_fn(params, input_ids, attention_mask=None):
        batch = {"input_ids": input_ids, "attention_mask": attention_mask}
        return {"loss": llama.loss_fn(params, batch, cfg)}

    model, opt = acc.prepare(
        JaxModel(apply_fn, seeded_params(cfg, SEED), partition_rules=llama.PARTITION_RULES),
        optax.adamw(3e-4),
    )
    if n > 1:
        phase.facts.update(check_spread(model.params, n))
    step = acc.make_train_step(model, opt)

    rng = np.random.default_rng(SEED)
    rows = n  # one sequence a chip: what one v5e holds at these widths
    tokens = rng.integers(0, cfg.vocab_size, (rows, seq_len), dtype=np.int32)
    sharding = data_sharding(acc.mesh)
    batch = {"input_ids": jax.device_put(tokens, sharding)}
    # A ragged right-padded tail: each row keeps a different share of s.
    mask = np.ones((rows, seq_len), np.int32)
    for r in range(rows):
        mask[r, seq_len - (seq_len // 8) * (1 + r % 3) :] = 0
    padded = dict(batch, attention_mask=jax.device_put(mask, sharding))

    def compiled_text(b) -> str:
        compiled = step.lower(b).compile()
        mem = compiled.memory_analysis()
        phase.facts.setdefault("argument_gib", round(mem.argument_size_in_bytes / 2**30, 2))
        phase.facts.setdefault("temp_gib", round(mem.temp_size_in_bytes / 2**30, 2))
        return compiled.as_text()

    for name, b in (("train", batch), ("padded", padded)):
        text = compiled_text(b)
        phase.facts[f"{name}_mosaic_calls"] = text.count("tpu_custom_call")
        if expect_mosaic:
            check(
                "tpu_custom_call" in text,
                f"attention_impl='auto' put no Mosaic kernel into the {name} step",
            )
        if n > 1:
            # Really partitioned, not n replicas of the whole job: sharded
            # parameters have to be gathered to be used.  The rest is printed,
            # not asserted — on a real 2x2 the TPU compiler turns the larger
            # all-gathers and the gradient reduce-scatters into rings of
            # collective-permutes over quarter-width shards, so no op named
            # reduce-scatter is left to find (PERF.md, PR 21).
            kinds = scan_hlo(text, acc.mesh).by_kind  # the repo's comms ledger
            ops = {k: [v["count"], f"{v['bytes'] / 2**20:.0f} MiB"] for k, v in kinds.items()}
            ops["collective-permute-start"] = text.count(" collective-permute-start(")
            phase.facts[f"{name}_collectives"] = ops
            check("all-gather" in kinds, f"the {name} step on {n} devices gathers no parameters: {ops}")

    losses = [float(step(batch))]  # warm-up: the dispatch path's own compile request
    warm = phase.watcher.count
    for _ in range(steps - 1):
        losses.append(float(step(batch)))
    check(phase.watcher.count == warm, "a train step compiled after the warm-up step")
    phase.facts["losses"] = [round(x, 4) for x in losses]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    check(
        abs(losses[0] - math.log(cfg.vocab_size)) < 0.5,
        f"first loss {losses[0]:.3f} is not within 0.5 of ln(V)={math.log(cfg.vocab_size):.3f}",
    )
    check(losses[-1] < losses[0], f"loss did not fall: {losses[0]:.3f} -> {losses[-1]:.3f}")

    padded_loss = float(step(padded))
    phase.facts["padded_loss"] = round(padded_loss, 4)
    check(math.isfinite(padded_loss), "non-finite loss on the right-padded batch")
    acc.free_memory(model, opt, step)


def server_phase(acc, cfg, phase, *, num_blocks, prompt_lens, new_tokens, shared_prefix) -> None:
    import numpy as np

    import jax

    from accelerate_tpu.models import llama

    n = jax.device_count()
    params = seeded_params(cfg, SEED + 1)
    engine = acc.prepare_serving(
        llama.apply_cached, llama.init_cache, params, cfg,
        num_blocks=num_blocks, block_size=16, max_slots=8,
    )
    if n > 1:
        for leaf in jax.tree_util.tree_leaves(engine.params):
            check(
                leaf.sharding.is_fully_replicated and len(leaf.sharding.device_set) == n,
                f"serving parameters are not replicated over the {n}-device mesh",
            )

    rng = np.random.default_rng(SEED + 1)
    prefix = rng.integers(0, cfg.vocab_size, shared_prefix).tolist()
    prompts = [rng.integers(0, cfg.vocab_size, length).tolist() for length in prompt_lens]
    # First and last request share the prefix; by the last wave the first has
    # prefilled and registered its blocks, so the last one must hit the cache.
    prompts[0][:shared_prefix] = prefix
    prompts[-1][:shared_prefix] = prefix
    asked = [int(x) for x in rng.integers(new_tokens[0], new_tokens[1] + 1, len(prompts))]

    ids, ticks, wave = {}, 0, 3
    pending = list(range(len(prompts)))
    while pending or not engine.sched.idle():
        if pending and ticks % 4 == 0:
            for i in pending[:wave]:
                ids[engine.submit(prompts[i], asked[i])] = i
            pending = pending[wave:]
        engine.step()
        ticks += 1
        check(ticks < 5000, "the engine did not drain within 5000 ticks")
    done = {ids[c.id]: c for c in engine.pop_finished()}
    stats = engine.stats()
    phase.facts.update(
        ticks=ticks,
        prefill_dispatches=stats["prefill_dispatches"],
        decode_dispatches=stats["decode_dispatches"],
        prefix_hits=stats["prefix_hits"],
        decode_bucket_widths=stats["decode_bucket_widths"],
    )
    check(len(done) == len(prompts), f"{len(done)} of {len(prompts)} requests completed")
    for i, c in sorted(done.items()):
        check(c.status == "ok", f"request {i} ended {c.status!r}")
        check(
            c.new_tokens == asked[i] and len(c.tokens) == len(prompts[i]) + asked[i],
            f"request {i} produced {c.new_tokens} tokens, {asked[i]} asked",
        )
    check(stats["prefix_hits"] > 0, "two requests shared a prefix and none hit the cache")
    check(stats["blocks_used"] == 0, f"{stats['blocks_used']} blocks leaked at drain")
    check(stats["quarantined"] == 0 and stats["preempted"] == 0, f"unexpected {stats}")

    # The repo's own oracle: greedy tokens equal the offline loop's.  With
    # random weights the top logits sit close, so the two programs may round a
    # near-tie differently; at the first divergence the reference's own logits
    # must then call it a tie.
    i = min(range(len(prompts)), key=lambda j: len(prompts[j]))
    got = done[i].tokens
    generate = jax.jit(llama.generate, static_argnums=(2, 3))
    want = np.asarray(generate(params, np.asarray([prompts[i]], np.int32), cfg, asked[i]))[0].tolist()
    phase.facts["oracle_request_len"] = [len(prompts[i]), asked[i]]
    phase.facts["oracle_identical"] = got == want
    if got != want:
        at = next(k for k, (a, b) in enumerate(zip(got, want)) if a != b)
        apply = jax.jit(llama.apply, static_argnums=2)
        logits = np.asarray(apply(params, np.asarray([want[:at]], np.int32), cfg))[0, -1]
        verdict = judge_divergence(logits, offline=want[at], engine=got[at])
        phase.facts["oracle_diverged"] = dict(verdict, at_generated_token=at - len(prompts[i]))
        check(
            verdict["tie"],
            f"engine and llama.generate diverge at generated token {at - len(prompts[i])} "
            f"and the reference does not call it a tie: {verdict}",
        )


def judge_divergence(logits, *, offline: int, engine: int) -> dict:
    """Where two greedy programs part ways, is it a rounding tie?  Judged on the
    REFERENCE's next-token logits for the common prefix: the two tokens must be
    its top two, and closer than ``LOGIT_TIE``.  Both candidates are reported
    with their logits, so a gap of 0.0 reads as two values that rounded alike
    and not as a check that compared a token with itself."""
    import numpy as np

    top2 = [int(t) for t in np.argsort(-logits)[:2]]
    gap = float(abs(logits[offline] - logits[engine]))
    return {
        "offline": [int(offline), float(logits[offline])],
        "engine": [int(engine), float(logits[engine])],
        "reference_top2": top2,
        "logit_gap": round(gap, 4),
        "tie": offline != engine and {int(offline), int(engine)} == set(top2) and gap < LOGIT_TIE,
    }


def kernels_phase(cfg, phase, *, interpret) -> None:
    """The compiled flash kernel with a padding mask against the einsum path,
    at this model's head geometry; the compiled fused expert kernel
    (``ops/pallas_moe.py``) against the ``lax.ragged_dot`` path it replaces at a
    few rows an expert."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.pallas_attention import pallas_attention

    h, kh, hd, groups = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads // cfg.num_kv_heads
    keys = iter(jax.random.split(jax.random.key(SEED + 2), 16))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(cfg.dtype)

    def close(got, want, what, tol=2e-2):
        # Both sides round to bf16 (2^-8 relative); 2e-2 of the largest value
        # allows a few stacked roundings and no masking or layout mistake.
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.maximum(1.0, jnp.max(jnp.abs(want))))
        phase.facts[f"{what}_rel_err"] = round(err, 5)
        check(err < tol, f"{what}: max |kernel - reference| / scale = {err:.4f} (tolerance {tol})")

    # Flash forward + gradients under a ragged key-validity mask.
    b, s = 2, 1024
    q, k, v = normal((b, s, h, hd)), normal((b, s, kh, hd)), normal((b, s, kh, hd))
    valid = jnp.arange(s)[None, :] < jnp.asarray([[s - 200], [s - 456]])
    mask = jnp.tril(jnp.ones((s, s), bool))[None] & valid[:, None, :]

    def flash(q, k, v):
        return pallas_attention(q, k, v, block_size=512, interpret=interpret, kv_valid=valid)

    def einsum(q, k, v):
        return llama._attention(q, k, v, mask, groups)

    def scalar(f):  # padded QUERY rows carry no loss: weight them out
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * valid[:, :, None, None])

    close(jax.jit(flash)(q, k, v) * valid[:, :, None, None],
          jax.jit(einsum)(q, k, v) * valid[:, :, None, None], "flash_kv_valid_fwd")
    got = jax.jit(jax.grad(scalar(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(scalar(einsum), argnums=(0, 1, 2)))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        close(g, w, f"flash_kv_valid_d{name}")

    # The fused grouped SwiGLU over the middle layer of a three-layer expert stack: an idle expert, an expert with more
    # rows than a tile, one row alone; against the three ragged_dot it stands in for.
    from accelerate_tpu.ops import moe, pallas_moe

    experts, d, f = 8, cfg.hidden_size, 512
    sizes = jnp.asarray([3, 0, 37, 1, 0, 9, 5, 7], jnp.int32)
    rows = normal((int(sizes.sum()), d))
    stack = [normal(shape) * fan ** -0.5 for shape, fan in (((3 * experts, d, f), d), ((3 * experts, d, f), d), ((3 * experts, f, d), f))]
    first = jnp.int32(experts)
    fused = jax.jit(lambda *a: pallas_moe.grouped_swiglu(*a, interpret=interpret))(rows, *stack, sizes, first)
    close(fused, jax.jit(moe._ragged_swiglu)(rows, *stack, sizes, first), "moe_grouped_swiglu")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind!r}) — no chip, no result",
            file=sys.stderr,
        )
        return 2

    from accelerate_tpu import Accelerator, FullyShardedDataParallelPlugin
    from accelerate_tpu.models.llama import LlamaConfig
    from accelerate_tpu.pipeline.compile_cache import enable_compile_cache
    from accelerate_tpu.telemetry import CompileWatcher, peak_flops_per_chip

    peak_flops_per_chip(dev)  # a device_kind outside the peak table is an error
    import jax.numpy as jnp

    # Published widths, full depth: 7.4 GB of bf16 parameters and AdamW state
    # plus one s=2048 sequence of activations fit one 16 GB chip.
    cfg = LlamaConfig.llama3_2_1b(
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat_policy="dots", attention_impl="auto"
    )
    print(f"model: Llama-3.2-1B widths, {cfg.num_layers} of 16 layers, "
          f"{cfg.num_params() / 1e9:.3f} B parameters", flush=True)
    watcher = CompileWatcher()
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    # Kernel against reference on ONE device, so before the Accelerator puts a
    # several-device mesh in context: a bare Mosaic call under one is refused
    # ("cannot be automatically partitioned" — the system's own path wraps the
    # kernel in shard_map, which the trainer phase exercises).
    with Phase("kernels", watcher) as phase:
        kernels_phase(cfg, phase, interpret=False)
    acc = Accelerator(
        mixed_precision="bf16",
        fsdp_plugin=FullyShardedDataParallelPlugin() if device["count"] > 1 else None,
    )
    print(f"mesh: {dict(acc.mesh.shape)}", flush=True)

    with Phase("trainer", watcher) as phase:
        trainer_phase(acc, cfg, phase, seq_len=SEQ_LEN, steps=TRAIN_STEPS, expect_mosaic=True)
    with Phase("server", watcher) as phase:
        server_phase(
            acc, cfg, phase,
            num_blocks=2048,  # 2048 blocks of 16 rows: 1.07 GB of bf16 K/V
            prompt_lens=[320, 64, 1024, 128, 512, 96, 768, 200, 640, 384, 900, 356],
            new_tokens=(32, 64),
            shared_prefix=256,
        )
    watcher.stop()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
