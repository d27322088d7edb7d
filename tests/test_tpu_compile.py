"""Every Pallas entry point, and the paged model step, compiles for a TPU v5e —
checked without a chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu", ...)`` gives a
compile-only client: ``jit(...).lower(abstract args on its devices).compile()``
runs the real XLA:TPU and Mosaic compilers.  Nothing here *runs* a kernel
(``chip_smoke.py`` does, on the chip); this keeps "the kernels compile" true
between chip runs.  The cases compile in a child process, because a Mosaic
check failure aborts the process instead of raising — the flash kernel's
padded-batch variant did exactly that before its validity operand was laid
out ``[B, 1, S]``.  It is ONE child running the cases in order (libtpu holds a
process-wide lockfile even for the compile-only client, so children cannot
overlap, and twelve interpreter starts would cost a minute): it reports after
each case, so an abort is charged to the case that caused it and the cases
behind it read "not reached".
"""

import os
import subprocess
import sys

import pytest

TOPOLOGY = "v5e:2x2"

_CHILD = r"""
import sys, traceback
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu", topology_name=sys.argv[1])
except Exception as e:  # no compile-only TPU client in this install: the test skips
    print("NO_TOPOLOGY", type(e).__name__, str(e)[:300].replace("\n", " "), flush=True)
    sys.exit(0)
from accelerate_tpu.ops.pallas_attention import pallas_attention

sh = SingleDeviceSharding(topo.devices[0])
sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
loss = lambda f: (lambda *a: f(*a).astype(jnp.float32).sum())


def program(case, hd, b):
    s, h, kh = 2048, 8, 2
    q, kv = sds((b, s, h, hd)), sds((b, s, kh, hd))
    if case == "flash_fwd":
        return (lambda q, k, v: pallas_attention(q, k, v, block_size=512, interpret=False)), (q, kv, kv)
    if case == "flash_grad":
        f = loss(lambda q, k, v: pallas_attention(q, k, v, block_size=512, interpret=False))
        return jax.grad(f, argnums=(0, 1, 2)), (q, kv, kv)
    if case == "flash_kv_valid":
        f = loss(lambda q, k, v, m: pallas_attention(
            q, k, v, block_size=512, interpret=False, kv_valid=m))
        return jax.grad(f, argnums=(0, 1, 2)), (q, kv, kv, sds((b, s), jnp.bool_))
    if case.startswith("paged_step"):
        return paged_step(case, hd, b)
    if case.startswith("latent_step"):
        return latent_step(case.removesuffix("_fused"))
    if case == "moe_kernel":
        return moe_kernel(hd, b)
    if case == "paged_kernel":
        return paged_kernel(hd, b)
    raise ValueError(case)


MOE_STACKS = {128: (7, 2048, 768), 32: (12, 2048, 1792)}  # experts a layer -> (expert layers served, d, f): kanana and SDAR; LFM2
FUSED_KERNEL = "moe_grouped_swiglu"  # ops/pallas_moe.py's pallas_call(name=): the custom call's name and the last scope of its op_name


def moe_kernel(pairs, experts):
    # ops/pallas_moe.py's fused grouped SwiGLU alone, interpret=False, at a serving cell's widths over the merged stack of
    # all its expert layers: Mosaic accepts the whole-expert weight blocks under the kernel's own VMEM limit
    from accelerate_tpu.ops.moe import expert_row_tile
    from accelerate_tpu.ops.pallas_moe import grouped_swiglu
    layers, d, f = MOE_STACKS[experts]
    g, tm = layers * experts, expert_row_tile(pairs, experts, d, f, jnp.bfloat16)  # 16 rows a tile at a few rows an expert, 64 at many
    assert tm, "the rule does not take the kernel at this shape"
    args = (sds((pairs, d)), sds((g, d, f)), sds((g, d, f)), sds((g, f, d)), sds((experts,), jnp.int32), sds((), jnp.int32))
    return (lambda rows, wg, wu, wd, sizes, first: grouped_swiglu(rows, wg, wu, wd, sizes, first, tm=tm, interpret=False)), args


def check_moe_kernel(experts, compiled):
    # the stack is an operand of the custom call as it lies: no result of the program is as large as a matrix of the stack
    import re
    layers, d, f = MOE_STACKS[experts]
    text = compiled.as_text()
    if FUSED_KERNEL not in text:
        raise AssertionError("the executable does not hold the kernel by its name")
    sized = re.compile(r"= \w+\[%d,(%d,%d|%d,%d)\]" % (layers * experts, d, f, f, d))
    copies = [line.strip()[:160] for line in text.splitlines() if sized.search(line) and "} parameter(" not in line]
    if copies:
        raise AssertionError("the stack is copied for the kernel: " + " ;; ".join(copies[:3]))
    return f"temp_bytes={compiled.memory_analysis().temp_size_in_bytes}"


STEP_WIDTH = 64  # table width of every step: a context of 64 * 16 = 1024 rows a slot
STEP_LAYERS, STEP_BLOCKS, STEP_SLOTS = 4, 6144, 4  # 6144 and 4 * 6144 are sizes of nothing but the pool, and no leaf of it fits the chip's fast memory

# Bytes of temporaries of the same step at the parent of PR 27, where the pool was a scanned input of the layer
# loop (this file's child, run in that checkout): what the geometries that still slice their layer may cost
SCANNED_POOL_TEMP_BYTES = {
    "paged_step:64:8": 211_563_008, "paged_step:64:12": 467_623_936, "paged_step:128:3": 0, "paged_step:256:2": 101_179_392,
    "paged_step_int8:128:2": 0, "paged_step_int8:128:8": 4_710_400,
    # two groups in one forward (PR 31): the same two slices and two re-tilings a layer as one group has (counted below),
    # scheduled so that three slice-sized buffers are alive at once where the one-group step has two
    "paged_step_mixed:64:8": 303_724_032,
}


def paged_step(case, hd, kv_heads):
    # llama.apply_paged at a decode shape (one token a slot), a prefill
    # shape (one row of 32) or both as the two groups of a mixed dispatch, over
    # a pool of [4, 6144, 16, K, hd] a leaf, bf16 or int8 codes with bf16 scales
    from accelerate_tpu.models import llama
    from accelerate_tpu.models.generation import make_paged_pool

    c = llama.LlamaConfig(
        vocab_size=1024, hidden_size=2 * kv_heads * hd, intermediate_size=1024, num_layers=STEP_LAYERS,
        num_heads=2 * kv_heads, num_kv_heads=kv_heads, head_dim=hd, max_seq_len=4096, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, remat=False, kv_cache_quant=case == "paged_step_int8")
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: llama.init_params(c, jax.random.key(0))))
    pool = place(jax.eval_shape(lambda: make_paged_pool(llama.init_cache, c, STEP_BLOCKS, 16)))
    group = lambda rows, tokens: (sds((rows, tokens), jnp.int32), sds((rows, STEP_WIDTH), jnp.int32), sds((rows,), jnp.int32))
    groups = {"paged_step_prefill": (group(1, 32),), "paged_step_mixed": (group(STEP_SLOTS, 1), group(1, 32))}.get(case, (group(STEP_SLOTS, 1),))
    return (lambda p, pl, g: llama.apply_paged(p, g, c, pl)), (params, pool, groups)


LATENT_EXPERTS = (8, 256, 128)  # one layer's routed experts [E, d, f] in latent_step


def latent_step(case):
    # models/deepseek_v3.apply_paged and the engine's scatter of the new rows, at a decode shape (one token a slot)
    # or a prefill shape (one row of 32), over a latent pool at the published widths of the cache: ckv [4, 6144, 16, 512]
    # and kr [2, 6144, 16, 128] (two layers' rotated keys of 64 side by side); 1 dense + 3 expert layers
    from accelerate_tpu.models import deepseek_v3 as ds
    from accelerate_tpu.models.generation import make_paged_pool, scatter_token_rows

    c = ds.DeepseekV3Config(
        vocab_size=1024, hidden_size=LATENT_EXPERTS[1], intermediate_size=512, moe_intermediate_size=LATENT_EXPERTS[2],
        num_layers=STEP_LAYERS, first_k_dense_replace=1, num_heads=2, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=LATENT_EXPERTS[0], num_experts_per_tok=2, n_shared_experts=2,
        max_seq_len=4096, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False)
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: ds.init_params(c, jax.random.key(0))))
    pool = place(jax.eval_shape(lambda: make_paged_pool(ds.init_cache, c, STEP_BLOCKS, 16)))
    assert {k: v.shape for k, v in pool.items()} == {"ckv": (4, STEP_BLOCKS, 16, 512), "kr": (2, STEP_BLOCKS, 16, 128)}
    rows, tokens = (1, 32) if case == "latent_step_prefill" else (STEP_SLOTS, 1)

    def f(p, pl, i, t, s):
        (logits,), (new_rows,), counters = ds.apply_paged(p, ((i, t, s),), c, pl)
        return logits, counters, {n: scatter_token_rows(pl[n], r, t, s, tokens) for n, r in new_rows.items()}

    f.donate = (1,)  # the engine donates the pool: the scatter writes it where it lies
    return f, (params, pool, sds((rows, tokens), jnp.int32), sds((rows, STEP_WIDTH), jnp.int32), sds((rows,), jnp.int32))


def check_grouped_product(text, fused, where=""):
    # Which grouped product the program holds: the three Mosaic kernels XLA:TPU makes of lax.ragged_dot, or, with the rule
    # of ops/moe.py answering as on a TPU (the compile-only client's default backend is the CPU), the one fused kernel
    if fused and ("ragged-dot" in text or FUSED_KERNEL not in text):
        raise AssertionError(where + "the expert product is not the fused kernel alone")
    if not fused and ("ragged-dot" not in text or "tpu_custom_call" not in text or FUSED_KERNEL in text):
        raise AssertionError(where + "the expert product is not the grouped-matmul kernel")


def check_latent_step(compiled, fused=False):
    # The latent leaves are read where they lie: nothing but the scatter of the new rows has a result as large as a
    # leaf or a layer's slice of one.  The routed experts are read where the stack lies too: the grouped product is the
    # Mosaic kernel XLA:TPU makes of lax.ragged_dot, and no layer's experts are cut out of the stack for it.
    import re
    text = compiled.as_text()
    sizes = "|".join(str(x) for x in (4 * STEP_BLOCKS, 2 * STEP_BLOCKS, "4,%d" % STEP_BLOCKS, "2,%d" % STEP_BLOCKS, STEP_BLOCKS))
    sized = re.compile(r"= \w+\[(%s)," % sizes)
    moved = [line.strip()[:160] for line in text.splitlines()
             if sized.search(line) and not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)
             and "scatter(" not in line and "kv_pool.write/scatter" not in line]
    if moved:
        raise AssertionError("the step moves pool-sized arrays besides the scatter: " + " ;; ".join(moved[:4]))
    check_grouped_product(text, fused)
    cut = re.compile(r"= \w+\[%d,(%d,%d|%d,%d)\]" % (LATENT_EXPERTS[0], *LATENT_EXPERTS[1:], *LATENT_EXPERTS[:0:-1]))
    experts = [line.strip()[:160] for line in text.splitlines()
               if cut.search(line) and not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)]
    if experts:
        raise AssertionError("a layer's experts are cut out of the stack: " + " ;; ".join(experts[:3]))
    return f"temp_bytes={compiled.memory_analysis().temp_size_in_bytes}"


MIXED_CELLS = {  # the two serving cells of BENCHMARK.json: family, published widths at the depth served, the engine's geometry
    "mixed_step_chat": ("llama", dict(
        vocab_size=151936, hidden_size=2048, intermediate_size=11008, num_layers=36, num_heads=16, num_kv_heads=2, head_dim=128,
        max_seq_len=32768, tie_embeddings=True, attention_bias=True), 256),
    "mixed_step_agent": ("deepseek_v3", dict(
        vocab_size=128256, hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768, num_layers=8, first_k_dense_replace=1,
        num_heads=32, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=128,
        num_experts_per_tok=6, n_shared_experts=2, max_seq_len=32768), 128),
}
MIXED_BLOCKS, MIXED_SLOTS = 8192, 16  # the chunk is what the engine's rule gives the cell on a v5e (PR 37)


def counted_weight_bytes(params):
    # What XLA's cost analysis counts of the weights in ONE program: a scanned stack's body once (one layer of it), the
    # leaves a body holds whole (the routed experts) whole, and the head's matrix (the embedding where it is tied)
    size = lambda a: a.size * a.dtype.itemsize
    total = size(params["lm_head"] if "lm_head" in params else params["embed"])
    for stack in (v for v in params.values() if isinstance(v, dict)):
        for name, leaf in stack.items():
            held = "router" in stack and name in ("w_gate", "w_up", "w_down")
            total += size(leaf) if held else size(leaf) // leaf.shape[0]
    return total


def check_mixed_step(case, width):
    # serving/programs.py's decode_chunk at a cell's shapes against the two dispatches it replaces (decode, and the chunk
    # through the one-group forward with the old prefill head).  The forward runs what does not look at the cache once
    # over all its rows (16 and the chunk's): XLA's bytes accessed of the mixed program lie below the sum of the two by
    # the weights one program reads, to a tenth and three passes over the chunk's float32 logits (the mixed head computes
    # the head's matmul once over every row and hands each group its own logits, the chunk's for its finiteness flag and
    # its last row's argmax: by XLA's count 59 MB for every 32 rows of the chat cell's chunk beyond what the old prefill
    # head passed over; a trace of the cell shows no such time under `head`, 0.82 ms at 48 rows and at 80: PERF.md
    # section 6, PR 37).  And each group still reads every pool leaf where it lies: no result of the program is as large as a leaf
    # or a layer's slice of one but the scatters of the new rows.
    import importlib, re
    from accelerate_tpu.models.generation import make_paged_pool
    from accelerate_tpu.serving import ServingConfig, programs as P

    family_name, widths, max_blocks = MIXED_CELLS[case]
    family = importlib.import_module("accelerate_tpu.models." + family_name)
    config_cls = next(v for k, v in vars(family).items() if k.endswith("Config") and isinstance(v, type))
    c = config_cls(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False, **widths)
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: family.init_params(c, jax.random.key(0))))
    pool = place(jax.eval_shape(lambda: make_paged_pool(family.init_cache, c, MIXED_BLOCKS, 16)))
    from accelerate_tpu.serving import engine as E
    rows = E.resolve_prefill_chunk(None, device_kind="TPU v5 lite", max_slots=MIXED_SLOTS, window=1, block_size=16, routed_experts=E._routed_experts(c))
    if rows != {"mixed_step_chat": 64, "mixed_step_agent": 32}[case]:
        raise AssertionError(f"the rule gives {case} a chunk of {rows} rows")
    serving = ServingConfig(block_size=16, num_blocks=MIXED_BLOCKS, max_slots=MIXED_SLOTS, max_blocks_per_seq=max_blocks, prefill_chunk=rows)
    built = P.build_programs(family.apply_cached, c, list(pool), serving, 0)
    forward = P._paged_forward(family.apply_paged, c)

    def prefill(params, pool, table_row, start, chunk, n_real):
        tables, starts = table_row[None], start[None]
        (logits,), counters, (kv,) = forward(params, pool, ((chunk, tables, starts),))
        parts = [jnp.argmax(logits[0, n_real - 1], axis=-1), jnp.all(jnp.isfinite(logits))]
        return P._packed(parts, counters), P._write_rows(pool, kv, tables, starts, rows)

    i32 = lambda *shape: sds(shape, jnp.int32)
    lanes = (i32(MIXED_SLOTS, width), i32(MIXED_SLOTS), i32(MIXED_SLOTS, 1), i32(MIXED_SLOTS), i32(MIXED_SLOTS + 1), i32(MIXED_SLOTS))
    chunk = (i32(width), i32(), i32(1, rows), i32())
    compiled = {
        "decode": built.decode.lower(params, pool, *lanes).compile(),
        "prefill": jax.jit(prefill, donate_argnums=(1,)).lower(params, pool, *chunk).compile(),
        "mixed": built.decode_chunk.lower(params, pool, *lanes, *chunk).compile(),
    }
    read = {name: comp.cost_analysis()["bytes accessed"] for name, comp in compiled.items()}
    saved, weights = read["decode"] + read["prefill"] - read["mixed"], counted_weight_bytes(params)
    logits_passes = 3 * rows * c.vocab_size * 4
    if saved < 0.9 * weights - logits_passes:
        raise AssertionError(f"the mixed program reads {read['mixed']:.4g} B, {saved:.4g} under decode + prefill "
                             f"({read['decode']:.4g} + {read['prefill']:.4g}): the weights are {weights:.4g}")
    sizes = set()
    for leaf in pool.values():
        layers, blocks = leaf.shape[:2]
        sizes |= {str(layers * blocks), "%d,%d" % (layers, blocks), str(blocks)}
    sized = re.compile(r"= \w+\[(%s)," % "|".join(sorted(sizes)))
    moved = [line.strip()[:160] for line in compiled["mixed"].as_text().splitlines()
             if sized.search(line) and not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)
             and "scatter(" not in line and "kv_pool.write/scatter" not in line]
    if moved:
        raise AssertionError("the mixed program moves pool-sized arrays besides the scatters: " + " ;; ".join(moved[:4]))
    return "read_bytes=" + "/".join(f"{read[k]:.4g}" for k in ("decode", "prefill", "mixed")) + f" weights={weights:.4g}"


def check_state_step(width, fused=False):
    # serving/programs.py's decode and decode_chunk for models/lfm2_moe.py at the cut the benchmark serves (14 of the 24
    # published layers, published widths, the cell's geometry): K/V token rows for the 3 attention layers as rows of 512 =
    # 8 heads x 64 without a head axis, [3, 8192, 16, 512], beside the state by slot [11, 32, 2, 2048].  The rows of 512 are
    # read where they lie (the other geometry, [.., 8, 64], is paged_step:64:8 above: the block axis in the lanes, a layer's
    # slice cut out a dispatch): no result of either program is as large as a K/V leaf or a layer's slice of one but the
    # scatters of the new rows.  The state is written by slot, a leaf of 2.9 MB.  And the experts' grouped product reads
    # the stack where it lies, under the lax.cond that picks the layer's operator.
    import re
    from accelerate_tpu.models import lfm2_moe as lf
    from accelerate_tpu.models.generation import STATE, make_paged_pool
    from accelerate_tpu.serving import ServingConfig, programs as P

    blocks, slots, chunk_rows = 8192, 32, 32
    c = lf.Lfm2MoeConfig(num_layers=14, layer_types=lf.PUBLISHED_LAYER_TYPES[:14], dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False)
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: lf.init_params(c, jax.random.key(0))))
    pool = place(jax.eval_shape(lambda: make_paged_pool(lf.init_cache, c, blocks, 16, slots)))
    assert {k: v.shape for k, v in pool.items() if k != STATE} == {"k": (3, blocks, 16, 512), "v": (3, blocks, 16, 512)}
    assert pool[STATE]["conv"].shape == (11, slots, 2, 2048)
    serving = ServingConfig(block_size=16, num_blocks=blocks, max_slots=slots, max_blocks_per_seq=128, prefill_chunk=chunk_rows)
    built = P.build_programs(lf.apply_cached, c, ["k", "v"], serving, 0, stateful=True)
    i32 = lambda *shape: sds(shape, jnp.int32)
    lanes = (i32(slots, width), i32(slots), i32(slots, 1), i32(slots), i32(slots + 1), i32(slots))  # the feed and the lanes' sources among them
    chunk = (i32(width), i32(), i32(1, chunk_rows), i32())
    programs = {"decode": (built.decode, (*lanes, i32(slots))), "decode_chunk": (built.decode_chunk, (*lanes, *chunk, i32(slots), i32()))}
    sized = re.compile(r"= \w+\[(%d|3,%d|%d)," % (3 * blocks, blocks, blocks))
    cut = re.compile(r"= \w+\[32,(2048,1792|1792,2048)\]")
    temps = []
    for name, (program, args) in programs.items():
        compiled = program.lower(params, pool, *args).compile()
        text = compiled.as_text()
        lines = [line for line in text.splitlines() if not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)]
        moved = [line.strip()[:160] for line in lines if sized.search(line) and "scatter(" not in line and "kv_pool.write/scatter" not in line]
        if moved:
            raise AssertionError(f"{name} moves pool-sized arrays besides the scatter of the new rows: " + " ;; ".join(moved[:4]))
        experts = [line.strip()[:160] for line in lines if cut.search(line)]
        if experts:
            raise AssertionError(f"{name}: a layer's experts are cut out of the stack: " + " ;; ".join(experts[:3]))
        check_grouped_product(text, fused, name + ": ")
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    return "temp_bytes=" + "/".join(map(str, temps))


def check_window_step(width, fused=False, in_place=False):
    # serving/programs.py's decode and decode_chunk for models/afmoe.py at the cut the benchmark serves (trinity-large-preview:
    # one dense layer and one period s s s f at the published widths, 32 of 256 experts held, the cell's geometry): the full
    # layer's rows [1, 16384, 16, 8, 128] beside the four sliding layers' [4, 16 * 259 + 1, 16, 8, 128], a ring of 259 blocks a
    # sequence behind window tables min(width, 259) wide.  At K = 8 x hd = 128 a block is whole (8, 128) tiles
    # (generation._blocks_lie_row_by_row), so both kinds are read where they lie, under the lax.cond that picks the layer's
    # kind: no result of either program is as large as the window leaves or a layer's slice of them but the scatters of the
    # new rows, and what the programs hold besides their arguments stays under the two gathered contexts (the full layer's
    # 16 x width x 16 rows, K and V, the widest thing a dispatch makes) and a half again.  The experts' grouped product reads
    # the held experts' stack where it lies.
    import re
    from accelerate_tpu.models import afmoe as af
    from accelerate_tpu.models.generation import WINDOW, make_paged_pool, window_ring_blocks
    from accelerate_tpu.serving import ServingConfig, programs as P

    blocks, slots, chunk_rows = 16384, 16, 32
    ring = window_ring_blocks(4096, chunk_rows, 16)
    window_blocks = slots * ring + 1
    c = af.AfmoeConfig(
        vocab_size=25024, num_layers=5, layer_types=(af.SLIDING,) * 4 + (af.FULL,), num_dense_layers=1, experts_held=(0, 32),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False)
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: af.init_params(c, jax.random.key(0))))
    pool = place(jax.eval_shape(lambda: make_paged_pool(af.init_cache, c, blocks, 16, window_blocks=window_blocks)))
    assert ring == 259 and pool["k"].shape == (1, blocks, 16, 8, 128) and pool[WINDOW]["v"].shape == (4, window_blocks, 16, 8, 128)
    serving = ServingConfig(block_size=16, num_blocks=blocks, max_slots=slots, max_blocks_per_seq=1024, prefill_chunk=chunk_rows)
    built = P.build_programs(af.apply_cached, c, ["k", "v"], serving, 0, ring_blocks=ring)
    i32 = lambda *shape: sds(shape, jnp.int32)
    wide = built.window_width(width)
    assert wide == min(width, ring)
    lanes = (i32(slots, width), i32(slots), i32(slots, 1), i32(slots), i32(slots + 1), i32(slots))
    chunk = (i32(width), i32(), i32(1, chunk_rows), i32())
    programs = {"decode": (built.decode, (*lanes, i32(slots, wide))), "decode_chunk": (built.decode_chunk, (*lanes, *chunk, i32(slots, wide), i32(wide)))}
    sized = re.compile(r"= \w+\[(%d|4,%d|%d)," % (4 * window_blocks, window_blocks, window_blocks))
    cut = re.compile(r"= \w+\[32,3072,3072\]")
    context_bytes = 2 * slots * width * 16 * 8 * 128 * 2
    temps = []
    for name, (program, args) in programs.items():
        compiled = program.lower(params, pool, *args).compile()
        text = compiled.as_text()
        lines = [line for line in text.splitlines() if not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)]
        moved = [line.strip()[:160] for line in lines if sized.search(line) and "scatter(" not in line and "kv_pool.write/scatter" not in line]
        if moved:
            raise AssertionError(f"{name} moves arrays as large as the window leaves besides the scatter of the new rows: " + " ;; ".join(moved[:4]))
        experts = [line.strip()[:160] for line in lines if cut.search(line)]
        if experts:
            raise AssertionError(f"{name}: a layer's held experts are cut out of the stack: " + " ;; ".join(experts[:3]))
        if "kv_pool.window" not in text or "attn.window" not in text or "attn.gate" not in text:
            raise AssertionError(f"{name}: the sliding layers' scopes are not in the executable's op names")
        check_grouped_product(text, fused, name + ": ")
        temp = compiled.memory_analysis().temp_size_in_bytes
        if temp > 1.5 * context_bytes + 2**27:
            raise AssertionError(f"{name}: {temp} bytes of temporaries, the full layer's gathered context is {context_bytes}")
        check_lanes_in_place(text, temp, in_place, f"window_step:{width}:{name}")
        temps.append(temp)
    return "temp_bytes=" + "/".join(map(str, temps))


def check_sparse_step(width, fused=False):
    # serving/programs.py's decode and decode_chunk for models/keye_vl2.py at the cut the benchmark serves
    # (keye-vl-2.0-30b-a3b: 4 of 48 layers at the published widths, the cell's geometry): K/V [4, 32768, 16, 4, 128] and
    # the index keys of two layers a row, ki [2, 32768, 16, 128], both read where they lie.  The three scopes of the sparse
    # attention are in the executable; no result is as large as a leaf or a layer's slice of one but the scatters of the
    # new rows; and the decoding lanes read the rows their selection names, not their context: with the lanes alone
    # (decode) the program holds less than a quarter of the K/V context a dense read of its tables would gather.
    import re
    from accelerate_tpu.models import keye_vl2 as kv
    from accelerate_tpu.models.generation import make_paged_pool
    from accelerate_tpu.serving import ServingConfig, programs as P

    blocks, slots, chunk_rows = 32768, 16, 32
    c = kv.KeyeVl2Config(num_layers=4, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False)
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: kv.init_params(c, jax.random.key(0))))
    pool = place(jax.eval_shape(lambda: make_paged_pool(kv.init_cache, c, blocks, 16)))
    assert pool["k"].shape == (4, blocks, 16, 4, 128) and pool["ki"].shape == (2, blocks, 16, 128)
    serving = ServingConfig(block_size=16, num_blocks=blocks, max_slots=slots, max_blocks_per_seq=2048, prefill_chunk=chunk_rows)
    built = P.build_programs(kv.apply_cached, c, ["k", "v", "ki"], serving, 0)
    i32 = lambda *shape: sds(shape, jnp.int32)
    lanes = (i32(slots, width), i32(slots), i32(slots, 1), i32(slots), i32(slots + 1), i32(slots))
    chunk = (i32(width), i32(), i32(1, chunk_rows), i32())
    programs = {"decode": (built.decode, lanes), "decode_chunk": (built.decode_chunk, (*lanes, *chunk))}
    sized = re.compile(r"= \w+\[(%d|4,%d|2,%d|%d),16," % (4 * blocks, blocks, blocks, blocks))
    dense_context = 2 * slots * width * 16 * 4 * 128 * 2  # K and V of every row the lanes' tables name, one layer
    temps = []
    for name, (program, args) in programs.items():
        compiled = program.lower(params, pool, *args).compile()
        text = compiled.as_text()
        lines = [line for line in text.splitlines() if not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)]
        moved = [line.strip()[:160] for line in lines if sized.search(line) and "scatter(" not in line and "kv_pool.write/scatter" not in line]
        if moved:
            raise AssertionError(f"{name} moves arrays as large as a pool leaf besides the scatter of the new rows: " + " ;; ".join(moved[:4]))
        missing = [s for s in ("attn.index", "attn.select", "attn.sparse") if s not in text]
        if missing:
            raise AssertionError(f"{name}: the scopes {missing} are not in the executable's op names")
        check_grouped_product(text, fused, name + ": ")
        temp = compiled.memory_analysis().temp_size_in_bytes
        if name == "decode" and temp > dense_context // 4:
            raise AssertionError(f"decode: {temp} bytes of temporaries, a dense read of the lanes' K/V is {dense_context}")
        temps.append(temp)
    return "temp_bytes=" + "/".join(map(str, temps))


# Bytes of temporaries of serving/programs.py's two programs at the parent of PR 39, where every group gathered its
# context (this file's child, run in that checkout): what a program whose decoding lanes read the pool in place undercuts.
# The chat cell's held 354,816 / 548,352 B at widths 64 and 256 alike (XLA fuses each layer's gather into its consumers
# there), and hold it still: models/llama.py's lanes gather their context
CHAT_GATHERED_TEMP_BYTES = {"decode": 354_816, "decode_chunk": 548_352}
GATHERED_TEMP_BYTES = {
    "window_step:256:decode": 138_034_176, "window_step:256:decode_chunk": 142_011_904,
    "window_step:1024:decode": 576_196_096, "window_step:1024:decode_chunk": 581_979_136,  # the full layer's context
}
IN_PLACE_KERNEL = "paged_decode_attention"  # ops/pallas_paged_attention.py's pallas_call(name=)


def check_lanes_in_place(text, temp, in_place, key):
    # The decoding lanes read the pool through the kernel, one custom call a layer kind, the chunk's group gathered as
    # ever; what the program holds besides its arguments is less than the gathered program's (PR 39)
    if not in_place:
        if IN_PLACE_KERNEL in text:
            raise AssertionError(f"{key}: the kernel is in a program whose rule keeps the gathered path")
        return
    if IN_PLACE_KERNEL not in text:
        raise AssertionError(f"{key}: the executable does not hold the kernel by its name")
    if key in GATHERED_TEMP_BYTES and temp >= GATHERED_TEMP_BYTES[key]:
        raise AssertionError(f"{key}: {temp} bytes of temporaries, {GATHERED_TEMP_BYTES[key]} gathered")


def check_chat_step(width, in_place=False):
    # serving/programs.py's decode and decode_chunk at the chat cell's shapes (qwen2.5-3b through models/llama.py: 36
    # layers of K 2 x hd 128, [36, 8192, 16, 2, 128] a leaf, sixteen lanes, a chunk of 64), as a TPU builds them and as
    # the CPU does: the same gathered programs, no kernel (llama.apply_paged does not take it, PR 39); no result of either
    # program is as large as a pool leaf or a layer's slice of one but the scatters of the new rows
    import re
    from accelerate_tpu.models import llama
    from accelerate_tpu.models.generation import make_paged_pool
    from accelerate_tpu.serving import ServingConfig, programs as P

    _, widths, max_blocks = MIXED_CELLS["mixed_step_chat"]
    c = llama.LlamaConfig(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=False, **widths)
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda: llama.init_params(c, jax.random.key(0))))
    pool = place(jax.eval_shape(lambda: make_paged_pool(llama.init_cache, c, MIXED_BLOCKS, 16)))
    serving = ServingConfig(block_size=16, num_blocks=MIXED_BLOCKS, max_slots=MIXED_SLOTS, max_blocks_per_seq=max_blocks, prefill_chunk=64)
    built = P.build_programs(llama.apply_cached, c, list(pool), serving, 0)
    i32 = lambda *shape: sds(shape, jnp.int32)
    lanes = (i32(MIXED_SLOTS, width), i32(MIXED_SLOTS), i32(MIXED_SLOTS, 1), i32(MIXED_SLOTS), i32(MIXED_SLOTS + 1), i32(MIXED_SLOTS))
    chunk = (i32(width), i32(), i32(1, 64), i32())
    sized = re.compile(r"= \w+\[(%d|36,%d|%d)," % (36 * MIXED_BLOCKS, MIXED_BLOCKS, MIXED_BLOCKS))
    temps = []
    for name, program, args in (("decode", built.decode, lanes), ("decode_chunk", built.decode_chunk, (*lanes, *chunk))):
        compiled = program.lower(params, pool, *args).compile()
        text = compiled.as_text()
        lines = [line for line in text.splitlines() if not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)]
        moved = [line.strip()[:160] for line in lines if sized.search(line) and "scatter(" not in line and "kv_pool.write/scatter" not in line]
        if moved:
            raise AssertionError(f"{name} moves pool-sized arrays besides the scatter of the new rows: " + " ;; ".join(moved[:4]))
        temp = compiled.memory_analysis().temp_size_in_bytes
        # as a TPU builds them the chat cell's programs gather, as the parent's
        check_lanes_in_place(text, temp, False, f"chat_step:{width}:{name}")
        if in_place and temp != CHAT_GATHERED_TEMP_BYTES[name]:
            raise AssertionError(f"{name}: {temp} bytes of temporaries, the parent's program held {CHAT_GATHERED_TEMP_BYTES[name]}")
        temps.append(temp)
    return "temp_bytes=" + "/".join(map(str, temps))


def paged_kernel(width, kv_heads):
    # ops/pallas_paged_attention.py alone, interpret=False, at the cells' geometries: Trinity's full layer (K 8, a table of
    # 1,024 blocks over [16384, 16, 8, 128]) and its window layers (a ring of 259 over the four layers' leaf), the chat
    # cell's K 2 (its 36 layers' leaf): Mosaic takes the double buffer and the scalar-prefetched tables, the pool stays
    # an operand where it lies
    from accelerate_tpu.ops.pallas_paged_attention import paged_decode_attention
    rows = {1024: 16384, 259: 4 * (16 * 259 + 1)}.get(width, 36 * MIXED_BLOCKS)
    heads = {8: 48, 2: 16}[kv_heads]
    args = (sds((16, heads, 128)), sds((rows, 16, kv_heads, 128)), sds((rows, 16, kv_heads, 128)), sds((16, width), jnp.int32),
            sds((16,), jnp.int32), sds((16,), jnp.int32))
    return (lambda q, k, v, t, lo, hi: paged_decode_attention(q, k, v, t, lo, hi)), args


def check_context_assembly(text):
    # A decode step assembles its context in one pass (PR 29): under kv_pool.gather the blocks are gathered and the new
    # rows scattered into them, a row-sized write.  Nothing else there is as large as the context: no select over it (the
    # fill pass of a gather in jnp.take's default mode; the overlay as a where), no mask or index as long as it (pred, s32),
    # no gather out of the new rows (take_along_axis)
    import re
    # as large as the context: [slots, rows, ...] or, gathered block by block, [slots, blocks, a block's rows, ...]
    sized = re.compile(r"= (\w+)\[%d,(%d[,\]]|%d,)\S* ([\w-]+)\(" % (STEP_SLOTS, STEP_WIDTH * 16, STEP_WIDTH))
    passes = []
    for line in text.splitlines():
        found = sized.search(line)
        if found and "kv_pool.gather" in line and (
                found.group(3) == "select" or found.group(1) in ("pred", "s32") or "take_along_axis" in line):
            passes.append(line.strip()[:160])
    if passes:
        raise AssertionError("the context is passed over again after the block gather: " + " ;; ".join(passes[:4]))


def pool_sized_results(text, whole_pool_only, sizes=None):
    # Instructions of a compiled paged step that produce an array as large as
    # the pool (whole_pool_only) or as a layer's slice of it: a copy, a slice
    # or a re-tiling.  Views (bitcast), parameters and tuple reads move nothing
    import re
    sizes = sizes or (STEP_LAYERS * STEP_BLOCKS, "%d,%d" % (STEP_LAYERS, STEP_BLOCKS)) + (() if whole_pool_only else (STEP_BLOCKS,))
    sized = re.compile(r"= \w+\[(%s)," % "|".join(map(str, sizes)))
    return [line.strip()[:160] for line in text.splitlines()
            if sized.search(line) and not re.search(r"\} (parameter|bitcast|get-tuple-element)\(", line)]


def check_paged_step(spec, compiled):
    # A pool the TPU holds block by block is read where it lies: no result of
    # the step is as large as a layer's slice.  Any other pool costs at most
    # what it cost as a scanned input, and never a copy of the whole pool.
    text, temp = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    sliced = spec in SCANNED_POOL_TEMP_BYTES
    moved = pool_sized_results(text, whole_pool_only=sliced)
    if moved:
        raise AssertionError("the step moves pool-sized arrays: " + " ;; ".join(moved[:4]))
    if sliced and temp > 1.01 * SCANNED_POOL_TEMP_BYTES[spec] + 2**16:
        raise AssertionError(f"temporaries {temp} bytes, {SCANNED_POOL_TEMP_BYTES[spec]} with the pool as a scanned input")
    import re
    cuts = [line for line in text.splitlines() if re.search(r"= \w+\[1,%d," % STEP_BLOCKS, line) and " fusion(" in line]
    if spec.startswith("paged_step_mixed") and sliced and len(cuts) != 2:
        raise AssertionError(f"two groups cut {len(cuts)} layer slices out of the pool's two leaves, not one a leaf")
    return f"temp_bytes={temp}"


from accelerate_tpu.models import generation
from accelerate_tpu.ops import moe

for spec in sys.argv[2:]:
    case, hd, b = spec.split(":")
    print("BEGIN", spec, flush=True)   # an abort after this line belongs to this case
    in_place = case.endswith("_in_place")
    fused = case.endswith("_fused") or case == "moe_kernel" or in_place
    moe._on_tpu = (lambda: True) if fused else (lambda: False)  # the one fact of ops/moe.py's rule that this client cannot show
    generation._on_tpu = (lambda: True) if in_place else (lambda: False)  # and of generation.reads_in_place's
    try:
        if case.startswith("chat_step"):
            print("COMPILED", spec, check_chat_step(int(hd), in_place), flush=True)
            continue
        if case.startswith("mixed_step"):
            print("COMPILED", spec, check_mixed_step(case, int(hd)), flush=True)
            continue
        if case.startswith("state_step"):
            print("COMPILED", spec, check_state_step(int(hd), fused), flush=True)
            continue
        if case.startswith("window_step"):
            print("COMPILED", spec, check_window_step(int(hd), fused, in_place), flush=True)
            continue
        if case.startswith("sparse_step"):
            print("COMPILED", spec, check_sparse_step(int(hd), fused), flush=True)
            continue
        f, args = program(case, int(hd), int(b))
        compiled = jax.jit(f, donate_argnums=getattr(f, "donate", ())).lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text() and not case.startswith("paged_step"):
            raise AssertionError("compiled, but the executable holds no Mosaic kernel")
        if case == "paged_kernel" and (pool_sized_results(compiled.as_text(), True, sizes=(args[1].shape[0],))
                                       or compiled.memory_analysis().temp_size_in_bytes):
            raise AssertionError("the kernel's pool is copied: temporaries " + str(compiled.memory_analysis().temp_size_in_bytes))
        note = check_paged_step(spec, compiled) if case.startswith("paged_step") else ""
        note = check_latent_step(compiled, fused) if case.startswith("latent_step") else note
        note = check_moe_kernel(int(b), compiled) if case == "moe_kernel" else note
        if case in ("paged_step", "latent_step") and spec not in SCANNED_POOL_TEMP_BYTES:  # a decode over a pool read in place
            check_context_assembly(compiled.as_text())
    except Exception:
        print("REFUSED", spec, traceback.format_exc()[-1500:].replace("\n", " | "), flush=True)
    else:
        print("COMPILED", spec, note, flush=True)
"""

CASES = [
    (case, hd, b)
    for hd in (64, 128)
    for case, b in (
        ("flash_fwd", 2),
        ("flash_grad", 2),
        ("flash_kv_valid", 1),
        ("flash_kv_valid", 8),
    )
] + [
    # the whole paged step of models/llama.py; the third field is the number of kv heads.  Beyond "it compiles":
    # pools the TPU holds block by block (generation._blocks_lie_row_by_row) are gathered from where they lie, at a
    # decode and a prefill shape (PERF.md section 6, PR 27) ...
    ("paged_step", 128, 2),
    ("paged_step_prefill", 128, 2),
    ("paged_step_mixed", 128, 2),  # the decode lanes and the chunk as the two groups of one forward (PR 31)
    ("paged_step", 128, 1),
    ("paged_step", 128, 4),
    ("paged_step", 128, 8),
    ("paged_step", 256, 8),
    # ... and just past each edge of that rule, where a gather from the whole pool would cost a second pool (PERF.md
    # section 7.0a), the layer's slice costs what it did as a scanned input: Llama-3.2-1B's heads, GPT-2 small's,
    # an odd K, a wide head with few K, an int8 pool at the chat cell's heads and at Llama-3-8B's
    ("paged_step", 64, 8),
    ("paged_step_mixed", 64, 8),  # two groups slice their layer out of the pool once, not once a group
    ("paged_step", 64, 12),
    ("paged_step", 128, 3),
    ("paged_step", 256, 2),
    ("paged_step_int8", 128, 2),
    ("paged_step_int8", 128, 8),
    # the latent pool of models/deepseek_v3.py (PR 28; the two numbers are not read): no pool-sized result but the scatter
    # of the new rows, the grouped expert product a Mosaic kernel fed from the stacked experts where they lie
    ("latent_step", 512, 64),
    ("latent_step_prefill", 512, 64),
    # serving/programs.py's mixed program (PR 31) at the two serving cells' shapes; the second field is the table width both
    # groups share: it reads the weights once where decode + prefill read them twice, and every pool leaf in place
    ("mixed_step_chat", 64, 0),
    ("mixed_step_chat", 256, 0),
    ("mixed_step_agent", 16, 0),
    ("mixed_step_agent", 64, 0),
    # models/lfm2_moe.py through serving/programs.py at the cut the benchmark serves (PR 32; the second field is the table
    # width): K/V rows of 512 without a head axis beside a state by slot; nothing pool-sized but the scatters of the new rows
    ("state_step", 64, 0),
    # ops/pallas_moe.py (PR 35): the fused grouped SwiGLU alone at the three expert cells' widths and stack sizes (the fields
    # are the token-expert pairs of a dispatch and the experts a layer: kanana's decode, SDAR's mixed dispatch, LFM2's), at the
    # row tile ops/moe.py's rule gives the shape ...
    ("moe_kernel", 96, 128),
    ("moe_kernel", 1280, 128),
    ("moe_kernel", 256, 32),
    ("moe_kernel", 4096, 32),  # 128 rows an expert, the most the rule gives the kernel: its widest row tile at the widest experts
    # ... and the latent step and LFM2's two programs as a TPU builds them, the rule's backend test answered for it: the one
    # fused kernel in place of the three ragged-dot kernels, fed from the stack where it lies like them
    ("latent_step_fused", 512, 64),
    ("latent_step_prefill_fused", 512, 64),
    ("state_step_fused", 64, 0),
    # serving/programs.py's two programs for models/afmoe.py at the trinity-large-preview cell's cut and geometry (PR 38), as a
    # TPU builds them: token rows of two kinds, the window tables narrower than the ring (256 < 259: no sequence has wrapped)
    # and at the ring's width under the widest block tables (1024); and once with lax.ragged_dot, as off the TPU
    ("window_step_fused", 256, 0),
    ("window_step_fused", 1024, 0),
    ("window_step", 256, 0),
    # ops/pallas_paged_attention.py (PR 39): the kernel alone at Trinity's full table and ring and at the chat cell's K 2
    # (the fields are the table's width and K) ...
    ("paged_kernel", 1024, 8),
    ("paged_kernel", 259, 8),
    ("paged_kernel", 256, 2),
    # ... and the two programs that take it, as a TPU builds them: the decoding lanes read in place (one custom call a
    # kind of layer), nothing pool-sized but the scatters, fewer temporaries than the gathered programs of the parent;
    # the chat cell's, at tables of 1,024 and 4,096 rows, keep the parent's gathered programs (models/llama.py does not
    # take the kernel), as a TPU builds them and as the CPU does
    ("window_step_in_place", 256, 0),
    ("window_step_in_place", 1024, 0),
    ("chat_step_in_place", 64, 0),
    ("chat_step_in_place", 256, 0),
    ("chat_step", 256, 0),
    # serving/programs.py's two programs for models/keye_vl2.py at the keye-vl-2.0-30b-a3b cell's cut and geometry, as a
    # TPU builds them, at tables of 1,024 blocks: the decoding lanes score every index key and read the K/V rows their
    # top 2,048 name, nothing pool-sized but the scatters
    ("sparse_step_fused", 1024, 0),
]
IDS = [f"{c}-w{h}" if c.startswith(("mixed_step", "state_step", "window_step", "chat_step", "sparse_step")) else f"{c}-pairs{h}-e{b}" if c == "moe_kernel"
       else f"{c}-w{h}-k{b}" if c == "paged_kernel"
       else f"{c}-hd{h}-{'k' if c.startswith(('paged_step', 'latent_step')) else 'b'}{b}" for c, h, b in CASES]


# ``python -c`` puts its working directory first on sys.path: the child
# imports the accelerate_tpu of the checkout this file belongs to.
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", ACCELERATE_TPU_COMPILE_CACHE="")
    env.pop("XLA_FLAGS", None)  # the child needs no virtual CPU devices
    return env


@pytest.fixture(scope="module")
def compiled():
    """{case: (verdict, detail)} from the one child."""
    specs = [f"{c}:{h}:{b}" for c, h, b in CASES]
    child = subprocess.run(
        [sys.executable, "-c", _CHILD, TOPOLOGY, *specs],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    if child.stdout.startswith("NO_TOPOLOGY"):
        pytest.skip(f"no compile-only TPU client for {TOPOLOGY}: {child.stdout.strip()}")
    results, began = {}, None
    for line in child.stdout.splitlines():
        word, _, rest = line.partition(" ")
        if word == "BEGIN":
            began = rest
        elif word in ("COMPILED", "REFUSED"):
            spec, _, detail = rest.partition(" ")
            results[spec] = (word, detail)
            began = None
    for spec in specs:
        if spec not in results:
            results[spec] = (
                ("ABORTED", f"the compiler process died (exit {child.returncode}) in this "
                            f"case:\n{child.stderr[-2000:]}")
                if spec == began
                else ("NOT REACHED", f"the compiler process died (exit {child.returncode}) in {began}")
            )
    return results


@pytest.mark.parametrize("case,hd,b", CASES, ids=IDS)
def test_pallas_entry_point_compiles_for_v5e(compiled, case, hd, b):
    verdict, detail = compiled[f"{case}:{hd}:{b}"]
    assert verdict == "COMPILED", f"{case} head={hd} batch={b}: {verdict}\n{detail}"
