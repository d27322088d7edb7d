"""CPU-tier perf-regression gate: the PR-4 pipeline wins, asserted forever.

The overlapped execution pipeline was proven with a one-off CPU probe (eager
49 → fused 104 steps/s, 6 → 1 dispatches/step at accum=2) — but a one-off
number in a PR description cannot stop a later change from quietly
re-introducing a per-micro-batch dispatch or a host sync.  And the TPU
benchmark can't either: 4 of 5 bench rounds died to device flake, so "the
benchmark will catch it" means *nothing* catches it.

This gate re-runs a bounded version of that probe on CPU and asserts the
**relative** invariants against a committed baseline
(``benchmarks/perf_baseline_cpu.json``):

- ``dispatches_per_step == 1`` on the fused path (exact and deterministic —
  the sharpest tripwire: any regression to eager-style dispatch shows up as
  an integer, immune to machine noise);
- fused-vs-eager steps/s ratio ≥ a conservative floor (the measured win is
  ~2.1×; the floor is far below it so CI load cannot flake the gate, while a
  real fused-path rot — which lands the ratio at ~1.0 — still fails loudly);
- fused-path host-blocked ms/step under a generous ceiling (catches a
  reintroduced synchronous host round-trip, not scheduler jitter);
- a **ZeRO row** (multi-device runs): the sharded-update fused step must
  report ``zero_active`` (the silent-fallback-to-replicated tripwire),
  still run at ``dispatches/step == 1`` and hold the same fused-vs-eager
  ratio floor — a regression that quietly rebuilds the replicated update
  fails in tier-1, not on the next TPU window;
- an **overlap row** (multi-device runs): a ``jax.profiler`` trace of the
  ZeRO arm is scanned (``telemetry/profile_scan.py``) and the fraction of
  collective time NOT hidden behind concurrent compute must stay under
  ``max_exposed_collective_frac`` — the static byte ledger proves the
  collectives exist; this row proves at runtime that they overlap;
- a **pp row** (multi-device runs): the fused pipeline-parallel train step
  (pp=2 llama through ``make_train_step``) must stay at
  ``max_pp_dispatches_per_step`` == 1 (the whole microbatch schedule +
  backward + update in ONE donated dispatch), the interleaved schedule must
  actually build (``pp_interleaved_active`` — the gpipe-only-fallback
  tripwire, with the analytic tick counts as proof: v·M + S - 1 vs
  M + S - 1), and interleaved-vs-gpipe steps/s must hold
  ``min_interleaved_vs_gpipe_ratio`` (interleaved does
  (v·M+S-1)/(v·(M+S-1)) of gpipe's total layer work — the realized
  bubble-shrink this row keeps honest).

Absolute steps/s are *reported* but never gated — a 2-core CI box drifts
±50% run to run; ratios and dispatch counts don't.

Run it: ``make perf-gate`` (or ``python -m accelerate_tpu.pipeline.perf_gate``);
``tests/test_perf_gate.py`` runs the same gate inside tier-1 so a perf
regression fails the test suite even when no TPU answers.

``ACCELERATE_TPU_PERF_GATE_DEGRADE=eager`` replaces the fused arm with the
eager loop — the knob that *proves* the gate fails when the fused path is
degraded (dispatches/step jumps to ``3 × accum``, the ratio collapses to ~1).
``=zero-fallback`` runs the ZeRO arm with the replicated update — the knob
that proves the ``zero_active`` tripwire catches a silent fallback.
``=no-overlap`` scans the same trace with the concurrent-compute credit
disabled (every collective µs counts as exposed — what stripping the
latency-hiding scheduler flags does to a TPU run) — the knob that proves the
overlap row fails when collectives stop hiding.
``=gpipe-only`` runs the pp row's interleaved arm with the gpipe schedule —
the knob that proves the ``pp_interleaved_active`` tripwire catches a
silently-degraded pipeline schedule.
``=badput`` sleeps between the goodput arm's steps (pure idle badput) — the
knob that proves the **goodput row** (wall-clock productive fraction from
``telemetry/goodput.py``'s attribution ledger, compiles warmed outside the
window) actually judges where the wall clock went.
``=mem-bloat`` registers four extra live parameter copies in the HBM ledger
under a ``perf_gate.bloat`` owner — the knob that proves the **memory row**
(per-chip train-state and serving-pool byte ceilings from
``telemetry/memledger.py``'s attribution ledger; deterministic shape
arithmetic, not allocator stats, so CI load cannot flake it) actually judges
the footprint.  A change that silently doubles optimizer state or fattens
the KV pool fails in tier-1, not on the next real-model TPU run.
``=no-spec`` runs the **spec row**'s speculative arm with ``spec_tokens=0``
— plain greedy masquerading as the speculative config.  The
``serving_spec_active`` tripwire must catch it: the measured ITL ratio stays
near 1.0 (often ABOVE the 0.9 floor, since greedy-vs-greedy is noise), which
is exactly why the integer tripwires, not the ratio floor, carry exactness
(PR 19: the floor only guards a pathological verify-window slowdown).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Optional

__all__ = [
    "load_baseline", "run_probe", "run_pp_probe", "run_serving_probe",
    "run_spec_probe",
    "evaluate", "run_gate", "main",
]

ENV_BASELINE = "ACCELERATE_TPU_PERF_BASELINE"
ENV_DEGRADE = "ACCELERATE_TPU_PERF_GATE_DEGRADE"

DEFAULT_BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "benchmarks",
    "perf_baseline_cpu.json",
)


def load_baseline(path: Optional[str] = None) -> dict:
    """Parse the committed baseline JSON (``$ACCELERATE_TPU_PERF_BASELINE``
    overrides the path for experiments)."""
    path = path or os.environ.get(ENV_BASELINE) or DEFAULT_BASELINE_PATH
    with open(path) as f:
        return json.load(f)


def run_pp_probe(
    steps: int = 3,
    micro_batches: int = 4,
    virtual_stages: int = 2,
    degrade: Optional[str] = None,
) -> dict:
    """The pp row's measurement: gpipe vs interleaved fused pipeline train
    steps on a pp=4 mesh (llama-tiny through ``make_train_step``), at the
    SAME microbatch count M.  The batch geometry (B=32, seq=64) keeps the
    probe in the compute-dominated regime where the schedule's tick count —
    not the scan's per-tick fixed overhead — sets the step time, so the
    interleaved win ((v·M+S-1)/(v·(M+S-1)) = 11/14 of gpipe's layer work at
    these settings) is measurable on a CPU box.  Returns the ``pp_*``
    measurement keys.  ``degrade="gpipe-only"`` builds the "interleaved" arm
    with the gpipe schedule — the self-test that the
    ``pp_interleaved_active`` tripwire actually judges this row."""
    import numpy as np

    import jax

    from .. import telemetry
    from ..accelerator import Accelerator
    from ..models import llama
    from ..parallel.pipeline import (
        pipeline_bubble_fraction,
        pipeline_llama_model,
        pipeline_ticks,
    )
    from ..parallel.sharding import data_sharding
    from ..state import AcceleratorState, GradientState, PartialState
    from ..utils import set_seed
    from ..utils.dataclasses import ParallelismConfig, PipelineParallelPlugin

    import optax

    pp = 4
    M = micro_batches
    v = virtual_stages
    if jax.device_count() < pp or jax.device_count() % pp:
        raise RuntimeError(
            f"run_pp_probe needs a device count divisible by pp={pp} "
            f"(got {jax.device_count()})"
        )
    if degrade is None:
        degrade = os.environ.get(ENV_DEGRADE, "").strip().lower() or None
    tel = telemetry.get_telemetry()
    owns_telemetry = not tel.enabled
    if owns_telemetry:
        telemetry.enable(dir=tempfile.mkdtemp(prefix="atpu_pp_gate_"))
    dispatches = tel.registry.counter("pipeline.dispatches")

    def arm(schedule, vs):
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        set_seed(0)
        acc = Accelerator(
            parallelism_config=ParallelismConfig(pp=pp, dp=max(jax.device_count() // pp, 1)),
            pp_plugin=PipelineParallelPlugin(
                pp_size=pp, num_micro_batches=M, schedule=schedule, virtual_stages=vs
            ),
        )
        cfg = llama.LlamaConfig.tiny(num_layers=8, hidden_size=64, intermediate_size=128)
        params = llama.init_params(cfg, jax.random.key(0))
        model, opt = acc.prepare(pipeline_llama_model(params, cfg), optax.adamw(1e-3))
        step_fn = acc.make_train_step(model, opt)
        rng = np.random.default_rng(0)
        batches = [
            {
                "input_ids": jax.device_put(
                    rng.integers(0, cfg.vocab_size, (32, 64)).astype("int32"),
                    data_sharding(acc.mesh),
                )
            }
            for _ in range(steps)
        ]
        # Warmup compiles AND syncs — its device tail must not bleed into the
        # first timed step's window.
        float(np.asarray(step_fn(batches[0])))
        d0 = dispatches.value
        t0 = time.perf_counter()
        for b in batches[1:]:
            step_fn(b)
        jax.block_until_ready(model.params)
        dt = time.perf_counter() - t0
        timed = max(steps - 1, 1)
        return timed / dt, (dispatches.value - d0) / timed, step_fn

    try:
        gpipe_sps, gpipe_disp, _ = arm("gpipe", 1)
        if degrade == "gpipe-only":
            inter_sps, inter_disp, step_fn = arm("gpipe", 1)
            inter_schedule, inter_v = "gpipe", 1
        else:
            inter_sps, inter_disp, step_fn = arm("interleaved", v)
            inter_schedule, inter_v = "interleaved", v
    finally:
        if owns_telemetry:
            telemetry.disable()
    return {
        "pp_degree": pp,
        "pp_micro_batches": M,
        "pp_virtual_stages": inter_v,
        "pp_gpipe_steps_per_s": round(gpipe_sps, 2),
        "pp_interleaved_steps_per_s": round(inter_sps, 2),
        "pp_interleaved_vs_gpipe_ratio": round(inter_sps / max(gpipe_sps, 1e-9), 3),
        "pp_gpipe_dispatches_per_step": gpipe_disp,
        "pp_dispatches_per_step": inter_disp,
        "pp_active": step_fn.pp_active,
        # The schedule tripwire: interleaved really built iff its analytic
        # tick count differs from gpipe's (v > 1).
        "pp_interleaved_active": inter_schedule == "interleaved" and inter_v > 1,
        "pp_gpipe_ticks": pipeline_ticks(pp, M, 1),
        "pp_interleaved_ticks": pipeline_ticks(pp, M, inter_v),
        "pp_analytic_bubble_gpipe": round(pipeline_bubble_fraction(pp, M, 1), 4),
        "pp_analytic_bubble_interleaved": round(pipeline_bubble_fraction(pp, M, inter_v), 4),
    }


def run_serving_probe(decode_ticks: int = 25) -> dict:
    """The serving row's measurement: a bounded CPU engine (gpt2-tiny) with
    four requests in the decode batch, ticked ``decode_ticks`` times.  What
    repeats exactly, and is judged: decode dispatches per tick == 1,
    ``serving_paged_active`` (a family with an ``apply_paged`` was served on
    the paged back end, not the dense gather-view one), and the pool's
    bytes (the memory row's input)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..models import gpt2
    from ..serving import ServingConfig, ServingEngine
    from ..serving.scheduler import RequestState

    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(
            block_size=8, num_blocks=80, max_slots=4, prefill_chunk=8,
            max_blocks_per_seq=16, prefix_cache=False,
        ),
    )
    for _ in range(4):
        eng.submit(list(rng.integers(0, cfg.vocab_size, size=33)), 30)
    # Prefill everyone into the decode batch before the counted ticks.
    while (
        any(s.request.state != RequestState.DECODING for s in eng.sched.slots.values())
        or eng.sched.pending
    ):
        eng.step()
    d0 = eng.decode_dispatches
    for _ in range(decode_ticks):
        eng.step()
    stats = eng.stats()
    return {
        "serving_decode_dispatches_per_tick": (eng.decode_dispatches - d0) / decode_ticks,
        "serving_paged_active": stats["decode_path"] == "paged",
        # Memory row input: the engine is single-device by design, so the
        # pool's allocation IS its per-chip footprint.
        "serving_pool_bytes_per_chip": stats.get("pool_bytes"),
    }


def run_spec_probe(degrade: Optional[str] = None, max_new: int = 60) -> dict:
    """The serving-spec row's measurement: speculative draft-then-verify vs
    plain greedy decode inter-token latency on a bounded CPU engine pair at
    IDENTICAL geometry (gpt2-tiny, same prompts, same budgets, paged path
    both sides — only ``spec_tokens`` differs).

    The prompts carry a repeated pattern so the default n-gram drafter
    actually hits (the workload speculative serving targets: templated /
    repetitive traffic), and random tiny-model greedy decode promptly falls
    into repetition loops of its own — everything is deterministic per seed,
    so the measured acceptance rate is CI-stable.  Each arm first runs a
    warm-up request end to end (same geometry) so every bucket's program is
    jit-cached before the timed batch; mean inter-token latency then comes
    from the completed requests' own SLO samples.  Judged invariants:
    ``serving_spec_active`` (acceptance > 0 AND tokens/dispatch > 1 — the
    silent-fallback tripwire), per-request token identity vs the greedy
    arm, and the spec-vs-greedy ITL ratio over the committed floor.
    ``degrade="no-spec"`` builds the spec arm with ``spec_tokens=0`` — the
    self-test that this row actually judges speculative decode."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..models import gpt2
    from ..serving import ServingConfig, ServingEngine

    if degrade is None:
        degrade = os.environ.get(ENV_DEGRADE, "").strip().lower() or None
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(13)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=8)]
    # Pure pattern repeats at staggered phases: the trailing n-gram recurs
    # from the very first decode tick, so the drafter contributes over the
    # whole run rather than only after the model falls into its own loop.
    prompts = [pattern * 2 + pattern[:j] for j in (0, 2, 4, 6)]
    max_new = int(max_new)  # 60 for the gated row; self-tests run shorter

    def arm(spec_tokens):
        eng = ServingEngine(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            serving=ServingConfig(
                block_size=8, num_blocks=80, max_slots=4, prefill_chunk=8,
                max_blocks_per_seq=16, prefix_cache=False,
                spec_tokens=spec_tokens,
            ),
        )
        # Warm every bucket's programs (decode, decode_chunk) outside the
        # timed window — same prompt shape as the timed batch.
        eng.submit(list(prompts[0]), max_new)
        eng.run()
        rids = [eng.submit(list(p), max_new) for p in prompts]
        t0 = time.perf_counter()
        outs = eng.run()
        wall = time.perf_counter() - t0
        itl = [
            ms
            for r in eng.pop_finished()
            if r.id in set(rids)
            for ms in r.inter_token_ms
        ]
        stats = eng.stats()
        itl_sorted = sorted(itl)
        return {
            "outputs": [outs[r] for r in rids],
            "itl_ms": sum(itl) / max(len(itl), 1),
            "itl_p95_ms": (
                itl_sorted[min(int(len(itl_sorted) * 0.95), len(itl_sorted) - 1)]
                if itl_sorted else 0.0
            ),
            "wall_s": wall,
            "spec": stats["spec"],
        }

    arm(0)  # discarded: process-level warm-up (first arm pays one-time
    # costs no per-engine warm request covers; measured ~1.4x ITL skew
    # between two IDENTICAL greedy arms without this)
    greedy = arm(0)
    spec = arm(0 if degrade == "no-spec" else 3)
    acceptance = spec["spec"]["acceptance_rate"]
    tokens_per_dispatch = spec["spec"]["tokens_per_dispatch"]
    return {
        "serving_greedy_itl_ms": round(greedy["itl_ms"], 3),
        "serving_spec_itl_ms": round(spec["itl_ms"], 3),
        "serving_greedy_itl_p95_ms": round(greedy["itl_p95_ms"], 3),
        "serving_spec_itl_p95_ms": round(spec["itl_p95_ms"], 3),
        "serving_spec_vs_greedy_itl_ratio": round(
            greedy["itl_ms"] / max(spec["itl_ms"], 1e-9), 3
        ),
        "serving_spec_acceptance_rate": acceptance,
        "serving_spec_tokens_per_dispatch": tokens_per_dispatch,
        "serving_spec_active": bool(acceptance > 0 and tokens_per_dispatch > 1),
        "serving_spec_token_identical": spec["outputs"] == greedy["outputs"],
    }


def run_tiering_probe(cycles: int = 4, degrade: Optional[str] = None) -> dict:
    """The serving-tiering row's measurement: preempt-resume latency with
    the host-DRAM KV tier (demote the victim's blocks on preemption, promote
    on re-admission, zero re-prefill dispatches) vs the re-prefill fallback
    it replaces, at IDENTICAL geometry (gpt2-tiny, same prompt, same preempt
    cadence — only ``host_blocks`` differs).

    Each arm runs one warm request end to end, then repeatedly preempts the
    probe request mid-decode via ``preempt_slot`` and times preemption ->
    next emitted token; one discarded cycle per arm lands the migration /
    re-prefill programs' compiles outside the timed window.  Judged
    invariants: ``serving_tiering_active`` (promotions landed, zero fallback
    re-prefills, and the completed request's prefill dispatches stayed at
    the no-preemption count — the silent-re-prefill tripwire), token
    identity vs the untiered arm, and the migrated-vs-re-prefill resume
    ratio over the committed floor.  ``degrade="no-tiering"`` builds the
    tiered arm with ``host_blocks=0`` — the self-test that this row
    actually judges the migration path."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..models import gpt2
    from ..serving import ServingConfig, ServingEngine
    from ..serving.scheduler import RequestState

    if degrade is None:
        degrade = os.environ.get(ENV_DEGRADE, "").strip().lower() or None
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(7)
    # A long prompt makes the structural gap measurable on CPU: a migrated
    # resume is one promote + one decode tick regardless of prompt length,
    # while the re-prefill fallback pays ceil(rows/chunk) = 13 dispatches.
    # 97 rows keeps the request at EXACTLY 13 blocks through every timed
    # cycle (rows 98..102 as tokens land) — a block-boundary crossing
    # recompiles the demote/promote copies mid-window and poisons the mean.
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=97)]
    max_new = 12

    def arm(host_blocks):
        eng = ServingEngine(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            serving=ServingConfig(
                block_size=8, num_blocks=80, max_slots=4, prefill_chunk=8,
                max_blocks_per_seq=16, prefix_cache=False,
                host_blocks=host_blocks,
            ),
        )
        # Warm every bucket's program end to end outside the timed cycles.
        eng.submit(list(prompt), max_new)
        eng.run()
        eng.pop_finished()
        rid = eng.submit(list(prompt), max_new)
        req = next(r for r in eng.sched.queue if r.id == rid)
        resumes = []
        for cycle in range(cycles + 1):  # cycle 0 discarded: warms the
            # demote/promote (or re-prefill-resume) programs themselves.
            while req.state != RequestState.DECODING or len(req.emitted) <= cycle:
                eng.step()
            idx = next(i for i, s in eng.sched.slots.items() if s.request.id == rid)
            n0 = len(req.emitted)
            t0 = time.perf_counter()
            eng.sched.preempt_slot(idx)
            while len(req.emitted) == n0:
                eng.step()
            if cycle:
                resumes.append((time.perf_counter() - t0) * 1e3)
        outs = eng.run()
        done = next(r for r in eng.pop_finished() if r.id == rid)
        # Raw migration bandwidth: one timed 8-block round trip through the
        # drained cache (second pass — the first warms the per-shape copies).
        demote_ms = promote_ms = None
        if eng.cache.host is not None and eng.cache.host.free_blocks >= 8:
            blocks = eng.sched.allocator.alloc(8)
            for _ in range(2):
                t0 = time.perf_counter()
                host_ids = eng.cache.demote(blocks)
                demote_ms = (time.perf_counter() - t0) * 1e3
                t0 = time.perf_counter()
                eng.cache.promote(host_ids, blocks)
                jax.block_until_ready(list(eng.cache.pool.values()))
                promote_ms = (time.perf_counter() - t0) * 1e3
            eng.sched.allocator.free(blocks)
        return {
            "resume_ms": sum(resumes) / max(len(resumes), 1),
            "outputs": outs[rid],
            "tiering": eng.stats()["tiering"],
            "prefill_dispatches": done.prefill_dispatches,
            "migrations": done.migrations,
            "block_bytes": eng.cache.block_bytes(),
            "demote_ms": demote_ms,
            "promote_ms": promote_ms,
        }

    base = arm(0)  # the re-prefill resume path the tier replaces
    tier = arm(0 if degrade == "no-tiering" else 16)
    tiering = tier["tiering"]
    active = bool(
        tiering is not None
        and tiering["promotions"] >= 1
        and tiering["fallback_reprefills"] == 0
        # Zero re-prefill: the completed request's prefill dispatches must
        # equal the single-admission chunk count despite every preemption.
        and tier["prefill_dispatches"] == -(-len(prompt) // 8)
    )
    def bw(ms):
        return round(8 * tier["block_bytes"] / (ms / 1e3) / 1e6, 1) if ms else None
    return {
        "serving_reprefill_resume_ms": round(base["resume_ms"], 3),
        "serving_migrated_resume_ms": round(tier["resume_ms"], 3),
        "serving_migrated_vs_reprefill_ratio": round(
            base["resume_ms"] / max(tier["resume_ms"], 1e-9), 3
        ),
        "serving_tiering_active": active,
        "serving_tiering_token_identical": tier["outputs"] == base["outputs"],
        "serving_tier_migrations": tier["migrations"],
        "serving_tier_fallback_reprefills": (
            tiering["fallback_reprefills"] if tiering is not None else None
        ),
        "serving_tier_demote_mb_per_s": bw(tier["demote_ms"]),
        "serving_tier_promote_mb_per_s": bw(tier["promote_ms"]),
    }


def run_probe(
    accum: int = 2,
    steps: int = 10,
    dim: int = 128,
    batch: int = 8,
    epochs: int = 3,
    prefetch: int = 2,
    degrade: Optional[str] = None,
    pp: bool = True,
    serving: bool = True,
) -> dict:
    """Bounded eager-vs-fused micro-benchmark (the bench.py pipeline probe,
    trimmed for a test-suite budget).  Returns the measurements dict the gate
    judges.  ``degrade="eager"`` runs the eager loop in the fused arm — the
    self-test knob.  ``pp=False`` / ``serving=False`` skip the
    pipeline-parallel / serving-decode rows (targeted self-tests of the
    other rows don't pay for their extra compiles)."""
    import numpy as np
    import torch

    from .. import telemetry
    from ..accelerator import Accelerator
    from ..state import AcceleratorState, GradientState, PartialState
    from ..utils import DataLoaderConfiguration, set_seed

    if degrade is None:
        degrade = os.environ.get(ENV_DEGRADE, "").strip().lower() or None
    tel = telemetry.get_telemetry()
    owns_telemetry = not tel.enabled
    if owns_telemetry:
        telemetry.enable(dir=tempfile.mkdtemp(prefix="atpu_perf_gate_"))
    dispatches = tel.registry.counter("pipeline.dispatches")

    class MLPWithLoss(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.net = torch.nn.Sequential(
                torch.nn.Linear(dim, dim),
                torch.nn.Tanh(),
                torch.nn.Linear(dim, 1),
            )

        def forward(self, x, y):
            pred = self.net(x)
            return {"loss": torch.nn.functional.mse_loss(pred, y), "logits": pred}

    n_batches = accum * steps

    def build():
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()
        set_seed(0)
        acc = Accelerator(
            gradient_accumulation_steps=accum,
            dataloader_config=DataLoaderConfiguration(prefetch_to_device=prefetch),
        )
        model = MLPWithLoss()
        opt = torch.optim.AdamW(model.parameters(), lr=1e-3)
        rng = np.random.default_rng(0)
        data = [
            {
                "x": torch.from_numpy(rng.standard_normal((batch, dim)).astype("float32")),
                "y": torch.from_numpy(rng.standard_normal((batch, 1)).astype("float32")),
            }
            for _ in range(n_batches)
        ]
        model, opt = acc.prepare(model, opt)
        dl = acc.prepare_data_loader(data)
        return acc, model, opt, dl

    def eager_arm():
        import jax

        acc, model, opt, dl = build()

        def one_epoch():
            blocked = 0.0
            it = iter(dl)
            t_start = time.perf_counter()
            while True:
                t0 = time.perf_counter()
                try:
                    batch_data = next(it)
                except StopIteration:
                    break
                blocked += time.perf_counter() - t0
                with acc.accumulate(model):
                    out = model(**batch_data)
                    acc.backward(out.loss)
                    opt.step()
                    opt.zero_grad()
            jax.block_until_ready(model.params)
            return time.perf_counter() - t_start, blocked

        one_epoch()  # warmup: compiles
        best_dt, best_blocked, d0 = float("inf"), 0.0, dispatches.value
        for _ in range(epochs):
            dt, blocked = one_epoch()
            if dt < best_dt:
                best_dt, best_blocked = dt, blocked
        per_step_dispatch = (dispatches.value - d0) / (epochs * steps)
        return steps / best_dt, per_step_dispatch, best_blocked / steps * 1e3

    def fused_arm(zero=None, trace_dir=None):
        import jax

        acc, model, opt, dl = build()
        step_fn = acc.make_train_step(model, opt, zero=zero)

        def one_epoch():
            blocked = 0.0
            window = []
            it = iter(dl)
            t_start = time.perf_counter()
            while True:
                t0 = time.perf_counter()
                try:
                    batch_data = next(it)
                except StopIteration:
                    break
                blocked += time.perf_counter() - t0
                window.append(batch_data)
                if len(window) == accum:
                    step_fn(window)
                    window = []
            jax.block_until_ready(model.params)
            return time.perf_counter() - t_start, blocked

        one_epoch()
        best_dt, best_blocked, d0 = float("inf"), 0.0, dispatches.value
        for _ in range(epochs):
            dt, blocked = one_epoch()
            if dt < best_dt:
                best_dt, best_blocked = dt, blocked
        per_step_dispatch = (dispatches.value - d0) / (epochs * steps)
        if trace_dir is not None:
            # One extra, untimed epoch under the profiler: the overlap audit
            # must not tax the steps/s measurement it rides along with.
            jax.profiler.start_trace(trace_dir)
            try:
                one_epoch()
            finally:
                jax.profiler.stop_trace()
        return (
            steps / best_dt,
            per_step_dispatch,
            best_blocked / steps * 1e3,
            step_fn.zero_active,
        )

    try:
        eager_sps, eager_disp, eager_blocked = eager_arm()
        if degrade == "eager":
            fused_sps, fused_disp, fused_blocked = eager_arm()
        else:
            # zero=False pinned: the baseline arm must measure the replicated
            # fused step even when the operator exports ACCELERATE_TPU_ZERO=1
            # (zero=None would defer to that env and skew every ratio).
            fused_sps, fused_disp, fused_blocked, _ = fused_arm(zero=False)
        # ZeRO row: only meaningful on a multi-device mesh (a 1-device run
        # has no dp axis to shard over — the arm is skipped, and evaluate()
        # skips its judgments when zero_active is None).
        import jax
        import warnings

        zero_sps = zero_disp = zero_blocked = None
        zero_active = None
        zero_exposed_frac = None
        zero_profile = None
        zero_profile_error = None
        if jax.device_count() >= 2:
            trace_dir = tempfile.mkdtemp(prefix="atpu_perf_gate_trace_")
            with warnings.catch_warnings():
                # The deliberate zero-fallback degrade warns; the probe's
                # numbers are the signal, not the warning.
                warnings.simplefilter("ignore")
                zero_sps, zero_disp, zero_blocked, zero_active = fused_arm(
                    zero=False if degrade == "zero-fallback" else True,
                    trace_dir=trace_dir,
                )
            # Overlap audit over the captured trace: the only *runtime* proof
            # that the ZeRO collectives hide behind compute.  The "no-overlap"
            # degrade disables the concurrent-compute credit — the self-test
            # that shows the exposed-comms row actually judges this number.
            try:
                from ..telemetry import profile_scan

                zero_profile = profile_scan.analyze_trace_dir(
                    trace_dir, assume_no_overlap=(degrade == "no-overlap")
                )
                if zero_profile.collective_ms > 0:
                    zero_exposed_frac = round(
                        zero_profile.exposed_collective_ms / zero_profile.collective_ms,
                        4,
                    )
                else:
                    zero_profile_error = "trace has no collective ops"
            except Exception as e:
                zero_profile_error = str(e)[:200]
        # pp row: the probe builds a pp=4 mesh, so it needs a device count
        # divisible by 4 (the ZeRO row's >= 2 condition is not enough here —
        # a 2-device run must SKIP the row, not crash the gate).
        pp_row = None
        if pp and jax.device_count() >= 4 and jax.device_count() % 4 == 0:
            pp_row = run_pp_probe(degrade=degrade)

        # serving row: the continuous-batching engine's decode tick —
        # single-device by design (the engine is mesh-agnostic), so
        # unlike the ZeRO/pp rows it runs on every probe.
        serving_row = None
        if serving:
            serving_row = run_serving_probe()
            # spec row: speculative vs greedy decode on the same engine
            # geometry (one more paired probe; rides the serving flag).
            serving_row.update(run_spec_probe(degrade=degrade))
            # tiering row: migrated preempt-resume vs re-prefill on the same
            # engine geometry (the host-DRAM KV tier's paired probe).
            serving_row.update(run_tiering_probe(degrade=degrade))

        # goodput row: one fused epoch (compiles warmed OUTSIDE the window)
        # through the wall-clock attribution ledger — the productive fraction
        # is the runtime proof that steps, not overhead, own the wall clock.
        # ``degrade="badput"`` sleeps between steps: pure idle badput, the
        # self-test that this row actually judges the fraction.
        def goodput_arm():
            from ..telemetry import goodput as goodput_mod

            acc, model, opt, dl = build()
            step_fn = acc.make_train_step(model, opt, zero=False)
            # Pre-staged windows: the row judges the step-dominated regime
            # (loader overhead has its own host-blocked row above).
            windows, window = [], []
            for batch_data in dl:
                window.append(batch_data)
                if len(window) == accum:
                    windows.append(window)
                    window = []
            # Warmup epoch: BOTH compiles (the uncommitted-params first call
            # and the committed-sharding steady-state program) land outside
            # the measured window.
            for w in windows:
                step_fn(w)
            jax.block_until_ready(model.params)
            badput_sleep = 0.1 if degrade == "badput" else 0.0
            # attached() restores any pre-existing ledger: the gate running
            # inside a goodput-enabled process must not destroy its host
            # run's accounting.
            with goodput_mod.attached() as led:
                for _ in range(epochs):
                    for w in windows:
                        step_fn(w)
                        if badput_sleep:
                            time.sleep(badput_sleep)
                jax.block_until_ready(model.params)
                return led.summary(), model.params

        goodput_summary, probe_params = goodput_arm()

        # memory row: the per-chip train-state footprint from the HBM ledger
        # (``make_train_step``'s build registers ``train.params`` and
        # ``train.opt_state`` after ZeRO placement).  Deterministic shape
        # arithmetic, not allocator stats — CI load cannot flake it.
        # ``degrade="mem-bloat"`` registers four real extra parameter copies
        # under ``perf_gate.bloat``: the self-test that the committed per-chip
        # ceiling actually judges this row.
        def memory_arm():
            from ..telemetry.memledger import get_memory_ledger

            # The goodput arm's ``make_train_step`` build just registered
            # ``train.params``/``train.opt_state`` at this exact geometry
            # (zero=False, same build()) and registrations outlive the arm —
            # read the ledger rather than paying another build + compile.
            ledger = get_memory_ledger()
            bloat = None
            if degrade == "mem-bloat":
                # Live copies (leaf + 1 forces fresh buffers), registered
                # like any other owner; released once the number is read.
                bloat = [
                    jax.tree_util.tree_map(lambda leaf: leaf + 1, probe_params)
                    for _ in range(4)
                ]
                ledger.register("perf_gate.bloat", tree=bloat)
            try:
                by_owner = {r.owner: r.device_bytes for r in ledger.owners()}
                return sum(
                    by_owner.get(k, 0)
                    for k in ("train.params", "train.opt_state", "perf_gate.bloat")
                ) or None
            finally:
                if bloat is not None:
                    del bloat
                    ledger.unregister("perf_gate.bloat")

        train_state_bytes = memory_arm()
    finally:
        if owns_telemetry:
            telemetry.disable()
    measurements = {
        "probe": {
            "accum_steps": accum,
            "optimizer_steps": steps,
            "dim": dim,
            "batch": batch,
            "epochs": epochs,
            "prefetch": prefetch,
            "degrade": degrade,
        },
        "eager_steps_per_s": round(eager_sps, 2),
        "fused_steps_per_s": round(fused_sps, 2),
        "fused_vs_eager_ratio": round(fused_sps / max(eager_sps, 1e-9), 3),
        "eager_dispatches_per_step": eager_disp,
        "dispatches_per_step": fused_disp,
        "fused_host_blocked_ms_per_step": round(fused_blocked, 3),
        "eager_host_blocked_ms_per_step": round(eager_blocked, 3),
        "zero_active": zero_active,
        "goodput_productive_frac": round(goodput_summary["goodput_fraction"], 4),
        "goodput_elapsed_s": round(goodput_summary["elapsed_s"], 3),
        "goodput_conservation_error_s": goodput_summary["conservation_error_s"],
        "train_state_bytes_per_chip": train_state_bytes,
    }
    if zero_sps is not None:
        measurements.update(
            {
                "zero_steps_per_s": round(zero_sps, 2),
                "zero_vs_eager_ratio": round(zero_sps / max(eager_sps, 1e-9), 3),
                "zero_dispatches_per_step": zero_disp,
                "zero_host_blocked_ms_per_step": round(zero_blocked, 3),
                "zero_exposed_collective_frac": zero_exposed_frac,
            }
        )
        if zero_profile is not None and zero_exposed_frac is not None:
            measurements["zero_overlap_fraction"] = zero_profile.overlap_fraction
            measurements["zero_collective_ms"] = zero_profile.collective_ms
            measurements["zero_exposed_collective_ms"] = zero_profile.exposed_collective_ms
        if zero_profile_error is not None:
            measurements["zero_profile_error"] = zero_profile_error
    if pp_row is not None:
        measurements.update(pp_row)
    if serving_row is not None:
        measurements.update(serving_row)
    return measurements


def evaluate(measurements: dict, baseline: dict) -> list:
    """Judge measurements against the baseline; returns failure strings
    (empty == gate passes)."""
    failures = []
    max_disp = baseline.get("max_dispatches_per_step")
    if max_disp is not None and measurements["dispatches_per_step"] > max_disp + 1e-9:
        failures.append(
            f"dispatches/step {measurements['dispatches_per_step']:.2f} > "
            f"baseline max {max_disp} — the fused train step is no longer one "
            "dispatch per optimizer step"
        )
    min_ratio = baseline.get("min_fused_vs_eager_ratio")
    if min_ratio is not None and measurements["fused_vs_eager_ratio"] < min_ratio:
        failures.append(
            f"fused-vs-eager steps/s ratio {measurements['fused_vs_eager_ratio']:.3f} < "
            f"baseline min {min_ratio} — the fused-path speedup regressed"
        )
    max_blocked = baseline.get("max_fused_host_blocked_ms_per_step")
    if (
        max_blocked is not None
        and measurements["fused_host_blocked_ms_per_step"] > max_blocked
    ):
        failures.append(
            f"fused host-blocked {measurements['fused_host_blocked_ms_per_step']:.1f} "
            f"ms/step > baseline max {max_blocked} — a synchronous host wait "
            "crept back into the hot loop"
        )
    # ZeRO row: judged only when the arm ran (multi-device probe).  A run
    # where the sharded update silently fell back to the replicated one is
    # exactly the regression this row exists to catch.
    zero_active = measurements.get("zero_active")
    if zero_active is not None or "zero_dispatches_per_step" in measurements:
        if baseline.get("require_zero_active") and zero_active is False:
            failures.append(
                "zero_active is False — the ZeRO sharded update silently fell "
                "back to the replicated fused update"
            )
        max_zero_disp = baseline.get("max_zero_dispatches_per_step")
        if (
            max_zero_disp is not None
            and measurements.get("zero_dispatches_per_step") is not None
            and measurements["zero_dispatches_per_step"] > max_zero_disp + 1e-9
        ):
            failures.append(
                f"ZeRO dispatches/step {measurements['zero_dispatches_per_step']:.2f} > "
                f"baseline max {max_zero_disp} — the sharded update broke the "
                "one-dispatch fused window"
            )
        min_zero_ratio = baseline.get("min_zero_vs_eager_ratio")
        if (
            min_zero_ratio is not None
            and measurements.get("zero_vs_eager_ratio") is not None
            and measurements["zero_vs_eager_ratio"] < min_zero_ratio
        ):
            failures.append(
                f"ZeRO-vs-eager steps/s ratio {measurements['zero_vs_eager_ratio']:.3f} < "
                f"baseline min {min_zero_ratio} — the sharded update lost the "
                "fused-path speedup"
            )
        # Overlap row: the runtime comms/compute-overlap invariant from the
        # trace scan of the ZeRO arm.  A broken capture is a broken check —
        # it fails loudly rather than silently skipping the row.
        max_exposed = baseline.get("max_exposed_collective_frac")
        if max_exposed is not None:
            exposed_frac = measurements.get("zero_exposed_collective_frac")
            if exposed_frac is None:
                failures.append(
                    "exposed-collective audit produced no number ("
                    f"{measurements.get('zero_profile_error') or 'no trace analyzed'}) — "
                    "the overlap invariant went unchecked"
                )
            elif exposed_frac > max_exposed:
                failures.append(
                    f"exposed-collective fraction {exposed_frac:.3f} > baseline max "
                    f"{max_exposed} — ZeRO collectives are no longer hidden behind "
                    "compute (comms/compute overlap regressed)"
                )
    # goodput row: the wall-clock productive fraction of a fused epoch (the
    # attribution-ledger audit).  Like the overlap row, a missing number is
    # a broken check and fails loudly; the conservation residual must also
    # stay at float noise — a ledger that double-counts is no ledger.
    min_goodput = baseline.get("min_goodput_productive_frac")
    if min_goodput is not None:
        frac = measurements.get("goodput_productive_frac")
        if frac is None:
            failures.append(
                "goodput audit produced no number — the goodput row went "
                "unchecked"
            )
        elif frac < min_goodput:
            failures.append(
                f"goodput productive fraction {frac:.3f} < baseline min "
                f"{min_goodput} — wall-clock is leaking into badput "
                "(idle/input-wait) around the fused step"
            )
    max_conservation = baseline.get("max_goodput_conservation_error_s")
    if (
        max_conservation is not None
        and measurements.get("goodput_conservation_error_s") is not None
        and abs(measurements["goodput_conservation_error_s"]) > max_conservation
    ):
        failures.append(
            f"goodput conservation error "
            f"{measurements['goodput_conservation_error_s']} s exceeds "
            f"{max_conservation} — the ledger's categories no longer sum to "
            "the elapsed wall-clock window"
        )
    # memory row: per-chip footprint ceilings from the HBM ledger.  Like the
    # overlap and goodput rows, a missing number is a broken check and fails
    # loudly — a deleted registration hook must not silently un-gate memory.
    max_train_bytes = baseline.get("max_train_state_bytes_per_chip")
    if max_train_bytes is not None:
        train_bytes = measurements.get("train_state_bytes_per_chip")
        if train_bytes is None:
            failures.append(
                "memory audit produced no number — the train-state memory row "
                "went unchecked (ledger registration missing?)"
            )
        elif train_bytes > max_train_bytes:
            failures.append(
                f"train-state footprint {train_bytes} B/chip > baseline max "
                f"{max_train_bytes} — params+optimizer memory bloated past "
                "the committed per-chip ceiling"
            )
    max_pool_bytes = baseline.get("max_serving_pool_bytes_per_chip")
    if max_pool_bytes is not None and "serving_paged_active" in measurements:
        pool_bytes = measurements.get("serving_pool_bytes_per_chip")
        if pool_bytes is None:
            failures.append(
                "serving pool audit produced no number — the serving memory "
                "row went unchecked"
            )
        elif pool_bytes > max_pool_bytes:
            failures.append(
                f"serving KV pool {pool_bytes} B/chip > baseline max "
                f"{max_pool_bytes} — the paged pool's footprint bloated past "
                "the committed per-chip ceiling"
            )
    # pp row: judged only when the arm ran (multi-device probe).  An
    # "interleaved" request that silently built gpipe, a fused pp step that
    # regressed to per-tick dispatches, or an interleaved schedule slower
    # than gpipe are exactly the regressions this row exists to catch.
    if "pp_dispatches_per_step" in measurements:
        if baseline.get("require_pp_interleaved") and not measurements.get(
            "pp_interleaved_active"
        ):
            failures.append(
                "pp_interleaved_active is False — the interleaved pipeline "
                "schedule silently fell back to gpipe "
                f"(ticks {measurements.get('pp_interleaved_ticks')} vs gpipe "
                f"{measurements.get('pp_gpipe_ticks')})"
            )
        max_pp_disp = baseline.get("max_pp_dispatches_per_step")
        if max_pp_disp is not None:
            # BOTH schedules' fused steps must hold the one-dispatch invariant
            # (a schedule-conditional regression could break just one arm).
            for key, label in (
                ("pp_dispatches_per_step", "interleaved"),
                ("pp_gpipe_dispatches_per_step", "gpipe"),
            ):
                disp = measurements.get(key)
                if disp is not None and disp > max_pp_disp + 1e-9:
                    failures.append(
                        f"pp dispatches/step ({label}) {disp:.2f} > baseline max "
                        f"{max_pp_disp} — the fused pipeline-parallel train step "
                        "is no longer one dispatch per optimizer step"
                    )
        min_pp_ratio = baseline.get("min_interleaved_vs_gpipe_ratio")
        if (
            min_pp_ratio is not None
            and measurements.get("pp_interleaved_vs_gpipe_ratio") is not None
            and measurements["pp_interleaved_vs_gpipe_ratio"] < min_pp_ratio
        ):
            failures.append(
                f"interleaved-vs-gpipe steps/s ratio "
                f"{measurements['pp_interleaved_vs_gpipe_ratio']:.3f} < baseline min "
                f"{min_pp_ratio} — the interleaved schedule lost its bubble-shrink "
                "win over gpipe"
            )
    # serving row: judged only when the arm ran.  A paged family that was
    # served by the dense gather-view program, or a tick that grew a second
    # dispatch, are exactly the regressions this row exists to catch.
    if "serving_paged_active" in measurements:
        if baseline.get("require_serving_paged") and not measurements.get(
            "serving_paged_active"
        ):
            failures.append(
                "serving_paged_active is False — the serving decode silently "
                "fell back to the dense gather-view program"
            )
        max_serving_disp = baseline.get("max_serving_decode_dispatches_per_tick")
        if max_serving_disp is not None:
            disp = measurements.get("serving_decode_dispatches_per_tick")
            if disp is not None and disp > max_serving_disp + 1e-9:
                failures.append(
                    f"serving decode dispatches/tick {disp:.2f} > baseline max "
                    f"{max_serving_disp} — the paged decode is no longer one "
                    "fused dispatch per engine tick"
                )
    # spec row: judged only when the arm ran.  A speculative config that
    # silently decodes greedily (drafter never fires, verify program lost),
    # an accept/rewind bug that diverges from greedy, or a verify dispatch
    # slower per token than the single-token program it replaces are exactly
    # the regressions this row exists to catch.
    if "serving_spec_vs_greedy_itl_ratio" in measurements:
        if baseline.get("require_spec_active"):
            if not measurements.get("serving_spec_active"):
                failures.append(
                    "serving_spec_active is False — speculative decode "
                    "silently fell back to plain greedy (no drafts accepted "
                    "or no multi-token dispatches landed)"
                )
            if measurements.get("serving_spec_token_identical") is False:
                failures.append(
                    "speculative serving outputs diverged from the greedy "
                    "arm — the per-slot accept/rewind contract is broken"
                )
        min_spec_ratio = baseline.get("min_spec_vs_greedy_itl_ratio")
        if (
            min_spec_ratio is not None
            and measurements["serving_spec_vs_greedy_itl_ratio"] < min_spec_ratio
        ):
            failures.append(
                f"spec-vs-greedy inter-token latency ratio "
                f"{measurements['serving_spec_vs_greedy_itl_ratio']:.3f} < baseline "
                f"min {min_spec_ratio} — draft-then-verify stopped beating "
                "one-token-per-dispatch greedy decode"
            )
    # tiering row: judged only when the arm ran.  A preempted request that
    # silently re-prefills instead of resuming from its host-demoted blocks,
    # a migration round trip that corrupts the KV (token divergence), or a
    # migrated resume slower than the re-prefill it replaces are exactly the
    # regressions this row exists to catch.
    if "serving_migrated_vs_reprefill_ratio" in measurements:
        if baseline.get("require_tiering_active"):
            if not measurements.get("serving_tiering_active"):
                failures.append(
                    "serving_tiering_active is False — preempted requests are "
                    "not resuming from host-demoted KV blocks (no promotions "
                    "landed, a fallback re-prefill fired, or prefill "
                    "dispatches grew past the single-admission count)"
                )
            if measurements.get("serving_tiering_token_identical") is False:
                failures.append(
                    "tiered serving outputs diverged from the untiered arm — "
                    "the HBM->host->HBM round trip corrupted KV state"
                )
        min_tier_ratio = baseline.get("min_migrated_resume_vs_reprefill_ratio")
        if (
            min_tier_ratio is not None
            and measurements["serving_migrated_vs_reprefill_ratio"] < min_tier_ratio
        ):
            failures.append(
                f"migrated-vs-re-prefill resume ratio "
                f"{measurements['serving_migrated_vs_reprefill_ratio']:.3f} < "
                f"baseline min {min_tier_ratio} — resuming a preempted request "
                "from the host tier stopped beating re-prefilling it from "
                "scratch"
            )
    return failures


def run_gate(baseline_path: Optional[str] = None, probe_kwargs: Optional[dict] = None) -> int:
    """Run probe + evaluate; prints the verdict, returns a process rc."""
    baseline = load_baseline(baseline_path)
    probe_cfg = dict(baseline.get("probe") or {})
    probe_cfg.update(probe_kwargs or {})
    measurements = run_probe(**probe_cfg)
    print(json.dumps({"perf_gate": measurements}), flush=True)
    failures = evaluate(measurements, baseline)
    if failures:
        for failure in failures:
            print(f"PERF GATE FAIL: {failure}", file=sys.stderr, flush=True)
        return 1
    zero_note = ""
    if measurements.get("zero_vs_eager_ratio") is not None:
        zero_note = (
            f", ZeRO {measurements['zero_vs_eager_ratio']}x at "
            f"{measurements['zero_dispatches_per_step']:.0f} dispatch/step"
        )
        if measurements.get("zero_exposed_collective_frac") is not None:
            zero_note += (
                f", exposed comms {measurements['zero_exposed_collective_frac']:.2f} "
                "of collective time"
            )
    elif measurements.get("zero_active") is None:
        zero_note = ", ZeRO row skipped (single-device probe)"
    if measurements.get("pp_interleaved_vs_gpipe_ratio") is not None:
        zero_note += (
            f", pp interleaved/gpipe {measurements['pp_interleaved_vs_gpipe_ratio']}x "
            f"at {measurements['pp_dispatches_per_step']:.0f} dispatch/step "
            f"(analytic bubble {measurements['pp_analytic_bubble_gpipe']} -> "
            f"{measurements['pp_analytic_bubble_interleaved']})"
        )
    if measurements.get("goodput_productive_frac") is not None:
        zero_note += (
            f", goodput {measurements['goodput_productive_frac']:.2f} productive"
        )
    if measurements.get("serving_decode_dispatches_per_tick") is not None:
        zero_note += (
            f", serving decode {measurements['serving_decode_dispatches_per_tick']:.0f} "
            "dispatch/tick"
        )
    if measurements.get("serving_migrated_vs_reprefill_ratio") is not None:
        zero_note += (
            f", tiering migrated/re-prefill resume "
            f"{measurements['serving_migrated_vs_reprefill_ratio']}x"
        )
    if measurements.get("train_state_bytes_per_chip") is not None:
        zero_note += (
            f", train state {measurements['train_state_bytes_per_chip']} B/chip"
        )
        if measurements.get("serving_pool_bytes_per_chip") is not None:
            zero_note += (
                f", serving pool {measurements['serving_pool_bytes_per_chip']} B/chip"
            )
    print(
        "perf-gate OK — "
        f"fused/eager {measurements['fused_vs_eager_ratio']}x "
        f"({measurements['eager_steps_per_s']} -> {measurements['fused_steps_per_s']} steps/s), "
        f"{measurements['dispatches_per_step']:.0f} dispatch/step, "
        f"host-blocked {measurements['fused_host_blocked_ms_per_step']} ms/step"
        + zero_note
    )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m accelerate_tpu.pipeline.perf_gate",
        description="CPU-tier perf-regression gate for the fused train step.",
    )
    parser.add_argument(
        "--baseline",
        default=None,
        help=f"baseline JSON (default: {os.path.normpath(DEFAULT_BASELINE_PATH)})",
    )
    args = parser.parse_args(argv)
    return run_gate(args.baseline)


if __name__ == "__main__":
    sys.exit(main())
