"""Compiled-program inspector: HLO comms ledger, cost/memory analysis, and
resharding lint, on CPU meshes (conftest provides 8 virtual devices).

The toy cases pin the collectives XLA's SPMD partitioner inserts for the three
canonical shardings — dp (gradient all-reduce), fsdp (weight all-gather +
grad sync), tp (activation all-reduce) — and the headline ledger invariant:
on a dp mesh the gradient all-reduce byte volume equals total parameter bytes
(within 10%).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from accelerate_tpu.telemetry import hlo_scan, introspect


def _mesh(axes: dict) -> Mesh:
    shape = tuple(axes.values())
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), tuple(axes))


def _sq_loss_step(lr=0.01):
    def loss(w, x):
        return jnp.sum((x @ w) ** 2)

    def step(w, x):
        return w - lr * jax.grad(loss)(w, x)

    return step


# ---------------------------------------------------------------------------
# hlo_scan unit tests (pure text, no compilation)
# ---------------------------------------------------------------------------


def test_parse_shape_bytes():
    assert hlo_scan.parse_shape_bytes("f32[4,8]{1,0}") == 128
    assert hlo_scan.parse_shape_bytes("bf16[2,3]") == 12
    assert hlo_scan.parse_shape_bytes("pred[]") == 1
    assert hlo_scan.parse_shape_bytes("(f32[4], bf16[4])") == 16 + 8


def test_parse_collectives_text_fixture():
    hlo = """
  %all-reduce.1 = f32[256,128]{1,0} all-reduce(f32[256,128]{1,0} %dot), channel_id=1, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%add
  %ag = f32[64,64]{1,0} all-gather(f32[32,64]{1,0} %p0), channel_id=2, replica_groups={{0,1},{2,3}}, dimensions={0}
  %noop = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %x), replica_groups={{0},{1},{2},{3}}, to_apply=%add
  %cp = f32[16]{0} collective-permute(f32[16]{0} %y), source_target_pairs={{0,1},{1,0}}
"""
    ops = hlo_scan.parse_collectives(hlo)
    assert [op.kind for op in ops] == [
        "all-reduce", "all-gather", "all-reduce", "collective-permute",
    ]
    assert ops[0].bytes == 256 * 128 * 4
    assert ops[2].is_degenerate  # single-member groups: no traffic
    ledger = hlo_scan.scan_hlo(hlo)
    assert ledger.degenerate_ops == 1
    assert ledger.by_kind["all-reduce"]["count"] == 1  # degenerate one excluded
    assert ledger.total_bytes == 256 * 128 * 4 + 64 * 64 * 4 + 16 * 4


def test_async_start_tuple_shapes_count_result_only():
    """TPU lowers collectives async: <op>-start result tuples carry operand
    buffers and scalar context next to the result — only the result may count."""
    hlo = """
  %ag = (f32[32,64]{1,0}, f32[64,64]{1,0}) all-gather-start(f32[32,64]{1,0} %p0), channel_id=1, replica_groups={{0,1}}, dimensions={0}
  %cp = (f32[16]{0}, f32[16]{0}, u32[], u32[]) collective-permute-start(f32[16]{0} %y), source_target_pairs={{0,1},{1,0}}
  %q = (s8[32]{0}, s8[64]{0}) all-gather-start(s8[32]{0} %w8), channel_id=2, replica_groups={{0,1}}, dimensions={0}
  %c = (f32[8]{0}, f32[4]{0}, f32[8]{0}, f32[4]{0}) all-reduce-start(f32[8]{0} %a, f32[4]{0} %b), replica_groups={{0,1}}, to_apply=%add
"""
    ops = hlo_scan.parse_collectives(hlo)
    assert [op.bytes for op in ops] == [
        64 * 64 * 4,  # the gathered result, not operand + result
        16 * 4,       # one buffer; u32[] contexts excluded
        64,           # int8 PAYLOAD keeps counting (scalar-context filter only)
        8 * 4 + 4 * 4,  # combined (operands..., results...): the results half
    ]


def test_reduce_scatter_counts_operand_side_bytes():
    """Reduce-scatter's RESULT is the scattered shard — the ledger must scale
    it back up by the replica-group size so the ZeRO invariant
    (reduce-scatter ≈ param bytes ≈ the all-reduce it replaced) is checkable
    on the same byte convention as every other collective."""
    hlo = """
  %rs = f32[32,128]{1,0} reduce-scatter(f32[256,128]{1,0} %g), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, to_apply=%add
  %ag = f32[256,128]{1,0} all-gather(f32[32,128]{1,0} %p), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
"""
    ops = hlo_scan.parse_collectives(hlo)
    full = 256 * 128 * 4
    assert [op.kind for op in ops] == ["reduce-scatter", "all-gather"]
    assert ops[0].bytes == full  # shard result (full/8) x group_size 8
    assert ops[1].bytes == full  # gathered result counts as-is
    ledger = hlo_scan.scan_hlo(hlo)
    assert ledger.by_kind["reduce-scatter"]["bytes"] == full
    assert ledger.by_kind["all-gather"]["bytes"] == full


def test_reduce_scatter_async_start_and_unknown_groups():
    """Async -start form: the result half of the tuple is the shard — still
    scaled by group size.  Without replica_groups (group size unknown, 0) the
    shard bytes stand unscaled rather than guessing."""
    hlo = """
  %rs = (f32[256,128]{1,0}, f32[32,128]{1,0}) reduce-scatter-start(f32[256,128]{1,0} %g), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, to_apply=%add
  %rs2 = f32[32,128]{1,0} reduce-scatter(f32[256,128]{1,0} %g2), dimensions={0}, to_apply=%add
"""
    ops = hlo_scan.parse_collectives(hlo)
    assert ops[0].bytes == 256 * 128 * 4  # async: result element x group size
    assert ops[1].group_size == 0 and ops[1].bytes == 32 * 128 * 4


def test_iota_replica_groups_parse():
    hlo = "%ar = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups=[4,2]<=[8], to_apply=%add\n"
    ops = hlo_scan.parse_collectives(hlo)
    assert len(ops) == 1 and ops[0].group_size == 2 and not ops[0].is_degenerate


def test_classify_groups_maps_axes():
    mesh = _mesh({"dp": 2, "fsdp": 4})
    ids = {int(d.id): idx for idx, d in np.ndenumerate(mesh.devices)}
    # Groups varying only along dp: same fsdp coordinate, both dp coordinates.
    by_coord = {idx: int(d.id) for idx, d in np.ndenumerate(mesh.devices)}
    dp_groups = [[by_coord[(0, j)], by_coord[(1, j)]] for j in range(4)]
    axes, size = hlo_scan.classify_groups(dp_groups, mesh)
    assert axes == ("dp",) and size == 2
    fsdp_groups = [[by_coord[(i, j)] for j in range(4)] for i in range(2)]
    assert hlo_scan.classify_groups(fsdp_groups, mesh)[0] == ("fsdp",)
    both = [[by_coord[c] for c in np.ndindex(2, 4)]]
    assert hlo_scan.classify_groups(both, mesh)[0] == ("dp", "fsdp")


# ---------------------------------------------------------------------------
# Compiled-program ledgers: dp / fsdp / tp on toy 2x2 CPU meshes
# ---------------------------------------------------------------------------


def test_dp_grad_allreduce_bytes_match_param_bytes():
    """Acceptance invariant: on a dp=2 mesh the gradient all-reduce moves the
    full (replicated) parameter gradient — byte volume == param bytes."""
    mesh = _mesh({"dp": 2})
    W = jax.device_put(jnp.ones((256, 128), jnp.float32), NamedSharding(mesh, P()))
    x = jax.device_put(jnp.ones((8, 256), jnp.float32), NamedSharding(mesh, P("dp")))
    compiled = jax.jit(_sq_loss_step()).lower(W, x).compile()
    class _V5e:  # the ratio needs a device in the peak table; the CPU is not
        device_kind = "TPU v5 lite"

    report = introspect.inspect_compiled(compiled, name="dp_step", mesh=mesh, device=_V5e())

    param_bytes = 256 * 128 * 4
    ar = report.ledger.by_kind.get("all-reduce")
    assert ar is not None, f"no all-reduce in dp=2 ledger: {report.ledger.by_kind}"
    assert abs(ar["bytes"] - param_bytes) / param_bytes < 0.10
    assert report.ledger.by_axis.get("dp") == ar["bytes"]
    # Cost/memory analysis came along with the ledger.
    assert report.flops > 0 and report.bytes_accessed > 0
    assert report.memory.get("argument_bytes", 0) > 0
    assert report.comms_compute_ratio is not None


def test_fsdp_allgather_and_grad_sync():
    """FSDP pattern (params and batch sharded on the same axis): XLA must
    all-gather the weight shards for the matmul (full param bytes) and sync
    gradients back over the same axis."""
    mesh = _mesh({"fsdp": 4})
    W = jax.device_put(jnp.ones((256, 128), jnp.float32), NamedSharding(mesh, P("fsdp")))
    x = jax.device_put(jnp.ones((8, 256), jnp.float32), NamedSharding(mesh, P("fsdp")))
    compiled = jax.jit(_sq_loss_step()).lower(W, x).compile()
    report = introspect.inspect_compiled(compiled, name="fsdp_step", mesh=mesh)

    param_bytes = 256 * 128 * 4
    ag = report.ledger.by_kind.get("all-gather")
    assert ag is not None, f"no all-gather in fsdp ledger: {report.ledger.by_kind}"
    assert abs(ag["bytes"] - param_bytes) / param_bytes < 0.10
    # Gradient sync: reduce-scatter (ZeRO-style) or all-reduce, either way on
    # the fsdp axis.
    assert any(k in report.ledger.by_kind for k in ("reduce-scatter", "all-reduce"))
    assert report.ledger.by_axis.get("fsdp", 0) > param_bytes  # gather + sync


def test_tp_activation_allreduce():
    """Megatron column->row parallel pair: one all-reduce of the layer output
    over tp, byte volume == activation bytes."""
    mesh = _mesh({"tp": 2})
    W1 = jax.device_put(jnp.ones((64, 128), jnp.float32), NamedSharding(mesh, P(None, "tp")))
    W2 = jax.device_put(jnp.ones((128, 64), jnp.float32), NamedSharding(mesh, P("tp", None)))
    x = jax.device_put(jnp.ones((8, 64), jnp.float32), NamedSharding(mesh, P()))

    def fwd(w1, w2, x):
        return jax.nn.relu(x @ w1) @ w2

    compiled = jax.jit(fwd).lower(W1, W2, x).compile()
    report = introspect.inspect_compiled(compiled, name="tp_fwd", mesh=mesh)
    ar = report.ledger.by_kind.get("all-reduce")
    assert ar is not None and ar["count"] == 1
    assert ar["bytes"] == 8 * 64 * 4  # the [8, 64] output
    assert report.ledger.by_axis == {"tp": 8 * 64 * 4}


# ---------------------------------------------------------------------------
# Resharding lint
# ---------------------------------------------------------------------------


def test_lint_flags_missharded_input_and_stays_silent_when_clean():
    mesh = _mesh({"dp": 2})
    W = jax.device_put(jnp.ones((32, 16), jnp.float32), NamedSharding(mesh, P()))
    x_ok = jax.device_put(jnp.ones((8, 32), jnp.float32), NamedSharding(mesh, P("dp")))
    compiled = jax.jit(_sq_loss_step()).lower(W, x_ok).compile()

    # Clean run: the arrays the program was compiled for — silent.
    assert introspect.lint_reshardings(compiled, (W, x_ok), mesh) == []

    # Mis-sharded: batch arrives replicated though the step wants it
    # dp-sharded — every call would pay a resharding copy.
    x_bad = jax.device_put(np.ones((8, 32), np.float32), NamedSharding(mesh, P()))
    findings = introspect.lint_reshardings(compiled, (W, x_bad), mesh)
    assert len(findings) == 1
    assert findings[0].kind == "implicit-reshard"
    assert "resharding copy" in findings[0].message


def test_lint_flags_replicated_by_default_param():
    """A large floating param left fully replicated on a mesh with an active
    fsdp axis is the under-constrained-annotation case; a declared-replicated
    spec suppresses it."""
    mesh = _mesh({"fsdp": 2})
    big = jax.device_put(
        jnp.ones((1024, 512), jnp.float32), NamedSharding(mesh, P())
    )  # 2 MiB >= lint threshold
    x = jax.device_put(jnp.ones((4, 1024), jnp.float32), NamedSharding(mesh, P()))

    def fwd(w, x):
        return x @ w

    compiled = jax.jit(fwd).lower(big, x).compile()
    findings = introspect.lint_reshardings(compiled, (big, x), mesh)
    assert any(f.kind == "replicated-by-default" for f in findings)
    # Declared P() == deliberate replication: lint stays silent for that leaf.
    declared = (P(None, None), None)
    findings = introspect.lint_reshardings(compiled, (big, x), mesh, declared_specs=declared)
    assert not any(f.kind == "replicated-by-default" and f.path == "0" for f in findings)


# ---------------------------------------------------------------------------
# Transparent hook: ACCELERATE_TPU_INTROSPECT on Accelerator-prepared models
# ---------------------------------------------------------------------------


def _prepare_jax_model(accelerator):
    from accelerate_tpu.accelerator import JaxModel

    params = {"w": jnp.ones((8, 8), jnp.float32)}

    def apply_fn(p, x, y):
        pred = x @ p["w"]
        return {"loss": jnp.mean((pred - y) ** 2)}

    return accelerator.prepare(JaxModel(apply_fn, params))


def test_env_unset_captures_nothing(monkeypatch):
    """ACCELERATE_TPU_INTROSPECT unset: the first call must not lower or
    compile anything for analysis — zero overhead."""
    monkeypatch.delenv(introspect.ENV_INTROSPECT, raising=False)
    from accelerate_tpu.accelerator import Accelerator

    model = _prepare_jax_model(Accelerator())
    before = introspect.CAPTURE_COUNT
    x = jnp.ones((8, 8), jnp.float32)
    model(x, jnp.zeros((8, 8), jnp.float32))
    assert introspect.CAPTURE_COUNT == before
    assert model._introspect_pending is False  # checked once, then never again


def test_env_set_captures_ledger_into_telemetry(monkeypatch, tmp_path):
    monkeypatch.setenv(introspect.ENV_INTROSPECT, "1")
    from accelerate_tpu import telemetry
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.utils.dataclasses import ParallelismConfig

    tel = telemetry.enable(dir=str(tmp_path))
    try:
        accelerator = Accelerator(parallelism_config=ParallelismConfig(dp=8))
        model = _prepare_jax_model(accelerator)
        before = introspect.CAPTURE_COUNT
        # Batch-shard the inputs the way the prepared dataloader would — the
        # dp gradient sync only exists when the batch is actually split.
        from accelerate_tpu.parallel.sharding import data_sharding

        sharding = data_sharding(accelerator.mesh)
        x = jax.device_put(np.ones((8, 8), np.float32), sharding)
        y = jax.device_put(np.zeros((8, 8), np.float32), sharding)
        model(x, y)
        assert introspect.CAPTURE_COUNT == before + 1
        path = tel.jsonl_path
        step_timer = telemetry.get_telemetry().step_timer
        records_flops = step_timer.effective_flops_per_step
    finally:
        # The telemetry hub is a process-wide singleton: leave it pristine
        # (registry gauges survive disable() by design — a re-enable resets).
        telemetry.disable()
        telemetry.get_telemetry().registry.reset()
        telemetry.get_telemetry().step_timer.reset()

    records = [json.loads(line) for line in open(path) if line.strip()]
    intro = [r for r in records if r.get("kind") == "introspect"]
    assert len(intro) == 1
    rec = intro[0]
    assert rec["name"] == "model0.fused_step"  # per-model label: no collisions
    assert rec["flops"] > 0
    # On the dp=8 mesh the fused step's gradient sync must show up in the
    # ledger.
    assert rec["comms"]["total_bytes"] > 0
    assert "all-reduce" in rec["comms"]["by_kind"]
    # Measured-cost MFU feed: the analyzed FLOPs reached the step timer.
    assert records_flops == rec["flops"]


def test_eval_first_still_captures_training_step(monkeypatch):
    """An eval warmup pass must not swallow the fused train step's capture —
    the forward and the fused step are inspected independently, and only the
    fused step feeds measured MFU."""
    monkeypatch.setenv(introspect.ENV_INTROSPECT, "1")
    from accelerate_tpu import telemetry
    from accelerate_tpu.accelerator import Accelerator

    tel = telemetry.get_telemetry()
    try:
        model = _prepare_jax_model(Accelerator())
        before = introspect.CAPTURE_COUNT
        x, y = jnp.ones((8, 8), jnp.float32), jnp.zeros((8, 8), jnp.float32)
        model.eval()
        model(x, y)
        assert introspect.CAPTURE_COUNT == before + 1  # forward captured
        assert not tel.step_timer.measured_flops  # eval does not feed MFU
        model.train()
        model(x, y)
        assert introspect.CAPTURE_COUNT == before + 2  # fused step captured too
        assert list(tel.step_timer.measured_flops) == ["model0.fused_step"]
        model(x, y)
        assert introspect.CAPTURE_COUNT == before + 2  # each program once
    finally:
        tel.registry.reset()
        tel.step_timer.reset()


def test_measured_flops_drive_mfu_gauge():
    from accelerate_tpu.telemetry.metrics import MetricsRegistry, StepTimer

    timer = StepTimer(MetricsRegistry())
    assert timer.effective_flops_per_step is None
    timer.record_measured_flops("model.fused_step", 2.0e9)
    timer.record_measured_flops("model.fused_step", 3.0e9)  # latest capture wins
    timer.record_measured_flops("optimizer.step", 1.0e9)
    assert timer.effective_flops_per_step == 4.0e9
    timer.configure(flops_per_step=7.0e9)  # explicit estimate beats measured
    assert timer.effective_flops_per_step == 7.0e9


def test_report_renders_comms_block():
    from accelerate_tpu.telemetry.report import format_report, summarize

    records = [
        {
            "kind": "introspect",
            "name": "model.fused_step",
            "flops": 1.0e9,
            "bytes_accessed": 2.0e8,
            "memory": {"argument_bytes": 1024, "temp_bytes": 2048},
            "comms": {
                "by_kind": {"all-reduce": {"count": 3, "bytes": 4096}},
                "by_axis": {"dp": 4096},
                "total_bytes": 4096,
                "n_ops": 3,
                "degenerate_ops": 0,
            },
            "comms_compute_ratio": 0.25,
            "lint": [
                {"kind": "implicit-reshard", "path": "x", "message": "input 'x' ..."}
            ],
        }
    ]
    text = format_report(summarize(records))
    assert "model.fused_step" in text
    assert "all-reduce" in text and "dp=4.1K B" in text
    assert "comms/compute ratio 0.250" in text
    assert "LINT[implicit-reshard]" in text


def test_while_trip_count_unrolls_executed_bytes():
    """unroll_loops=True multiplies in-loop collective bytes by the while
    trip count (XLA's known_trip_count backend config), including nested
    loops; the static default is unchanged."""
    hlo = """\
%inner_body (p: (s32[], f32[16])) -> (s32[], f32[16]) {
  %cp.inner = f32[16]{0} collective-permute(f32[16]{0} %y), source_target_pairs={{0,1},{1,0}}
}

%outer_body (q: (s32[], f32[16])) -> (s32[], f32[16]) {
  %cp.outer = f32[16]{0} collective-permute(f32[16]{0} %z), source_target_pairs={{0,1},{1,0}}
  %while.inner = (s32[], f32[16]) while((s32[], f32[16]) %t), condition=%inner_cond, body=%inner_body, backend_config={"known_trip_count":{"n":"3"}}
}

ENTRY %main (a: f32[16]) -> f32[16] {
  %cp.top = f32[16]{0} collective-permute(f32[16]{0} %a), source_target_pairs={{0,1},{1,0}}
  %while.outer = (s32[], f32[16]) while((s32[], f32[16]) %u), condition=%outer_cond, body=%outer_body, backend_config={"known_trip_count":{"n":"5"}}
}
"""
    ops = hlo_scan.parse_collectives(hlo, trip_counts=True)
    assert [op.trip_count for op in ops] == [3 * 5, 5, 1]
    # The default (static) parse skips the multiplier pass entirely.
    assert [op.trip_count for op in hlo_scan.parse_collectives(hlo)] == [1, 1, 1]
    static = hlo_scan.scan_hlo(hlo)
    assert static.by_kind["collective-permute"]["bytes"] == 3 * 16 * 4
    unrolled = hlo_scan.scan_hlo(hlo, unroll_loops=True)
    assert unrolled.by_kind["collective-permute"]["bytes"] == (15 + 5 + 1) * 16 * 4


def test_while_trip_count_from_condition_compare():
    """Without known_trip_count, the trip count falls back to the condition
    computation's constant-vs-induction-variable compare (LT -> N)."""
    hlo = """\
%cond (c: (s32[], f32[16])) -> pred[] {
  %limit = s32[] constant(7)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %limit), direction=LT
}

%body (p: (s32[], f32[16])) -> (s32[], f32[16]) {
  %cp = f32[16]{0} collective-permute(f32[16]{0} %y), source_target_pairs={{0,1},{1,0}}
}

ENTRY %main (a: f32[16]) -> f32[16] {
  %while.1 = (s32[], f32[16]) while((s32[], f32[16]) %u), condition=%cond, body=%body
}
"""
    ops = hlo_scan.parse_collectives(hlo, trip_counts=True)
    assert [op.trip_count for op in ops] == [7]
    assert ops[0].executed_bytes == 7 * 16 * 4
