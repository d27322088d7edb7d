"""The LFM2-MoE-class family file, its driver and its two readers: the counts
against hand-worked numbers at the published widths, the driver end to end at a
tiny preset on the CPU, the readers on three ticks of
``lfm2-8b-a1b.agent_closed32`` recorded on a TPU v5e in PR 32
(``fixtures/agent_closed32.tpu_v5e.program.json.gz``: one ``jit_decode_chunk`` and
two ``jit_decode`` dispatches).  The numbers describe the fixture; they are not a
benchmark result."""

import os

import pytest

from conftest import BENCH, CPU_DEVICE, CPU_PEAKS, HERE, real_cfg

FIXTURE = os.path.join(BENCH, "fixtures", "agent_closed32.tpu_v5e.program.json.gz")
AGENT16 = os.path.join(BENCH, "fixtures", "agent_closed16.tpu_v5e.program.json.gz")
TEST_BENCHMARK = os.path.join(HERE, "data", "BENCHMARK.lfm2_moe.json")
CELL = "tiny-lfm2-moe.agent_tiny"
MINE = "lfm2-8b-a1b.agent_closed32"


@pytest.fixture(scope="module")
def fam(run):
    return run.load_module("families", "lfm2_moe")


def test_counts_against_hand_worked_numbers(run, fam):
    cfg = real_cfg(run, "lfm2-8b-a1b")
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64  # q, o; k, v; the two head norms
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048  # W_in, the taps, W_out
    dense_ffn, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    expert_layer = 32 * expert + 2048 * 32 + 32  # + router + expert_bias
    assert (attention, conv, dense_ffn, expert, expert_layer) == (10_485_888, 16_783_360, 44_040_192, 11_010_048, 352_387_104)
    cut = 12 * expert_layer + 3 * attention + 11 * conv + 2 * dense_ffn + 14 * 2 * 2048 + 2048 + 65_536 * 2048
    assert fam.num_params(cfg) == cut == 4_667_077_376 == cfg["parameters"]
    whole = dict(cfg, **cfg["published"])
    assert fam.num_params(whole) == 22 * expert_layer + 6 * attention + 18 * conv + 2 * dense_ffn + 24 * 2 * 2048 + 2048 + 65_536 * 2048
    assert fam.num_params(whole) == 8_339_930_560  # the published 8.3 B, the head tied
    assert fam.expert_params(cfg) == expert and fam.expert_bytes(cfg) == 22_020_096
    assert fam.conv_bytes(cfg) == 11 * conv * 2 == 369_233_920
    # what one token multiplies: four experts and the router a layer, the operators' projections, the dense SwiGLUs, the
    # head (the embedding out); no norm, no tap, no bias, not the embedding in
    active = 12 * (4 * expert + 2048 * 32) + 3 * (attention - 128) + 11 * (conv - 3 * 2048) + 2 * dense_ffn + 2048 * 65_536
    assert fam.matmul_params(cfg) == active == 967_573_504
    assert fam.attn_flops(cfg, 10) == 10 * 3 * 32 * 2 * (64 + 64)
    assert fam.serve_flops(cfg, 3, 10) == 2 * active * 3 + fam.attn_flops(cfg, 10)
    assert fam.cache_row_bytes(cfg) == 6_144 and fam.state_slot_bytes(cfg) == 90_112
    pool_bytes = 8192 * 16 * fam.cache_row_bytes(cfg) + 32 * fam.state_slot_bytes(cfg)
    assert (2 * fam.num_params(cfg) + pool_bytes) / 16e9 == pytest.approx(0.634, abs=0.001)


def test_configuration_file_holds_the_published_keys(run):
    cfg = real_cfg(run, "lfm2-8b-a1b")
    types = ["full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)]
    published = {  # the catalog's `config` of LFM2-8B-A1B, every key
        "architectures": ["Lfm2MoeForCausalLM"], "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": types, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
        "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536,
    }
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert cfg["num_hidden_layers"] == 14 and cfg["layer_types"] == types[:14] and cfg["published"] == {
        "num_hidden_layers": 24, "layer_types": types}
    assert cfg["serve"] == {"block_size": 16, "num_blocks": 8192, "max_slots": 32, "max_blocks_per_seq": 128}
    assert cfg["assumed"]["head_dim"] == 64 and cfg["assumed"]["tie_word_embeddings"] is True and cfg["program"] == {}
    bench = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["source"] == cfg["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"


def test_traffic_file_is_the_issues(run):
    traffic = run.load_json(os.path.join(BENCH, "traffic", "agent_closed32.json"))
    assert {k: traffic[k] for k in ("driver", "callers", "think_time_s", "deck", "deck_pairing_seed", "deck_order_seed",
                                    "preroll_ticks", "check_requests", "trace_seconds")} == {
        "driver": "serve_closed_family", "callers": 32, "think_time_s": 0, "deck": 128, "deck_pairing_seed": 32,
        "deck_order_seed": 32, "preroll_ticks": 300, "check_requests": 8, "trace_seconds": 4}
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.8, "min": 32, "max": 1024}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64, "max": 768}


def test_family_file_imports_nothing_of_the_program(fam):
    import ast

    tree = ast.parse(open(fam.__file__).read())
    top_level = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not any("accelerate_tpu" in ast.dump(n) for n in top_level)
    lazy = [n for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) and (n.module or "").startswith("accelerate_tpu")]
    assert len(lazy) == 1  # program_module(): the one lazy import


def tiny_cell(run):
    cell = run.load_cell(CELL, TEST_BENCHMARK)
    cell["peaks"] = dict(cell["peaks"], cpu=CPU_PEAKS)
    return cell


def test_driver_end_to_end_at_the_tiny_preset(run):
    cell = tiny_cell(run)
    assert cell["traffic"]["driver"] == "serve_closed_family"
    result = run.run_cell(cell, 2**31 + 11, 1.5, False, CPU_DEVICE)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == {"served_gap_mean", "served_gap_share"}


def test_every_control_fails_the_tiny_cells_limits(run, fam):
    _, _, driver = run.build_driver(tiny_cell(run), 7, None)
    driver.setup()
    driver.window(1.0, run.Probe(False, 1.0, 0, ""))
    driver.release()
    checked = driver.check(control=True)
    limits = driver.ctx["limits"]
    assert all(c["value"] <= c["limit"] for c in checked["checks"].values()), checked["checks"]
    assert set(checked["control"]) == set(fam.CONTROLS) and len(fam.CONTROLS) == 8
    for name, read in checked["control"].items():
        assert any(read[k] > limits[k] for k in limits), (name, read)


def test_traced_run_reads_the_real_cells_readers(run):
    cell = tiny_cell(run)
    real = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    cell["per_layer"] = [m for m in real["per_layer"] if MINE in m["workloads"]]
    names = {m["name"] for m in cell["per_layer"]}
    assert len(names) == 12 and {"serve.conv_share", "serve.conv_roofline", "serve.moe_share", "serve.expert_roofline", "serve.mfu"} <= names
    assert not {"serve.decode_roofline", "serve.latent_attn_share"} & names
    # ttft_p90_ms spreads by 8% over six runs of this cell against a half-bound of 1.75% (PERF.md section 2): the cell reports the
    # other three end-to-end metrics, and none of the per-layer metrics that move it
    assert {m["name"] for m in real["end_to_end"] if MINE in m.get("workloads", [MINE])} == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert not {"serve.prefill_tick_share", "serve.compiles_in_window"} & names
    result = run.run_cell(cell, 5, 1.5, True, CPU_DEVICE)
    assert {"serve.mfu", "serve.decode_fill", "serve.tick_ms", "serve.device_idle"} <= set(result["metrics"])
    assert result["facts"]["compiles_in_window"] == 0


def test_snapshot_carries_the_expert_counters_and_one_dispatch_a_tick(run):
    _, _, driver = run.build_driver(tiny_cell(run), 3, None)
    driver.setup()
    before = driver.snapshot()
    driver.loop(30.0, None, ticks=5)
    after = driver.snapshot()
    stats = driver.engine.stats()
    assert after["ticks"] - before["ticks"] == 5 and after["moe_rows"] > before["moe_rows"]
    # what serve.conv_roofline counts as dispatches: the closed loop never ticks idle, and a tick is one dispatch
    assert stats["prefill_dispatches"] + stats["decode_dispatches"] - stats["mixed_dispatches"] == stats["ticks"]
    assert stats["state_bytes"] == 4 * 4 * 2 * 64 * 4 and stats["state_resets"] > 0 and stats["prefix_hits"] == 0
    driver.release()


def fixture_run(run, fam, ticks):
    pt = run.load_module("", "program_trace")
    window = pt.traced_window(FIXTURE)
    busy = window[1] - window[0] - sum(b - a for a, b in pt.idle_intervals(FIXTURE))
    return {
        "traced": {"raw_path": FIXTURE, "trace": {"busy_s": busy, "window_s": window[1] - window[0]}, "counters": {"ticks": ticks}},
        "family": fam, "cfg": real_cfg(run, "lfm2-8b-a1b"), "peak_bytes": 819e9,
    }


def test_two_readers_on_the_recorded_ticks(run, fam):
    pt = run.load_module("", "program_trace")
    ops = pt.load(FIXTURE)["ops"]
    assert {op[1] for op in ops} == {"jit_decode_chunk", "jit_decode"} and pt.ticks_in_window(FIXTURE) == 3
    r = fixture_run(run, fam, ticks=3)
    busy = r["traced"]["trace"]["busy_s"]
    parts_of = lambda op: [p.split("(")[-1].rstrip(")") for p in op[6].split("/")]
    conv = sum(op[5] for op in ops if "conv" in parts_of(op))
    state = sum(op[5] for op in ops if "state_pool" in parts_of(op))
    by_part = {part: sum(op[5] for op in ops if part in parts_of(op)) for part in ("conv.in", "conv.mix", "conv.out")}
    assert all(v > 0 for v in by_part.values()) and state > 0
    assert by_part["conv.in"] > by_part["conv.out"] > by_part["conv.mix"]  # 25 MB, 8 MB, and three taps of a few rows
    assert sum(by_part.values()) <= conv * (1 + 1e-9)
    share = run.load_module("readers", "serve.conv_share").read(r)
    either = sum(op[5] for op in ops if {"conv", "state_pool"} & set(parts_of(op)))  # the state is read inside the operator, written outside
    assert conv < either < conv + state
    assert share == pytest.approx(100 * either / busy, rel=1e-6) and 1 < share < 10
    roofline = run.load_module("readers", "serve.conv_roofline").read(r)
    assert roofline == pytest.approx(100 * 3 * 369_233_920 / 819e9 / conv, rel=1e-6) and 30 < roofline < 100
    # the accepted by-scope table files the operator's time under `attn` (its place in the block), the state's under `kv_pool.*`
    by_scope = pt.scope_seconds(FIXTURE, "jit_decode")
    assert by_scope["attn"][0] >= 0.9 * by_part["conv.in"] and pt.NO_SCOPE in by_scope
    assert run.load_module("readers", "serve.layer_loop_share").read(r) < 10
    # and the expert layer's readers read the same ticks
    assert 60 < run.load_module("readers", "serve.moe_share").read(r) < 95


def test_two_readers_find_nothing_in_a_program_without_the_operator(run, fam):
    pt = run.load_module("", "program_trace")
    window = pt.traced_window(AGENT16)
    other = {"traced": {"raw_path": AGENT16, "trace": {"busy_s": 0.07, "window_s": window[1] - window[0]}, "counters": {"ticks": 3}},
             "family": run.load_module("families", "deepseek_v3"), "cfg": real_cfg(run, "kanana-2-30b-a3b"), "peak_bytes": 819e9}
    for name in ("serve.conv_share", "serve.conv_roofline"):
        reader = run.load_module("readers", name)
        assert reader.read(other) is None
        assert reader.read({"traced": {"raw_path": None, "trace": {}}, "family": fam, "cfg": {}, "peak_bytes": 819e9}) is None
    # the parent's program under this PR's benchmark files: the family file is there, the scope is not
    assert run.load_module("readers", "serve.conv_roofline").read(dict(other, family=fam, cfg=real_cfg(run, "lfm2-8b-a1b"))) is None
