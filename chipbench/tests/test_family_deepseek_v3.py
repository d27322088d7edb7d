"""The DeepseekV3-class family file, its driver and its three readers: the
counters against hand-worked numbers at the published widths, the driver end to
end at the tiny preset on the CPU, the readers on three ticks of
``kanana-2-30b-a3b.agent_closed16`` recorded on a TPU v5e in PR 28
(``fixtures/agent_closed16.tpu_v5e.program.json.gz``).  The numbers describe the
fixture; they are not a benchmark result."""

import os

import pytest

from conftest import BENCH, CPU_DEVICE, CPU_PEAKS, HERE, real_cfg

FIXTURE = os.path.join(BENCH, "fixtures", "agent_closed16.tpu_v5e.program.json.gz")
CHAT = os.path.join(BENCH, "fixtures", "chat_closed16.tpu_v5e.program.json.gz")
TEST_BENCHMARK = os.path.join(HERE, "data", "BENCHMARK.deepseek_v3.json")
CELL = "tiny-deepseek-v3.agent_tiny"


@pytest.fixture(scope="module")
def fam(run):
    return run.load_module("families", "deepseek_v3")


def test_counters_against_hand_worked_numbers(run, fam):
    cfg = real_cfg(run, "kanana-2-30b-a3b")
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048  # 26,345,472 multiplied by a token
    assert attention == 26_345_472
    dense_layer = attention + 512 + 3 * 2048 * 6144 + 2 * 2048  # + kv norm + the two norms
    expert_layer = attention + 512 + 2 * 2048 + 2048 * 128 + 128 + 128 * 3 * 2048 * 768 + 3 * 2048 * 1536
    assert (dense_layer, expert_layer) == (64_098_816, 640_029_312)
    assert fam.num_params(cfg) == dense_layer + 7 * expert_layer + 2 * 128_256 * 2048 + 2048 == 5_069_642_624 == cfg["parameters"]
    assert fam.expert_params(cfg) == 4_718_592 and fam.expert_bytes(cfg) == 9_437_184
    # what one token multiplies: 6 of 128 experts, the shared ones, the router, the head; no norm, no bias, no embedding row
    active = (attention + 3 * 2048 * 6144) + 7 * (attention + 2048 * 128 + 6 * 4_718_592 + 3 * 2048 * 1536) + 2048 * 128_256
    assert fam.matmul_params(cfg) == active == 777_256_960
    assert fam.latent_row_bytes(cfg) == 8 * 576 * 2
    assert fam.attn_flops(cfg, 10) == 10 * 8 * 32 * 2 * (192 + 128)
    assert fam.serve_flops(cfg, 3, 10) == 2 * active * 3 + fam.attn_flops(cfg, 10)
    pool_bytes = 8192 * 16 * fam.latent_row_bytes(cfg)
    assert (2 * fam.num_params(cfg) + pool_bytes) / 16e9 == pytest.approx(0.709, abs=0.001)


def test_configuration_file_holds_the_published_keys(run):
    cfg = real_cfg(run, "kanana-2-30b-a3b")
    published = {  # the catalog's `config` of kanana-2-30b-a3b-instruct-2601, every key
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512, "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
    }
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 8 and cfg["published"]["num_hidden_layers"] == 48
    assert cfg["serve"] == {"block_size": 16, "num_blocks": 8192, "max_slots": 16, "max_blocks_per_seq": 128}


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


def test_traced_run_reads_the_real_cells_readers(run):
    cell = tiny_cell(run)
    real = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    mine = "kanana-2-30b-a3b.agent_closed16"
    cell["per_layer"] = [m for m in real["per_layer"] if mine in m["workloads"]]
    assert len(cell["per_layer"]) == 13
    result = run.run_cell(cell, 5, 1.5, True, CPU_DEVICE)
    assert {"serve.mfu", "serve.decode_fill", "serve.tick_ms", "serve.prefill_tick_share", "serve.compiles_in_window",
            "serve.device_idle"} <= set(result["metrics"])
    assert result["metrics"]["serve.compiles_in_window"]["value"] == 0.0


def test_snapshot_carries_the_expert_counters(run):
    _, _, driver = run.build_driver(tiny_cell(run), 3, None)
    driver.setup()
    before = driver.snapshot()
    driver.loop(30.0, None, ticks=5)
    after = driver.snapshot()
    ticks = after["ticks"] - before["ticks"]
    dispatches = after["prefill_dispatches"] - before["prefill_dispatches"] + after["decode_dispatches"] - before["decode_dispatches"]
    assert ticks == 5 and after["moe_rows"] > before["moe_rows"]
    # two expert layers, at least two experts a layer a dispatch (top-2), at most all eight
    assert 4 * dispatches <= after["moe_experts_hit"] - before["moe_experts_hit"] <= 16 * dispatches
    driver.release()


def fixture_run(run, fam, hit):
    pt = run.load_module("", "program_trace")
    window = pt.traced_window(FIXTURE)
    busy = window[1] - window[0] - sum(b - a for a, b in pt.idle_intervals(FIXTURE))
    return {
        "traced": {"raw_path": FIXTURE, "trace": {"busy_s": busy, "window_s": window[1] - window[0]},
                   "counters": {"moe_experts_hit": hit}},
        "family": fam, "cfg": real_cfg(run, "kanana-2-30b-a3b"), "peak_bytes": 819e9,
    }


def test_three_readers_on_the_recorded_ticks(run, fam):
    pt = run.load_module("", "program_trace")
    parts = run.load_module("", "scope_parts")
    ops = pt.load(FIXTURE)["ops"]
    assert {op[1] for op in ops} == {"jit_prefill", "jit_decode"}
    r = fixture_run(run, fam, hit=1500)
    busy = r["traced"]["trace"]["busy_s"]
    # by hand: the grouped product's kernels carry no scope path, only their name
    kernels = sum(op[5] for op in ops if op[6].startswith("ragged-dot"))
    scoped = sum(op[5] for op in ops if "/moe.experts/" in op[6] + "/")
    moe = sum(op[5] for op in ops if "/moe/" in op[6] + "/")
    assert kernels > 5 * scoped > 0
    programs = ("jit_prefill", "jit_decode")
    assert parts.self_seconds(r, programs, lambda s: "moe.experts" in s) == pytest.approx(kernels + scoped, rel=1e-9)
    assert parts.self_seconds(r, programs, lambda s: "moe" in s) == pytest.approx(kernels + moe, rel=1e-9)
    share = run.load_module("readers", "serve.moe_share").read(r)
    assert share == pytest.approx(100 * (kernels + moe) / busy) and 55 < share < 75
    roofline = run.load_module("readers", "serve.expert_roofline").read(r)
    assert roofline == pytest.approx(100 * 1500 * 9_437_184 / 819e9 / (kernels + scoped)) and 0 < roofline < 100
    attn = sum(op[5] for op in ops if any(p.split("(")[-1].rstrip(")").split(".")[0] in ("attn", "kv_pool") for p in op[6].split("/")))
    latent = run.load_module("readers", "serve.latent_attn_share").read(r)
    assert latent == pytest.approx(100 * attn / busy, rel=1e-6) and 10 < latent < 30
    # the accepted readers see the expert layer's scoped operations under `mlp`, not under the bare layer loop
    loop = run.load_module("readers", "serve.layer_loop_share").read(r)
    assert loop < 10


def test_three_readers_find_nothing_in_a_program_without_experts(run, fam):
    pt = run.load_module("", "program_trace")
    window = pt.traced_window(CHAT)
    chat = {"traced": {"raw_path": CHAT, "trace": {"busy_s": 0.19, "window_s": window[1] - window[0]}, "counters": {}},
            "family": run.load_module("families", "qwen2"), "cfg": {}, "peak_bytes": 819e9}
    for name in ("serve.moe_share", "serve.expert_roofline", "serve.latent_attn_share"):
        reader = run.load_module("readers", name)
        assert reader.read(chat) is None
        assert reader.read({"traced": {"raw_path": None, "trace": {}}, "family": fam, "cfg": {}, "peak_bytes": 819e9}) is None
