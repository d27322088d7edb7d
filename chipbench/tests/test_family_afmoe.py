"""The AFMoE-class family file, its driver and its two readers: the counts
against hand-worked numbers at the published widths and the cell's share, the
configuration and traffic files against the issue, the driver end to end at a
tiny preset on the CPU (``correct`` true, and false under each of the nine
controls), the counters against hand-worked numbers.  Nothing timed here is a
device metric."""

import os

import pytest

from conftest import BENCH, CPU_DEVICE, CPU_PEAKS, HERE, real_cfg

TEST_BENCHMARK = os.path.join(HERE, "data", "BENCHMARK.afmoe.json")
CELL = "tiny-afmoe.longform_tiny"
MINE = "trinity-large-preview.longform_closed16"


@pytest.fixture(scope="module")
def fam(run):
    return run.load_module("families", "afmoe")


def test_counts_against_hand_worked_numbers(run, fam):
    cfg = real_cfg(run, "trinity-large-preview")
    attention = 3 * 3072 * 6144 + 2 * 3072 * 1024 + 2 * 128  # q, gate, o; k, v; the two head norms
    norms = 4 * 3072
    dense_layer = attention + norms + 3 * 3072 * 12288
    expert = 3 * 3072 * 3072
    expert_layer = attention + norms + 3072 * 256 + 256 + expert + 32 * expert  # router, bias, the shared expert, 32 held
    assert (dense_layer, expert, expert_layer) == (176_173_312, 28_311_552, 997_995_008)
    assert expert_layer - 32 * expert == 92_025_344
    rest = 2 * 25_024 * 3072 + 3072
    assert rest == 153_750_528
    assert fam.num_params(cfg) == dense_layer + 4 * expert_layer + rest == 4_321_903_872 == cfg["parameters"]
    assert fam.expert_params(cfg) == expert and fam.expert_bytes(cfg) == 56_623_104
    # the published model: 60 layers, 6 dense, every expert of a layer, the whole vocabulary
    whole = dict(cfg, **cfg["published"])
    whole.pop("router_experts")
    assert 395e9 < fam.num_params(whole) < 405e9  # "400B"
    # what one token multiplies here: 4 x 32 / 256 = half an expert a layer on average, the router's 256 columns, the
    # shared expert, the five projections, the dense SwiGLU, the head over the slice
    active = 5 * (attention - 256) + 3 * 3072 * 12288 + 4 * (3072 * 256 + expert + 0.5 * expert) + 3072 * 25_024
    assert fam.matmul_params(cfg) == active == 677_707_776
    # a full layer attends over every pair, the four sliding layers over 4,096 keys a token at most
    assert fam.attn_flops(cfg, 10, 100) == 4 * 48 * 128 * (1 * 100 + 4 * 100)
    assert fam.attn_flops(cfg, 10, 10**6) == 4 * 48 * 128 * (10**6 + 4 * 10 * 4096)
    assert fam.serve_flops(cfg, 3, 10) == 2 * active * 3 + fam.attn_flops(cfg, 3, 10)
    assert fam.cache_row_bytes(cfg) == {"full": 4096, "window": 16_384}
    # the pool as the cell configures it: 16,384 full blocks, 16 rings of 259 and a null block on the window leaves
    full, window = 16_384 * 16 * 4096, (16 * 259 + 1) * 16 * 16_384
    assert (full, window) == (1_073_741_824, 1_086_586_880)
    assert (2 * fam.num_params(cfg) + full + window) / 16e9 == pytest.approx(0.675, abs=0.001)
    # every row held by every layer would not fit beside the weights
    assert 5 * 16 * 16_384 * 4096 == 5_368_709_120


def test_configuration_file_holds_the_published_keys(run):
    import json

    cfg = real_cfg(run, "trinity-large-preview")
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if os.path.isfile(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        published = next(r for r in rows if r["name"] == "Trinity-Large-Preview")["config"]
        changed = {k for k, v in published.items() if cfg.get(k, "left out") != v}
        assert changed == set(cfg["reduced"])
        assert {k: published[k] for k in cfg["reduced"]} == cfg["published"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 32, 25_024)
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"] and cfg["router_experts"] == 256
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (3072, 48, 8, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) == (12288, 3072, 4)
    assert (cfg["sliding_window"], cfg["route_scale"], cfg["rms_norm_eps"]) == (4096, 2.448, 1e-05)
    assert cfg["published"]["num_experts"] == 256 and cfg["published"]["vocab_size"] == 200_192 and 8 * 25_024 == 200_192
    assert "eight chips share each layer" in cfg["deployment"] and cfg["assumed"]["experts_held_first"] == 0
    for point in ("gate_width", "qk_norms", "rope", "sandwich_norms", "router", "embedding_scale", "window"):
        assert point in cfg["assumed"]
    assert cfg["serve"] == {"block_size": 16, "num_blocks": 16384, "max_slots": 16, "max_blocks_per_seq": 1024}
    assert cfg["program"] == {}
    bench = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-large-preview")
    assert entry["source"] == cfg["source"] == "https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json"
    assert entry["reduced"] == cfg["reduced"]


def test_traffic_file_is_the_issues(run):
    traffic = run.load_json(os.path.join(BENCH, "traffic", "longform_closed16.json"))
    assert {k: traffic[k] for k in ("driver", "callers", "think_time_s", "deck", "deck_pairing_seed", "deck_order_seed",
                                    "preroll_ticks", "check_requests", "trace_seconds")} == {
        "driver": "serve_closed_window", "callers": 16, "think_time_s": 0, "deck": 64, "deck_pairing_seed": 38,
        "deck_order_seed": 38, "preroll_ticks": 3000, "check_requests": 8, "trace_seconds": 4}
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024, "max": 12288}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 2560, "sigma": 0.4, "min": 1024, "max": 4096}
    cards = run.load_module("drivers", "serve_closed").deck(traffic)
    assert max(p + n for p, n in cards) <= 16_384  # every card fits a lane's table of 1,024 blocks


def test_the_cells_metrics_are_the_issues(run):
    real = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    names = {m["name"] for m in real["per_layer"] if MINE in m["workloads"]}
    assert names == {
        "serve.mfu", "serve.device_idle", "serve.decode_fill", "serve.tick_ms", "serve.kv_pool_share", "serve.layer_loop_share",
        "serve.moe_share", "serve.idle_build_ms", "serve.idle_readback_ms", "serve.host_busy_share",
        "serve.row_fill", "serve.width_forced_share", "serve.pipelined_share", "serve.window_attn_share", "serve.window_read_share"}
    # serve.expert_roofline is not reported: a traced run of this cell lost a second of device events and read 105.7,
    # past what a share of a roofline may read; the value by hand is in PERF.md section 5, the reason in section 7
    assert MINE not in next(m for m in real["per_layer"] if m["name"] == "serve.expert_roofline")["workloads"]
    mine = {m["name"]: m for m in real["per_layer"] if m["name"] in ("serve.window_attn_share", "serve.window_read_share")}
    assert all(m["workloads"] == [MINE] and m["moves"] == "serve_tokens_per_s" and m["unit"] == "%" for m in mine.values())
    assert mine["serve.window_attn_share"]["source"] == "device_trace" and mine["serve.window_read_share"]["source"] == "program_counter"
    cell = next(w for w in real["workloads"] if w["name"] == MINE)
    assert cell["chips"] == 1 and cell["config"] == "trinity-large-preview" and cell["traffic"] == "longform_closed16"


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
    assert cell["traffic"]["driver"] == "serve_closed_window"
    result = run.run_cell(cell, 2**31 + 11, 1.5, False, CPU_DEVICE)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
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
    assert set(checked["control"]) == set(fam.CONTROLS) and len(fam.CONTROLS) == 9
    for name, read in checked["control"].items():
        assert any(read[k] > limits[k] for k in limits), (name, read)


def test_traced_run_reads_the_real_cells_readers(run):
    cell = tiny_cell(run)
    real = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    cell["per_layer"] = [m for m in real["per_layer"] if MINE in m["workloads"]]
    result = run.run_cell(cell, 5, 1.5, True, CPU_DEVICE)
    assert {"serve.mfu", "serve.decode_fill", "serve.tick_ms", "serve.device_idle", "serve.window_read_share"} <= set(result["metrics"])
    assert 0 < result["metrics"]["serve.window_read_share"]["value"] < 100  # contexts run past the window of 12
    assert result["facts"]["compiles_in_window"] == 0


def test_window_readers_on_hand_made_runs(run):
    read_share = run.load_module("readers", "serve.window_read_share").read
    assert read_share({"traced": {"counters": {"window_rows_read": 600, "context_rows": 1000}}}) == 60.0
    assert read_share({"traced": {"counters": {"moe_rows": 5}}}) is None  # a program without window leaves: nothing to read
    assert read_share({"traced": {}}) is None
    attn_share = run.load_module("readers", "serve.window_attn_share").read
    assert attn_share({"traced": {}}) is None  # no trace
    fixture = os.path.join(BENCH, "fixtures", "agent_closed16.tpu_v5e.program.json.gz")
    assert attn_share({"traced": {"raw_path": fixture, "trace": {"busy_s": 1.0}}}) is None  # the kanana cell: no such scope


def test_snapshot_carries_the_window_counters_and_blocks_by_kind(run):
    _, _, driver = run.build_driver(tiny_cell(run), 3, None)
    driver.setup()
    before = driver.snapshot()
    driver.loop(30.0, None, ticks=8)
    after = driver.snapshot()
    stats = driver.engine.stats()
    assert after["ticks"] - before["ticks"] == 8 and after["moe_rows"] > before["moe_rows"]
    assert after["context_rows"] - before["context_rows"] >= after["window_rows_read"] - before["window_rows_read"] > 0
    # four expert layers route every row of a dispatch to two of eight experts; four of the eight are held here
    routed = after["moe_pairs_routed"] - before["moe_pairs_routed"]
    assert routed % (4 * 2) == 0 and 0 < after["moe_rows"] - before["moe_rows"] < routed
    assert stats["window_blocks_in_use"] <= 4 * stats["window_ring_blocks"] and stats["full_blocks_in_use"] >= stats["window_blocks_in_use"]
    assert stats["prefill_dispatches"] + stats["decode_dispatches"] - stats["mixed_dispatches"] == stats["ticks"]
    assert stats["window_ring_blocks"] == 6 and stats["prefix_hits"] == 0 and "window leaves" in stats["prefix_cache_off"]
    driver.release()
