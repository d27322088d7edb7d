"""The Keye-VL-2.0-class family file, its driver and its three readers: the
counts against hand-worked numbers at the published widths, the configuration
and traffic files against the model and the mix they stand for, the driver end
to end at a tiny preset on the CPU (``correct`` true, and false under each
control), the counters against hand-worked numbers.  Nothing timed here is a
device metric."""

import os

import pytest

from conftest import BENCH, CPU_DEVICE, CPU_PEAKS, HERE, real_cfg

TEST_BENCHMARK = os.path.join(HERE, "data", "BENCHMARK.keye_vl2.json")
CELL = "tiny-keye-vl2.longctx_tiny"
MINE = "keye-vl-2.0-30b-a3b.longctx_closed16"
# The language model's keys of the published config.json (the source named in the configuration file), as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"}, "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def fam(run):
    return run.load_module("families", "keye_vl2")


def test_counts_against_hand_worked_numbers(run, fam):
    cfg = real_cfg(run, "keye-vl-2.0-30b-a3b")
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2 * 2048  # q, o; k, v; the two head norms; two RMSNorms
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16 + 2 * 64  # queries, the key, the head weights; the key's LayerNorm
    expert = 3 * 2048 * 768
    layer = attention + indexer + 2048 * 128 + 128 * expert
    assert (attention, indexer, expert, layer) == (18_878_720, 2_261_120, 4_718_592, 625_381_760)
    rest = 2 * 151_936 * 2048 + 2048
    assert rest == 622_331_904
    assert fam.num_params(cfg) == 4 * layer + rest == 3_123_858_944 == cfg["parameters"]
    assert fam.expert_params(cfg) == expert and fam.expert_bytes(cfg) == 9_437_184
    whole = dict(cfg, **cfg["published"])
    assert 29e9 < fam.num_params(whole) < 31e9  # "30B-A3B"
    # what one token multiplies: the four projections, the indexer's three, the router, 8 experts, the head
    active = 4 * (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * (1024 + 64 + 16) + 2048 * 128 + 8 * expert) + 2048 * 151_936
    assert fam.matmul_params(cfg) == active
    # the indexer scores every causal pair, 16 heads of 64; attention runs over 2,048 keys a token at most
    assert fam.index_flops(cfg, 100) == 2 * 4 * 16 * 64 * 100
    assert fam.attn_flops(cfg, 10, 100) == 4 * 4 * 32 * 128 * 100
    assert fam.attn_flops(cfg, 10, 10**6) == 4 * 4 * 32 * 128 * 10 * 2048
    assert fam.serve_flops(cfg, 3, 10) == 2 * active * 3 + fam.index_flops(cfg, 10) + fam.attn_flops(cfg, 3, 10)
    assert (fam.kv_row_bytes(cfg), fam.index_row_bytes(cfg)) == (2048, 128)
    # the pool as the cell configures it: 32,768 blocks of 16 rows, 4 layers of K/V and 2 rows of index keys (two
    # layers a row of 128)
    kv, index = 4 * 32_768 * 16 * 2048, 2 * 32_768 * 16 * 256
    assert (kv, index) == (4_294_967_296, 268_435_456)
    assert (2 * fam.num_params(cfg) + kv + index) / 16e9 == pytest.approx(0.676, abs=0.001)


def test_least_bytes_of_the_sparse_read_against_hand_worked_numbers(fam, run):
    cfg = real_cfg(run, "keye-vl-2.0-30b-a3b")
    # one decoding lane at a context of 10,000 rows and a chunk at 1,000, four layers: every index key of each
    # context, 2,048 K/V rows of the lane's selection and all 1,000 of the chunk's
    counters = {"index_rows_scored": 4 * (10_000 + 1_000), "sparse_lane_rows": 4 * (2048 + 1_000)}
    assert fam.sparse_least_bytes(cfg, counters) == 4 * (11_000 * 128 + 3_048 * 2048)
    assert fam.sparse_least_bytes(cfg, {"moe_rows": 5}) is None


def test_configuration_file_holds_the_published_keys(run):
    cfg = real_cfg(run, "keye-vl-2.0-30b-a3b")
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k, "left out") != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert {k: PUBLISHED[k] for k in cfg["reduced"]} == cfg["published"]
    assert cfg["num_hidden_layers"] == 4 and cfg["published"] == {"num_hidden_layers": 48}
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["moe_intermediate_size"], cfg["num_experts"], cfg["num_experts_per_tok"], cfg["vocab_size"]) == (768, 128, 8, 151_936)
    assert cfg["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                                "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert cfg["rope_theta"] == 10_000_000 and cfg["torch_dtype"] == "bfloat16"
    for point in ("index_query", "index_rope_dim", "index_norm", "index_precision", "chunk_sizes", "qk_norm", "mrope", "weights"):
        assert point in cfg["assumed"]
    assert "twelve pipeline stages" in cfg["deployment"]
    assert cfg["serve"] == {"block_size": 16, "num_blocks": 32768, "max_slots": 16, "max_blocks_per_seq": 2048}
    assert cfg["program"] == {}
    bench = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["source"] == cfg["source"] == "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    assert entry["reduced"] == cfg["reduced"]


def test_traffic_file_is_the_cells_mix(run):
    traffic = run.load_json(os.path.join(BENCH, "traffic", "longctx_closed16.json"))
    assert {k: traffic[k] for k in ("driver", "callers", "think_time_s", "deck", "deck_pairing_seed", "deck_order_seed",
                                    "check_requests", "trace_seconds")} == {
        "driver": "serve_closed_sparse", "callers": 16, "think_time_s": 0, "deck": 64, "deck_pairing_seed": 40,
        "deck_order_seed": 40, "check_requests": 8, "trace_seconds": 4}
    assert 4000 <= traffic["preroll_ticks"] <= 5000
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 12288, "sigma": 0.4, "min": 6144, "max": 24576}
    assert traffic["new_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.3, "min": 2048, "max": 6144}
    cards = run.load_module("drivers", "serve_closed").deck(traffic)
    assert max(p + n for p, n in cards) <= 2048 * 16  # every card fits a lane's table of 2,048 blocks
    assert min(p for p, _ in cards) > 2048  # every decoding query lies past the top-k
    assert 16 * max(p + n for p, n in cards) <= 32_767 * 16  # sixteen of the longest fit the pool: no preemption


def test_the_cells_metrics_are_the_ones_it_reports(run):
    real = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    names = {m["name"] for m in real["per_layer"] if MINE in m["workloads"]}
    assert names == {
        "serve.mfu", "serve.device_idle", "serve.decode_fill", "serve.tick_ms", "serve.kv_pool_share", "serve.layer_loop_share",
        "serve.moe_share", "serve.expert_roofline", "serve.idle_build_ms", "serve.idle_readback_ms", "serve.host_busy_share",
        "serve.row_fill", "serve.width_forced_share", "serve.pipelined_share", "serve.sparse_attn_share",
        "serve.sparse_read_share", "serve.sparse_roofline"}
    assert {m["name"] for m in real["end_to_end"] if MINE in m.get("workloads", [MINE])} == {
        "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    mine = {m["name"]: m for m in real["per_layer"] if m["name"].startswith("serve.sparse_")}
    assert all(m["workloads"] == [MINE] and m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
               and m["layer"] == "model step" for m in mine.values())
    assert [mine[n]["source"] for n in ("serve.sparse_attn_share", "serve.sparse_read_share", "serve.sparse_roofline")] == [
        "device_trace", "program_counter", "device_trace"]
    cell = next(w for w in real["workloads"] if w["name"] == MINE)
    assert cell["chips"] == 1 and cell["config"] == "keye-vl-2.0-30b-a3b" and cell["traffic"] == "longctx_closed16"


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
    assert cell["traffic"]["driver"] == "serve_closed_sparse"
    result = run.run_cell(cell, 2**31 + 11, 1.5, False, CPU_DEVICE)
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == {"served_gap_mean", "served_gap_share", "served_gap_share_wide"}


def test_every_control_fails_the_tiny_cells_limits(run, fam):
    _, _, driver = run.build_driver(tiny_cell(run), 7, None)
    driver.setup()
    driver.window(1.0, run.Probe(False, 1.0, 0, ""))
    driver.release()
    checked = driver.check(control=True)
    limits = driver.ctx["limits"]
    assert all(c["value"] <= c["limit"] for c in checked["checks"].values()), checked["checks"]
    assert set(checked["control"]) == set(fam.CONTROLS) and len(fam.CONTROLS) == 7
    for name, read in checked["control"].items():
        assert any(read[k] > limits[k] for k in limits), (name, read)


def test_the_bf16_witness_is_rounding_and_no_control(run, fam):
    """The reference rounded to bf16 where the program holds bf16 (PERF.md section 2's witness of what rounding alone
    reads on the chip) moves the tiny cell's logits, by less than the fp8 control does, and is no control."""
    import numpy as np

    cfg = tiny_cell(run)["config"]
    params = fam.seeded_params(cfg, 3)
    tokens = np.random.default_rng(3).integers(0, cfg["vocab_size"], 48)
    exact = np.asarray(fam.ref_logits(params, tokens, cfg))
    moved = {p: float(np.max(np.abs(np.asarray(fam.ref_logits(params, tokens, cfg, p)) - exact))) for p in ("bf16", "fp8")}
    assert 0 < moved["bf16"] < moved["fp8"], moved
    assert fam.WITNESS == "bf16" and fam.WITNESS not in fam.CONTROLS


def test_traced_run_reads_the_real_cells_readers(run):
    cell = tiny_cell(run)
    real = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    cell["per_layer"] = [m for m in real["per_layer"] if MINE in m["workloads"]]
    result = run.run_cell(cell, 5, 1.5, True, CPU_DEVICE)
    assert {"serve.mfu", "serve.decode_fill", "serve.tick_ms", "serve.device_idle", "serve.sparse_read_share"} <= set(result["metrics"])
    assert 0 < result["metrics"]["serve.sparse_read_share"]["value"] < 100  # contexts run past the top 16
    assert result["facts"]["compiles_in_window"] == 0


def test_sparse_readers_on_hand_made_runs(run):
    read_share = run.load_module("readers", "serve.sparse_read_share").read
    assert read_share({"traced": {"counters": {"sparse_rows_read": 300, "context_rows": 1000}}}) == 30.0
    assert read_share({"traced": {"counters": {"moe_rows": 5, "context_rows": 1000}}}) is None  # no indexer: nothing to read
    assert read_share({"traced": {}}) is None
    attn_share = run.load_module("readers", "serve.sparse_attn_share").read
    roofline = run.load_module("readers", "serve.sparse_roofline").read
    assert attn_share({"traced": {}}) is None  # no trace
    fixture = os.path.join(BENCH, "fixtures", "agent_closed16.tpu_v5e.program.json.gz")
    assert attn_share({"traced": {"raw_path": fixture, "trace": {"busy_s": 1.0}}}) is None  # the kanana cell: no such scope
    counters = {"index_rows_scored": 4_000, "sparse_lane_rows": 2_000}
    assert roofline({"family": object(), "cfg": {}, "traced": {"counters": counters}}) is None  # a family without the count
    assert roofline({"family": run.load_module("families", "keye_vl2"), "cfg": real_cfg(run, "keye-vl-2.0-30b-a3b"),
                     "peak_bytes": 819e9, "traced": {"counters": counters, "raw_path": fixture, "trace": {"busy_s": 1.0}}}) is None


def test_the_preroll_may_take_five_minutes(run, monkeypatch):
    """The pre-roll ends at its 4,500 ticks (about 100 s on the chip): it may take five minutes, the measured window
    its own seconds, and the drain takes no tick at all."""
    mod = run.load_module("drivers", "serve_closed_sparse")
    seen = []
    monkeypatch.setattr(mod.base.Driver, "loop", lambda self, seconds, probe, refill=True, ticks=None: seen.append(seconds))
    monkeypatch.setattr(mod.Driver, "harvest", lambda self: seen.append("harvest"))
    driver = object.__new__(mod.Driver)
    assert driver.loop(mod.base.DRAIN_LIMIT_S, None, refill=False) == 0.0
    driver.loop(mod.base.DRAIN_LIMIT_S, None, ticks=4500)
    driver.loop(45.0, object())
    assert seen == ["harvest", 300.0, 45.0] and mod.PREROLL_LIMIT_S > mod.window_driver.DRAIN_LIMIT_S


def test_the_replies_due_at_the_close_are_taken_as_they_stand(run):
    """No drain: every request still due when the window closes leaves ``inflight`` at once; those that served a
    token count with the tokens they served so far (no request fails for want of a drain), and the others not at
    all; the check reads the partial replies."""
    cell = tiny_cell(run)
    cell["traffic"] = dict(cell["traffic"], new_tokens=dict(cell["traffic"]["new_tokens"], min=48, median=48))
    _, _, driver = run.build_driver(cell, 2**31 + 3, None)
    driver.setup()
    driver.window(1.0, run.Probe(False, 1.0, 0, ""))
    assert driver.inflight == {} and driver.partial
    assert all(c.status == "ok" and 0 < c.new_tokens < 48 for _, c in driver.partial)
    e2e = driver.end_to_end()
    assert e2e["failed"] == 0 and e2e["attempted"] >= len(driver.partial)
    assert driver.marks["completed"] <= len(driver.finished) - len(driver.partial)
    sampled = driver.sample()
    driver.release()
    assert any(c.new_tokens < 48 for c in sampled)
    assert all(c["value"] <= c["limit"] for c in driver.check()["checks"].values())


def test_the_check_counts_the_wide_gaps(run):
    mod = run.load_module("drivers", "serve_closed_sparse")
    assert mod.WIDE_GAP == 0.75
    assert mod.gap_stats([0.0, 0.2, 0.8, 1.0]) == {
        "served_gap_mean": 0.5, "served_gap_share": 0.75, "served_gap_share_wide": 0.5}
    assert mod.gap_stats([]) == {"served_gap_mean": None, "served_gap_share": None, "served_gap_share_wide": None}
    limits = run.load_json(os.path.join(BENCH, "limits", MINE + ".json"))["limits"]
    assert set(limits) == {"served_gap_mean", "served_gap_share", "served_gap_share_wide"}


def test_snapshot_carries_the_sparse_counters(run):
    _, _, driver = run.build_driver(tiny_cell(run), 3, None)
    driver.setup()
    before = driver.snapshot()
    driver.loop(30.0, None, ticks=8)
    after = driver.snapshot()
    assert after["ticks"] - before["ticks"] == 8
    grew = {k: after[k] - before[k] for k in ("index_rows_scored", "sparse_rows_read", "sparse_lane_rows", "context_rows")}
    assert grew["context_rows"] > grew["sparse_rows_read"] > 0 and grew["index_rows_scored"] > grew["sparse_lane_rows"] > 0
    assert "window_rows_read" not in after and "prefix_cache_off" not in driver.engine.stats()
    driver.release()
