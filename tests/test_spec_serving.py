"""Speculative serving decode: the per-slot draft-then-verify tick
(``ServingConfig.spec_tokens``).  Covers the shared greedy verify/accept
kernel, the drafters, per-slot variable acceptance across vmap lanes in ONE
fused dispatch, and the acceptance oracle:
speculative serving stays token-identical to the offline ``generate_loop``
across {paged, dense} x {fp, int8} under randomized mixes, forced
preemption, and journal recovery."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from conftest import recorded_spans, without_apply_paged

from accelerate_tpu import telemetry
from accelerate_tpu.models import gpt2, llama
from accelerate_tpu.models.generation import speculative_verify_greedy
from accelerate_tpu.serving import (
    DraftModelDrafter,
    NgramDrafter,
    ServingConfig,
    ServingEngine,
    ServingJournal,
)


@pytest.fixture(autouse=True)
def _telemetry_clean():
    yield
    telemetry.disable()
    telemetry.get_telemetry().registry.reset()
    telemetry.get_telemetry().step_timer.reset()


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, jax.random.key(0))
    return cfg, params


def _oracle(cfg, params, prompt, max_new):
    out = gpt2.generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                        max_new_tokens=max_new)
    return [int(t) for t in np.asarray(out[0])]


# ---------------------------------------------------------------------------
# The shared verify/accept kernel
# ---------------------------------------------------------------------------


def _logits_for(rows, vocab=16):
    """[B, W] target-argmax plan -> one-hot-ish logits [B, W, vocab]."""
    rows = np.asarray(rows)
    out = np.zeros(rows.shape + (vocab,), np.float32)
    for idx in np.ndindex(rows.shape):
        out[idx + (rows[idx],)] = 5.0
    return jnp.asarray(out)


def test_speculative_verify_greedy_mixed_lanes():
    """One call, three lanes with different fates: full accept, first-draft
    reject, partial accept — m is per-lane and the emitted chunk t[:m+1]
    always ends on the target's own correction/bonus token."""
    drafts = jnp.asarray([[7, 8], [7, 8], [7, 8]], jnp.int32)
    # target argmax rows per lane: [pos0, pos1, pos2]
    t_logits = _logits_for([
        [7, 8, 9],   # agrees with both drafts -> m=2, emit [7, 8, 9]
        [1, 8, 9],   # disagrees at pos 0      -> m=0, emit [1]
        [7, 2, 9],   # agrees then disagrees   -> m=1, emit [7, 2]
    ])
    t, m = speculative_verify_greedy(t_logits, drafts)
    assert m.tolist() == [2, 0, 1]
    assert t.tolist() == [[7, 8, 9], [1, 8, 9], [7, 2, 9]]


def test_speculative_verify_greedy_ragged_draft_len():
    """draft_len masks a lane's unused window tail: a padded draft that
    happens to equal the target argmax must NOT count as accepted."""
    drafts = jnp.asarray([[7, 8], [7, 8]], jnp.int32)
    t_logits = _logits_for([[7, 8, 9], [7, 8, 9]])
    t, m = speculative_verify_greedy(
        t_logits, drafts, draft_len=jnp.asarray([2, 1], jnp.int32)
    )
    # lane 1 only proposed 1 draft; its padded position cannot be accepted
    # even though the pad token matches the target argmax there.
    assert m.tolist() == [2, 1]


# ---------------------------------------------------------------------------
# Drafters
# ---------------------------------------------------------------------------


def test_ngram_drafter_prefers_full_length_continuation():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # Period-2 repetition loop: the LATEST match of the trailing n-gram sits
    # at the feed end where the continuation truncates to 1 token; an
    # earlier occurrence yields the same continuation at full length.
    feed = [5, 6] * 6
    assert d.propose(feed, 4) == [5, 6, 5, 6]
    # A period-1 loop drafts the repeated token at full length too.
    assert d.propose([1, 2, 9, 9, 9, 9, 9, 9], 3) == [9, 9, 9]
    # No earlier occurrence of any trailing n-gram: no drafts.
    assert d.propose([1, 2, 3, 4, 5], 4) == []
    # Truncated fallback: the only continuation on record is shorter than k.
    assert d.propose([7, 1, 2, 3, 7], 4) == [1, 2, 3, 7]
    assert d.propose([], 4) == []
    assert d.propose([1, 2, 3], 0) == []


def test_draft_model_drafter_matches_target_greedy(gpt2_setup):
    """The draft-model option, drafting with the TARGET model itself: its
    sequential greedy proposals must equal the offline greedy continuation
    (so in-engine acceptance would be total)."""
    cfg, params = gpt2_setup
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    want = _oracle(cfg, params, prompt, 4)[len(prompt):]
    d = DraftModelDrafter(gpt2.apply, params, cfg)
    assert d.propose(prompt, 4) == want


# ---------------------------------------------------------------------------
# Per-slot accept/rewind inside one fused dispatch
# ---------------------------------------------------------------------------


class _ScriptedDrafter:
    """Per-request drafts keyed by the feed's prompt prefix."""

    def __init__(self, script):
        self.script = script  # {first_token: fn(feed, k) -> list}

    def propose(self, feed, k):
        fn = self.script.get(int(feed[0]))
        return fn(list(feed), k) if fn else []


def test_mixed_acceptance_across_lanes_in_one_dispatch(gpt2_setup):
    """Two slots in the SAME verify dispatch: one slot's drafter proposes
    the true greedy continuation (full acceptance), the other proposes junk
    (zero acceptance).  The accept counts are per-lane — the oracle-drafted
    request lands k+1 tokens per tick while its neighbor lands 1 — and both
    finish token-identical."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(23)
    p_good = [int(t) for t in rng.integers(1, cfg.vocab_size, size=5)]
    p_junk = [int(t) for t in rng.integers(1, cfg.vocab_size, size=5)]
    p_junk[0] = (p_good[0] + 1) % cfg.vocab_size  # distinct script keys
    max_new_good, max_new_junk = 12, 8
    want_good = _oracle(cfg, params, p_good, max_new_good)
    want_junk = _oracle(cfg, params, p_junk, max_new_junk)
    full = want_good[len(p_good):]

    def good_fn(feed, k):
        # The feed holds the prompt and every emitted token, the last of which
        # is the window's first row: the drafts are what follows it.  (This
        # read ``full[done - 1:]`` — one token late — and passed only while
        # the oracle's continuation kept repeating one token.)
        done = len(feed) - len(p_good)
        return full[done:done + k]

    def junk_fn(feed, k):
        return [0] * k

    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8,
                              prefix_cache=False, spec_tokens=3),
        drafter=_ScriptedDrafter({p_good[0]: good_fn, p_junk[0]: junk_fn}),
    )
    ids = {eng.submit(p_good, max_new_good): "good",
           eng.submit(p_junk, max_new_junk): "junk"}

    def emitted():
        return {ids[s.request.id]: len(s.request.emitted)
                for s in eng.sched.slots.values()}

    # tick 1: good's one chunk rides alone and lands its first token; it
    # decodes from the next tick on (the lanes are built before the dispatch).
    eng.step()
    assert emitted() == {"good": 1, "junk": 0}
    # tick 2: ONE dispatch holds junk's chunk (its first token) and good's
    # verify window — full acceptance lands k+1 = 4 in it.
    eng.step()
    before = emitted()
    assert before == {"good": 5, "junk": 1}
    assert eng.stats()["mixed_dispatches"] == 1 and eng.decode_dispatches == 1 and eng.prefill_dispatches == 2
    # tick 3: BOTH lanes share the verify dispatch — good lands k+1, junk's
    # rejected drafts land 1.
    eng.step()
    after = emitted()
    assert after["good"] - before["good"] == 4, \
        "full acceptance should land k+1 tokens in one dispatch"
    assert after["junk"] - before["junk"] == 1, \
        "rejected drafts must land exactly 1 token in the same dispatch"
    outputs = eng.run(max_ticks=200)
    for rid, out in outputs.items():
        assert out == (want_good if ids[rid] == "good" else want_junk)
    spec = eng.stats()["spec"]
    assert spec["rounds"] == eng.decode_dispatches  # every tick verified
    assert 0.0 < spec["acceptance_rate"] < 1.0
    # the junk lane's 8 one-token rounds bound the dispatch count; the good
    # lane's 12 tokens rode along in ceil(12/4)=3 of them.
    assert eng.decode_dispatches == 8


def test_acceptance_caps_at_remaining_exact_finish(gpt2_setup):
    """A full-accept window crossing the request's budget: emission caps at
    ``remaining`` and the request finishes on exactly its last token."""
    cfg, params = gpt2_setup
    prompt = [2, 7, 1, 8]
    max_new = 6  # not a multiple of k+1: the last window over-proposes
    want = _oracle(cfg, params, prompt, max_new)
    full = want[len(prompt):]

    def fn(feed, k):
        done = len(feed) - len(prompt)
        return full[max(done - 1, 0):][:k]

    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8,
                              prefix_cache=False, spec_tokens=3),
        drafter=_ScriptedDrafter({prompt[0]: fn}),
    )
    rid = eng.submit(prompt, max_new)
    outputs = eng.run(max_ticks=100)
    assert outputs[rid] == want
    assert len(outputs[rid]) == len(prompt) + max_new
    # zero block leaks after completion
    assert eng.cache.allocator.used_blocks == 0


# ---------------------------------------------------------------------------
# Token-identity matrix (the acceptance oracle)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "decode_path",
    ["paged", pytest.param("dense", marks=pytest.mark.slow)],
)
@pytest.mark.parametrize("quant", [False, True])
def test_spec_matrix_token_identical(decode_path, quant):
    """spec x {paged, dense} x {fp, int8} under a randomized mix with a pool
    tight enough to force preemption: every request's output is exactly the
    offline generate_loop's, and verify rounds landed multi-token chunks."""
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32, kv_cache_quant=quant)
    params = gpt2.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(13)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
    # repetitive prompts so the n-gram drafter engages; staggered lengths
    prompts = [pattern * 2 + pattern[:j] for j in (1, 3, 2)]
    max_new = [8, 6, 7]
    want = {i: _oracle(cfg, params, p, m)
            for i, (p, m) in enumerate(zip(prompts, max_new))}
    # the family decides the back end: the wrapper's module has no apply_paged
    apply_cached = {"paged": gpt2.apply_cached, "dense": without_apply_paged(gpt2)}[decode_path]
    eng = ServingEngine(
        apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=9, max_slots=3,
                              prefill_chunk=4, max_blocks_per_seq=6,
                              prefix_cache=False, spec_tokens=2),
    )
    assert eng.stats()["decode_path"] == decode_path
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, max_new))}
    outputs = eng.run(max_ticks=2000)
    assert eng.sched.preempted_count > 0, "pool was not tight enough to force preemption"
    assert eng.decode_dispatches <= eng.ticks  # still <= 1 dispatch/tick
    for rid, out in outputs.items():
        assert out == want[ids[rid]], f"{decode_path}/int8={quant}: request {rid} diverged"
    spec = eng.stats()["spec"]
    assert spec["accepted"] > 0, "the repetitive mix should land some drafts"
    assert spec["tokens_per_dispatch"] > 1.0
    assert eng.cache.allocator.used_blocks == 0


def test_llama_gqa_spec_window_token_identical():
    """GQA end to end: llama-tiny (4 q heads / 2 kv heads) through the
    speculative paged path (a k+1 window of queries against grouped K/V)
    stays token-identical to the offline llama oracle."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(19)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
    prompts = [pattern * 2, pattern * 2 + pattern[:2]]
    want = {}
    for i, p in enumerate(prompts):
        out = llama.generate(params, jnp.asarray([p], jnp.int32), cfg,
                             max_new_tokens=5)
        want[i] = [int(t) for t in np.asarray(out[0])]
    eng = ServingEngine(
        llama.apply_cached, llama.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=5,
                              prefix_cache=False, spec_tokens=2),
    )
    ids = {eng.submit(p, 5): i for i, p in enumerate(prompts)}
    outputs = eng.run(max_ticks=200)
    for rid, out in outputs.items():
        assert out == want[ids[rid]], f"llama request {rid} diverged"
    assert eng.stats()["spec"]["rounds"] > 0


@pytest.mark.slow
def test_spec_journal_recovery_token_identical(gpt2_setup, tmp_path):
    """An abandoned speculative engine's journal rebuilds in a SPECULATIVE
    successor and finishes token-identically — greedy acceptance makes the
    replay deterministic whether tokens originally landed 1 or k+1 at a
    time."""
    cfg, params = gpt2_setup
    jp = str(tmp_path / "journal.json")
    rng = np.random.default_rng(41)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
    prompts = [pattern * 2 + pattern[:j] for j in (0, 1, 2)]
    want = {i: _oracle(cfg, params, p, 6) for i, p in enumerate(prompts)}

    def make(jpath):
        return ServingEngine(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                                  prefill_chunk=8, max_blocks_per_seq=8,
                                  prefix_cache=False, spec_tokens=2,
                                  journal_path=jpath),
        )

    eng = make(jp)
    ids = {eng.submit(p, 6, tag=f"t{i}"): i for i, p in enumerate(prompts)}
    assert len(ServingJournal.pending(ServingJournal.load(jp))) == 3
    eng.step(); eng.step(); eng.step()  # partial progress, then abandon
    finished = {c.tag for c in eng.pop_finished()}

    succ = make(jp)
    succ.recover_from_journal()
    succ.run(max_ticks=500)
    done = {c.tag: c.tokens for c in succ.pop_finished()}
    for old_id, i in ids.items():
        if f"t{i}" in finished:
            continue
        assert done[f"t{i}"] == want[i], f"recovered request {i} diverged"


@pytest.mark.slow
def test_spec_forced_preemption_mid_chunk_token_identical(gpt2_setup):
    """Preempting a slot whose emitted tokens landed in multi-token chunks:
    the re-prefill feeds prompt+emitted and the request still finishes
    token-identical (the rewind left no stale-row residue)."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(43)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
    prompts = [pattern * 2 + pattern[:j] for j in (1, 0, 2)]
    max_new = [8, 6, 7]
    want = {i: _oracle(cfg, params, p, m)
            for i, (p, m) in enumerate(zip(prompts, max_new))}
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=3,
                              prefill_chunk=4, max_blocks_per_seq=8,
                              prefix_cache=False, spec_tokens=2),
    )
    ids = {eng.submit(p, m): i for i, (p, m) in enumerate(zip(prompts, max_new))}
    # let verify rounds land some chunks, then force-evict a decoding slot
    for _ in range(6):
        eng.step()
    decoding = [idx for idx, s in eng.sched.slots.items()
                if len(s.request.emitted) > 1]
    assert decoding, "no slot accumulated a multi-token chunk before eviction"
    eng.sched.preempt_slot(decoding[0])
    outputs = eng.run(max_ticks=1000)
    assert eng.sched.preempted_count > 0
    for rid, out in outputs.items():
        assert out == want[ids[rid]], f"request {rid} diverged after preemption"


# ---------------------------------------------------------------------------
# Telemetry + tracing
# ---------------------------------------------------------------------------


def test_spec_counters_and_verify_phase_conservation(gpt2_setup, tmp_path):
    """serving.spec.* counters move, the gauges publish, verify intervals
    land in the per-request traces as productive phases, and every
    completed trace's phase sum still partitions its wall window."""
    cfg, params = gpt2_setup
    telemetry.enable(dir=str(tmp_path))
    rng = np.random.default_rng(47)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8,
                              prefix_cache=False, spec_tokens=2,
                              trace=True, trace_dir=str(tmp_path)),
    )
    reg = telemetry.get_telemetry().registry
    snap0 = reg.snapshot()
    # pre-created at construction: absent-vs-zero is diagnosable
    for name in ("serving.spec.rounds", "serving.spec.proposed",
                 "serving.spec.accepted"):
        assert name in snap0, f"{name} not pre-created"
    rids = [eng.submit(pattern * 2 + pattern[:j], 6) for j in (0, 2)]
    eng.run(max_ticks=200)
    snap = reg.snapshot()
    assert snap["serving.spec.rounds"] > 0
    assert snap["serving.spec.proposed"] > 0
    assert snap["serving.spec.accepted"] > 0
    assert snap["serving.spec.acceptance_rate"] > 0.0
    assert snap["serving.tokens_per_dispatch"] > 1.0
    spec = eng.stats()["spec"]
    assert spec["acceptance_rate"] == pytest.approx(
        snap["serving.spec.acceptance_rate"])
    traces = eng.tracer.completed
    assert len(traces) == 2
    saw_verify = False
    for t in traces:
        phases = t.phase_ms()
        saw_verify = saw_verify or phases.get("verify", 0.0) > 0.0
        window = t.window_ms()
        attributed = sum(phases.values())
        assert abs(window - attributed - t.unattributed_ms()) < 1e-6
    assert saw_verify, "no verify interval reached the traces"


@pytest.mark.parametrize("spec_tokens", [0, 2], ids=["greedy", "window"])
def test_a_verify_window_engine_settles_every_tick(gpt2_setup, spec_tokens):
    """The accepted count decides ``cache_len`` and the drafter reads the
    tokens, so with ``spec_tokens > 0`` every tick is read back before the
    next is built: no dispatch is made with a tick unread, one ``spec``
    settle a dispatch, no tick in flight between steps, and the tokens and
    the accepted counts are the synchronous engine's (the oracle's).  The
    same engine without a window pipelines every tick but the first: the
    depth follows from ``programs.window``, nothing is configured."""
    cfg, params = gpt2_setup
    rng = np.random.default_rng(47)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8,
                              prefix_cache=False, spec_tokens=spec_tokens),
    )
    prompts = [pattern * 2 + pattern[:j] for j in (0, 2)]
    rids = [eng.submit(p, 9) for p in prompts]
    while not eng.sched.idle():
        eng.step()
        assert spec_tokens == 0 or eng._flight is None
    stats = eng.stats()
    dispatches = stats["prefill_dispatches"] + stats["decode_dispatches"] - stats["mixed_dispatches"]
    assert dispatches == stats["ticks"]
    if spec_tokens:
        assert stats["pipelined_ticks"] == 0 and stats["settles"] == {"spec": dispatches}
        assert stats["spec"]["rounds"] == stats["decode_dispatches"] and stats["spec"]["accepted"] > 0
        assert stats["spec"]["tokens_per_dispatch"] > 1.0
    else:
        assert stats["pipelined_ticks"] == dispatches - 1 and stats["settles"] == {"idle": 1}
    done = {c.id: c.tokens for c in eng.pop_finished()}
    for rid, prompt in zip(rids, prompts):
        assert done[rid] == _oracle(cfg, params, prompt, 9)


def test_the_tick_account_counts_a_verify_windows_rows_and_its_settles(gpt2_setup):
    """Under a verify window a lane's rows of a dispatch are its token and its
    drafts (the drafter here always proposes all it is asked for: ``min(k,
    remaining - 1)``), the program computes ``max_slots`` windows of ``k + 1``,
    and every tick's one read is a settle's, of the tick itself."""
    cfg, params = gpt2_setup
    k, slots, chunk = 3, 2, 8
    with recorded_spans() as spans:
        eng = ServingEngine(
            gpt2.apply_cached, gpt2.init_cache, params, cfg,
            serving=ServingConfig(block_size=4, num_blocks=20, max_slots=slots, prefill_chunk=chunk,
                                  max_blocks_per_seq=8, prefix_cache=False, spec_tokens=k),
            drafter=_ScriptedDrafter({2: lambda feed, want: [0] * want}),
        )
        eng.submit([2, 7, 1, 8], 6)
        want_rows = []
        while not eng.sched.idle():
            remaining = [s.request.remaining for s in eng.sched.slots.values() if s.request.emitted]
            want_rows.append(sum(1 + min(k, r - 1) for r in remaining) or 4)  # the first tick: the prompt's one chunk
            eng.step()
    ticks = [s for s in spans if s.name == "serving.tick"]
    assert [t.meta["rows_live"] for t in ticks] == want_rows and want_rows[:2] == [4, 4] and want_rows[-1] == 1
    assert [t.meta["rows_computed"] for t in ticks] == [slots * (k + 1) + chunk] + [slots * (k + 1)] * (len(ticks) - 1)
    assert all((t.meta["pipelined"], t.meta["settles"]) == (0, 1) for t in ticks)
    reads = [s for s in spans if s.name == "serving.tick.read"]
    assert [(s.meta["tick"], s.meta["of"], s.meta["settle"]) for s in reads] == [(t.meta["tick"],) * 2 + ("spec",) for t in ticks]


def test_spec_report_block_renders(gpt2_setup, tmp_path):
    """The telemetry report's serving block includes the speculative line
    when verify rounds ran."""
    from accelerate_tpu.telemetry.report import format_serving_block

    cfg, params = gpt2_setup
    telemetry.enable(dir=str(tmp_path))
    rng = np.random.default_rng(53)
    pattern = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=40, max_slots=2,
                              prefill_chunk=8, max_blocks_per_seq=8,
                              prefix_cache=False, spec_tokens=2),
    )
    eng.submit(pattern * 3, 6)
    eng.run(max_ticks=200)
    block = "\n".join(
        format_serving_block(telemetry.get_telemetry().registry.snapshot())
    )
    assert "speculative:" in block
    assert "drafts accepted" in block


def test_scheduler_budgets_spec_overshoot(gpt2_setup):
    """Admission worst case includes the verify window's overshoot: a
    request that fits greedily is rejected under spec_tokens when the
    window headroom pushes it past max_blocks_per_seq."""
    from accelerate_tpu.serving import BlockAllocator, Request
    from accelerate_tpu.serving.scheduler import Scheduler

    cfg, params = gpt2_setup
    r = Request(list(range(10)), 7)  # 10 + 6 fed rows
    assert Scheduler(BlockAllocator(20), 1, 4, 4,
                     prefill_chunk=4).max_rows(r) == 16
    # +k rows of window overshoot crosses the next chunk boundary
    assert Scheduler(BlockAllocator(20), 1, 4, 5, prefill_chunk=4,
                     spec_overshoot=2).max_rows(r) == 20
    eng = ServingEngine(
        gpt2.apply_cached, gpt2.init_cache, params, cfg,
        serving=ServingConfig(block_size=4, num_blocks=20, max_slots=1,
                              prefill_chunk=4, max_blocks_per_seq=4,
                              prefix_cache=False, spec_tokens=2),
    )
    with pytest.raises(ValueError, match="max_blocks_per_seq"):
        eng.submit(list(range(10)), 7)  # fits greedy, not the spec window
