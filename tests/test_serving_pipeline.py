"""A tick is read back one dispatch late: ``step()`` dispatches tick N + 1 before
it reads tick N, and the decoders' token feed stays on the device.

The tokens served are, for every family the engine serves, those of the same
engine settled after every ``step()`` and those of the offline ``generate``;
the two counters the mechanism brings (``pipelined_ticks``, ``settles`` by
reason) add up; and every path that needs a token's value settles first and
loses nothing: a forced preemption, a live deadline, ``drain()``, journal
recovery, a poisoned lane (quarantined one read-back late), an idle engine.
(``tests/test_serving_mixed.py`` holds what a tick dispatches,
``tests/test_serving_programs.py`` the feed the programs pass on,
``tests/test_spec_serving.py`` the verify window, which settles every tick.)"""

import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import telemetry
from accelerate_tpu.models import lfm2_moe
from accelerate_tpu.resilience import faultinject

from test_serving_mixed import NEW_TOKENS, _dispatches, _engine, _oracle, _pool_is_finite, _prompts, _telemetry_clean  # noqa: F401 (the fixture)
from test_serving_mixed import _setup as _mixed_setup


def _setup(name):
    """name -> (the family, what it is served through, its tiny float32 config, parameters)."""
    if name != "lfm2_moe":
        return _mixed_setup(name)
    # a state by slot beside the token rows: six layers of the published interleaving
    cfg = lfm2_moe.Lfm2MoeConfig.tiny(num_layers=6, layer_types=lfm2_moe.PUBLISHED_LAYER_TYPES[:6], dtype=jnp.float32, param_dtype=jnp.float32)
    return lfm2_moe, lfm2_moe.apply_cached, cfg, lfm2_moe.init_params(cfg, jax.random.key(0))


def _retiring_beside_a_final_chunk(eng):
    """The tick in flight holds a lane whose last token it is and another request's last chunk."""
    flight = eng._flight
    return flight is not None and flight.final and any(slot in eng.sched.retiring for slot in flight.lanes)


@pytest.mark.parametrize("name", ["gpt2", "llama", "deepseek_v3", "lfm2_moe", "dense"])
def test_the_pipelined_engine_serves_the_settled_engines_tokens(name):
    """Six requests through three slots, chunks and decoders mixed, a request finishing while another's last
    chunk rides, slots reused: the engine that reads every tick back one dispatch late serves, token for token,
    what the same engine serves when it is settled after every ``step()`` (``stats()`` does that), and what the
    offline loop generates.  Every dispatch but the first was made with the one before it unread; the settled
    engine made none so."""
    family, apply_cached, cfg, params = _setup(name)
    prompts = _prompts(cfg)
    served, counters = {}, {}
    for mode in ("pipelined", "settled"):
        eng = _engine(family, apply_cached, cfg, params)
        ids = [eng.submit(prompt, new) for prompt, new in zip(prompts, NEW_TOKENS)]
        witnessed = reused = False
        while not eng.sched.idle():
            done = eng.step()
            assert all(c.status == "ok" and len(c.tokens) == c.prompt_len + c.new_tokens for c in done)
            witnessed |= _retiring_beside_a_final_chunk(eng)
            reused |= any(slot.admit_seq >= 3 for slot in eng.sched.slots.values())
            assert not (eng.sched.idle() and eng._flight is not None)  # never idle with a tick unread
            if mode == "settled":
                eng.stats()
        counters[mode] = eng.stats()
        served[mode] = {i: c.tokens for c in eng.pop_finished() for i, rid in enumerate(ids) if rid == c.id}
        assert len(served[mode]) == len(prompts) and reused and eng.cache.allocator.used_blocks == 0
        if mode == "pipelined":
            assert witnessed, "no request finished while another's last chunk rode: choose other sizes"
    assert served["pipelined"] == served["settled"]
    for i, (prompt, new) in enumerate(zip(prompts, NEW_TOKENS)):
        assert served["pipelined"][i] == _oracle(family, cfg, params, prompt, new), f"request {i} diverged from generate"
    piped, settled = counters["pipelined"], counters["settled"]
    assert piped["settles"] == {"idle": 1}  # the last tick, with nothing left to dispatch behind it
    assert piped["pipelined_ticks"] == piped["ticks"] - sum(piped["settles"].values()) == _dispatches(piped) - 1
    assert settled["pipelined_ticks"] == 0 and sum(settled["settles"].values()) == _dispatches(settled)
    for key in ("decode_dispatches", "prefill_dispatches", "mixed_dispatches", "ticks"):
        assert piped[key] == settled[key], key  # the same builds: what a tick holds does not depend on when it is read


def test_the_pipeline_counters_are_published_everywhere(tmp_path):
    """``pipelined_ticks`` and ``settles`` in ``stats()``, as telemetry counters, and ``pipelined`` / ``settle`` in
    the tracer's tick records."""
    tel = telemetry.enable(dir=str(tmp_path))
    family, apply_cached, cfg, params = _setup("llama")
    eng = _engine(family, apply_cached, cfg, params, trace=True)
    for prompt, new in zip(_prompts(cfg), NEW_TOKENS):
        eng.submit(prompt, new)
    records = []
    while not eng.sched.idle():
        eng.step()
        records.append(dict(eng._tick))
    stats, snap = eng.stats(), tel.registry.snapshot()
    assert stats["pipelined_ticks"] == snap["serving.pipelined_ticks"] == sum(r["pipelined"] for r in records) == len(records) - 1
    assert stats["settles"] == {"idle": 1} and snap["serving.settles"] == 1
    assert [r["settle"] for r in records] == [None] * (len(records) - 1) + ["idle"]
    assert not records[0]["pipelined"] and all(r["pipelined"] for r in records[1:])
    slow = stats["slow_ticks"]
    assert slow and all(isinstance(t["pipelined"], bool) and t["settle"] in (None, "idle") for t in slow)


def _decoding_engine(name="gpt2", steps=4, **overrides):
    """Two requests decoding side by side, a tick in flight, every tick so far pipelined."""
    family, apply_cached, cfg, params = _setup(name)
    eng = _engine(family, apply_cached, cfg, params, **overrides)
    prompts = _prompts(cfg, (5, 7), seed=21)
    ids = [eng.submit(prompt, 12) for prompt in prompts]
    for _ in range(steps):
        eng.step()
    assert eng._flight is not None and eng.settles == {} and all(s.unread == 1 for s in eng.sched.slots.values())
    return family, cfg, params, eng, prompts, ids


def _finish(eng, family, cfg, params, prompts, ids, new=12):
    eng.run(max_ticks=300)
    done = {c.id: c for c in eng.pop_finished()}
    for rid, prompt in zip(ids, prompts):
        assert done[rid].status == "ok" and done[rid].tokens == _oracle(family, cfg, params, prompt, new)
    assert eng.cache.allocator.used_blocks == 0
    return done


def test_a_forced_preemption_settles_first_and_the_victim_keeps_every_token():
    """A victim re-prefills ``prompt + emitted``: the token in flight for it is read before it is re-queued, so
    nothing dispatched is lost, and what it finishes with is the oracle's."""
    family, cfg, params, eng, prompts, ids = _decoding_engine()
    dispatched = {s.request.id: len(s.request.emitted) + s.unread for s in eng.sched.slots.values()}
    eng.sched.preempt_one()  # the youngest: its last token was still on the device
    assert eng.settles == {"preempt": 1} and eng._flight is None
    victim = eng.sched.queue[0]
    assert victim.id == ids[1] and len(victim.emitted) == dispatched[victim.id] and victim.to_feed == prompts[1] + victim.emitted
    survivor = next(iter(eng.sched.slots.values()))
    assert len(survivor.request.emitted) == dispatched[survivor.request.id] and survivor.unread == 0
    done = _finish(eng, family, cfg, params, prompts, ids)
    assert done[ids[1]].preemptions == 1 and done[ids[1]].prefill_dispatches == 1 + 2  # 7 + 4 rows fed again: two chunks
    assert eng.stats()["settles"]["preempt"] == 1


def test_a_live_deadline_expiry_settles_first():
    """A live request past its deadline is cancelled with every token dispatched for it, the one in flight too."""
    family, cfg, params, eng, prompts, ids = _decoding_engine()
    slot = next(s for s in eng.sched.slots.values() if s.request.id == ids[0])
    dispatched = len(slot.request.emitted) + slot.unread
    slot.request.deadline_ms = 0.0  # expired as of now
    eng.step()
    assert eng.settles == {"deadline": 1}
    done = {c.id: c for c in eng.pop_finished()}
    assert done[ids[0]].status == "deadline_expired" and done[ids[0]].new_tokens == dispatched
    assert done[ids[0]].tokens == _oracle(family, cfg, params, prompts[0], 12)[: len(prompts[0]) + dispatched]
    _finish(eng, family, cfg, params, prompts[1:], ids[1:])


def test_drain_with_a_tick_in_flight_journals_every_token_dispatched():
    """``drain()`` reads the tick in flight before it re-queues the slots: the requeue journal carries every token
    a program was launched for, and a successor that resubmits it finishes on the oracle's tokens."""
    family, cfg, params, eng, prompts, ids = _decoding_engine()
    dispatched = {s.request.id: len(s.request.emitted) + s.unread for s in eng.sched.slots.values()}
    journal = eng.drain()
    assert eng.settles == {"drain": 1} and eng._flight is None and eng.drained
    assert {rec["id"]: len(rec["emitted"]) for rec in journal} == dispatched
    successor = _engine(family, family.apply_cached, cfg, params)
    again = {successor.submit(rec["prompt"] + rec["emitted"], rec["remaining"]): rec["id"] for rec in journal}
    outputs = successor.run(max_ticks=300)
    for rid, old in again.items():
        assert outputs[rid] == _oracle(family, cfg, params, prompts[ids.index(old)], 12)


def test_journal_recovery_settles_the_tick_in_flight(tmp_path):
    """An engine that takes over a dead predecessor's journal while it serves its own requests reads its tick in
    flight back first; its own and the recovered requests finish on the oracle's tokens."""
    family, apply_cached, cfg, params = _setup("gpt2")
    dead = _engine(family, apply_cached, cfg, params, journal_path=str(tmp_path / "dead.jsonl"))
    orphan = _prompts(cfg, (9,), seed=33)[0]
    dead.submit(orphan, 8, tag="orphan")
    for _ in range(4):
        dead.step()  # its journal holds the admission; the tick in flight dies with it
    family, cfg, params, eng, prompts, ids = _decoding_engine()
    mapping = eng.recover_from_journal(str(tmp_path / "dead.jsonl"))
    assert eng.settles == {"recover": 1} and len(mapping) == 1
    done = _finish(eng, family, cfg, params, prompts, ids)
    (new_id,) = mapping.values()
    assert done[new_id].tag == "orphan" and done[new_id].tokens == _oracle(family, cfg, params, orphan, 8)


def test_a_poisoned_lane_is_quarantined_one_read_back_late(monkeypatch):
    """The flag of a poisoned lane is read one dispatch after its program was launched: by then the next tick is in
    flight with one row more for that lane.  The row is dropped, the tick is settled (``quarantine``), the pool is
    scrubbed, and the other lane's tokens are the oracle's bit for bit."""
    monkeypatch.setenv("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST", "2")
    faultinject.reload()
    try:
        family, apply_cached, cfg, params = _setup("gpt2")
        eng = _engine(family, apply_cached, cfg, params)
        prompts = _prompts(cfg, (5, 7), seed=21)
        ids = [eng.submit(prompt, 12) for prompt in prompts]
        eng.step()  # the first prompt's chunk
        eng.step()  # the second's, beside the first's first decode
        eng.step()  # the poisoned request's first decode: the poison fires in this dispatch
        assert eng.quarantined_count == 0 and eng.settles == {}
        poisoned = next(s for s in eng.sched.slots.values() if s.request.id == ids[1])
        rows = poisoned.cache_len
        eng.step()  # one row more for the poisoned lane is launched, then the flag is read: quarantined, and settled
        assert eng.quarantined_count == 1 and eng.settles == {"quarantine": 1} and eng._flight is None
        assert poisoned.cache_len == rows + 1 and poisoned not in eng.sched.slots.values()
        assert _pool_is_finite(eng)
        eng.run(max_ticks=100)
    finally:
        monkeypatch.delenv("ACCELERATE_TPU_FAULT_SERVING_NAN_REQUEST")
        faultinject.reload()
    done = {c.id: c for c in eng.pop_finished()}
    assert done[ids[1]].status == "quarantined" and done[ids[1]].tokens == prompts[1] + _oracle(family, cfg, params, prompts[1], 1)[-1:]
    assert done[ids[0]].status == "ok" and done[ids[0]].tokens == _oracle(family, cfg, params, prompts[0], 12)
    assert eng.cache.allocator.used_blocks == 0 and eng.stats()["settles"] == {"quarantine": 1, "idle": 1}


def test_run_returns_complete_replies_and_a_lone_request_settles_once():
    """One request alone: every tick but the first is pipelined, the last is settled at once (``idle``: nothing is
    left to dispatch behind it, so the reply is handed over in the step that launched its last token), and
    ``run()`` returns the whole reply."""
    family, apply_cached, cfg, params = _setup("llama")
    eng = _engine(family, apply_cached, cfg, params)
    (prompt,) = _prompts(cfg, (11,), seed=4)
    rid = eng.submit(prompt, 7)
    out = eng.run(max_ticks=50)
    assert out[rid] == _oracle(family, cfg, params, prompt, 7) and eng._flight is None and eng.sched.idle()
    stats = eng.stats()
    assert stats["ticks"] == 2 + 6 and stats["pipelined_ticks"] == 7 and stats["settles"] == {"idle": 1}  # 2 chunks, 6 decodes
    assert eng.debug_requests() == []


def test_debug_requests_reports_the_token_in_flight_without_settling():
    """``/debug/requests`` is read from the metrics server's thread: it counts the tokens read and those still on
    the device, and leaves the tick in flight alone."""
    family, cfg, params, eng, prompts, ids = _decoding_engine()
    snap = {r["id"]: r for r in eng.debug_requests()}
    assert eng._flight is not None and eng.settles == {}  # a read from another thread: it must not touch the device
    # four ticks: the first request's chunk and three decodes, the second's chunk (a tick later) and two; the last unread
    assert [(snap[rid]["emitted"], snap[rid]["unread"]) for rid in ids] == [(3, 1), (2, 1)]
    _finish(eng, family, cfg, params, prompts, ids)


def test_a_queue_waiting_for_a_retiring_lanes_blocks_is_not_starved():
    """The queue's head needs the blocks that a retiring lane holds until its last token is read, and nothing else is
    left to dispatch: the step that dispatches nothing reads the tick in flight back (``idle``), the blocks come free
    and the head is admitted in the next."""
    family, apply_cached, cfg, params = _setup("gpt2")
    eng = _engine(family, apply_cached, cfg, params, num_blocks=3, max_slots=1, max_blocks_per_seq=2)  # two blocks in all
    prompts = _prompts(cfg, (7, 7), seed=12)
    ids = [eng.submit(prompt, 2) for prompt in prompts]  # each needs both blocks; the second waits in the queue
    eng.step()  # the first prompt's chunk
    eng.step()  # its second and last token: the lane retires with both blocks
    assert eng.sched.retiring and not eng.sched.slots and eng.cache.allocator.free_blocks == 0 and eng.settles == {}
    eng.step()  # no block for the head's first chunk, nothing dispatched: the tick in flight is read back
    assert eng.settles == {"idle": 1} and not eng.sched.retiring and eng.cache.allocator.free_blocks == 2
    out = eng.run(max_ticks=20)
    for rid, prompt in zip(ids, prompts):
        assert out[rid] == _oracle(family, cfg, params, prompt, 2)
