"""Serving cells under a closed loop: a fixed pool of callers, each with one
request outstanding; a caller's next request is submitted the moment its reply
is popped, with ``arrival_t`` = that moment.

The engine is the one ``Accelerator.prepare_serving`` returns, driven tick by
tick through ``submit`` / ``step`` / ``pop_finished``; options the
configuration's ``serve`` group does not name stay at the program's defaults.

Every seed sends the same sizes: the ``deck`` is the stratified quantiles of
the two length distributions, paired by a permutation fixed in the traffic
file, and dealt again and again in one order.  With ``deck_order_seed`` in the
traffic file that order is the mix's own and ``--seed`` draws the token ids
(and the weights); without it ``--seed`` shuffles the order too.  In a closed
loop the order is the work: it decides which requests share the pool, so the
table width every slot decodes at and whose prefill waits behind whose, and
seeds that reorder read tokens/s 131..175 and ttft_p90 5.1..9.5 s (PERF.md).
Set-up warms every table width the mix can reach through the public API (one
request per width, drained), then runs the loop for ``preroll_ticks`` engine
ticks so that the window opens on a pool in mid-flight, the same one in every
run, and not on sixteen prompts submitted at once.

What the window counts: every new token, every first token and every gap
between two tokens of one request whose moment lies inside it, whichever
request it belongs to.  The moments are the engine's own marks
(``CompletedRequest.ttft_ms`` and ``.inter_token_ms`` from the ``arrival_t``
the driver passed).  Requests still in flight when the window closes are
answered after it (no new ones are sent), so that their tokens inside the
window count too: a rate taken over replies completed in the window alone
would swing by a whole reply at each edge.

The check: once the window has closed and the engine is freed, a seeded sample
of the finished requests, the longest among them, goes through the plain
reference (prompt + served tokens, one forward each); the number compared is
the widest gap by which a served token's reference logit lies below the
reference's best at that position.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

SPANS = ("submit", "engine.step", "pop_finished")
DRAIN_LIMIT_S = 60.0


def window_marks(finished: list, t_open: float, t_close: float) -> dict:
    """What lies inside [t_open, t_close) among the replies' own time marks.
    ``finished``: (arrival_t, CompletedRequest).  A request counts (``requests``)
    when any of its tokens lies inside; its prompt counts with its first token."""
    out = {"tokens": 0, "prompt_tokens": 0, "pairs": 0, "ttft_ms": [], "gaps_ms": [], "requests": 0, "failed": 0,
           "completed": 0}
    for arrival, c in finished:
        if c.status != "ok" or c.ttft_ms is None:
            out["failed"] += 1
            out["requests"] += 1
            continue
        t = arrival + c.ttft_ms / 1e3
        inside = 0
        if t_open <= t < t_close:
            out["ttft_ms"].append(c.ttft_ms)
            out["prompt_tokens"] += c.prompt_len
            out["pairs"] += c.prompt_len * (c.prompt_len + 1) // 2
            inside += 1
        for j, gap in enumerate(c.inter_token_ms):
            t += gap / 1e3
            if t_open <= t < t_close:
                out["gaps_ms"].append(gap)
                out["pairs"] += c.prompt_len + j + 1
                inside += 1
        out["tokens"] += inside
        out["requests"] += 1 if inside else 0
        out["completed"] += 1 if inside and t < t_close else 0
    return out


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as whole tokens."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(lengths), spec["min"], spec["max"]).astype(np.int64)


def deck(traffic: dict) -> list:
    """The fixed multiset of (prompt tokens, new tokens) every seed sends."""
    n = int(traffic["deck"])
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    news = quantile_lengths(traffic["new_tokens"], n)
    pairing = np.random.default_rng(int(traffic["deck_pairing_seed"])).permutation(n)
    return [(int(p), int(news[j])) for p, j in zip(prompts, pairing)]


class Requests:
    """The stream of requests of one run: the deck dealt in one order, again and
    again, each request with fresh uniform token ids from the seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        cards = deck(traffic)
        order_seed = traffic.get("deck_order_seed")
        order_rng = np.random.default_rng([int(seed), 19] if order_seed is None else int(order_seed))
        self.order = [cards[i] for i in order_rng.permutation(len(cards))]
        self.vocab, self.dealt = vocab, 0
        self.rng = np.random.default_rng([int(seed), 11])

    def next(self) -> tuple:
        prompt_len, new = self.order[self.dealt % len(self.order)]
        self.dealt += 1
        return self.rng.integers(0, self.vocab, prompt_len, dtype=np.int32), new


def warm_prompt_lengths(traffic: dict, block_size: int, chunk: int) -> list:
    """One prompt per table width the mix can reach.  A prompt of w/2 blocks
    decodes at width w, and its chunks prefill at every width up to w/2; the
    longest prompt of the mix covers the widest prefill."""
    longest = int(traffic["prompt_tokens"]["max"])
    lengths, rows = [], max(chunk, block_size)
    while rows < longest:
        lengths.append(rows)
        rows *= 2
    return lengths + [longest]


class Driver:
    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.cfg, self.traffic, self.family = ctx["cfg"], ctx["traffic"], ctx["family"]
        self.requests = Requests(self.traffic, self.cfg["vocab_size"], ctx["seed"])
        self.callers = int(self.traffic["callers"])

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        import jax

        from accelerate_tpu import Accelerator
        from accelerate_tpu.models import llama

        fam, cfg = self.family, self.cfg
        # weights ready, their program's temporaries freed, before the pool is placed: the same layout of the
        # chip's memory in every run (a whole run now and then decodes 6 ms a tick slower, PERF.md)
        self.params = jax.block_until_ready(fam.seeded_params(cfg, self.ctx["seed"]))
        self.ctx["mark"]("weights")
        self.acc = Accelerator()
        self.engine = self.acc.prepare_serving(
            llama.apply_cached, llama.init_cache, self.params, fam.program_config(cfg), **cfg["serve"]
        )
        self.ctx["mark"]("engine")
        sc = self.engine.serving
        self.max_slots, self.block_size = sc.max_slots, sc.block_size
        rng = np.random.default_rng([int(self.ctx["seed"]), 13])
        for n in warm_prompt_lengths(self.traffic, sc.block_size, sc.prefill_chunk):
            self.engine.submit(rng.integers(0, cfg["vocab_size"], n, dtype=np.int32), 4)
            self.engine.run()
        self.engine.pop_finished()
        self.ctx["mark"]("widths_warm")
        self.inflight = {}
        self.finished = []
        self.tick_s = []
        self.loop(DRAIN_LIMIT_S, None, ticks=int(self.traffic["preroll_ticks"]))

    # -- the loop -------------------------------------------------------------

    def submit(self, now: float) -> None:
        prompt, new = self.requests.next()
        self.inflight[self.engine.submit(prompt, new, arrival_t=now)] = now

    def snapshot(self) -> dict:
        e = self.engine
        s = e.stats()
        return {
            "ticks": s["ticks"], "prefill_dispatches": s["prefill_dispatches"],
            "decode_dispatches": s["decode_dispatches"], "decode_slot_ticks": e.decode_slot_ticks,
            "decode_gather_blocks": s["decode_gather_bytes"] // e.cache.block_bytes(),
            "preempted": s["preempted"], "prefix_hits": s["prefix_hits"],
        }

    def loop(self, seconds: float, probe, refill: bool = True, ticks: int | None = None) -> float:
        """Ticks until ``seconds`` have passed, or ``ticks`` ticks, or with
        ``refill`` off until every caller has its reply."""
        span, engine = self.ctx["span"], self.engine
        t0, last = time.monotonic(), len(self.tick_s) + (ticks or 0)
        while True:
            now = time.monotonic()
            if now - t0 >= seconds or (ticks is not None and len(self.tick_s) >= last) or (not refill and not self.inflight):
                return now - t0
            if probe is not None:
                probe.tick(now - t0, self.snapshot)
            if refill:
                with span("submit"):
                    while len(self.inflight) < self.callers:
                        self.submit(time.monotonic())
            with span("engine.step"):
                engine.step()
            self.tick_s.append(time.monotonic() - now)
            with span("pop_finished"):
                done = engine.pop_finished()
            for c in done:
                self.finished.append((self.inflight.pop(c.id), c))

    def window(self, seconds: float, probe) -> dict:
        c0, gc0 = self.snapshot(), gc.get_stats()
        self.tick_s = []
        self.t_open = time.monotonic()
        self.window_s = self.loop(seconds, probe)
        self.t_close = self.t_open + self.window_s
        traced = probe.close(self.snapshot)
        c1 = self.snapshot()
        self.widths = self.engine.stats()["decode_bucket_widths"]
        ticks = sorted(1e3 * t for t in self.tick_s)
        self.tick_facts = {f"tick_p{q}_ms": percentile(ticks, q) for q in (10, 50, 90, 100)}
        # where a run reads slow: ticks, the time in them, what the slowest took over the median, collections
        self.tick_facts.update(
            ticks=len(ticks), in_ticks_s=sum(ticks) / 1e3,
            stalls_ms=[round(t, 1) for t in ticks if t > 1.5 * self.tick_facts["tick_p50_ms"]][-8:],
            gc_collections=[b["collections"] - a["collections"] for a, b in zip(gc0, gc.get_stats())],
        )
        self.loop(DRAIN_LIMIT_S, None, refill=False)  # the replies still due, a minute past the close at most
        counters = self.ctx["delta"](c0, c1)
        self.marks = window_marks(self.finished, self.t_open, self.t_close)
        counters.update(tokens=self.marks["tokens"] + self.marks["prompt_tokens"], pairs=self.marks["pairs"],
                        max_slots=self.max_slots)
        if traced is not None:
            traced["counters"].update(max_slots=self.max_slots, block_size=self.block_size)
        return {"seconds": self.window_s, "counters": counters, "traced": traced}

    def end_to_end(self) -> dict:
        m = self.marks
        lost = len(self.inflight) + m["failed"]  # never answered, or answered with another status than ok
        ttft = sorted(m["ttft_ms"])
        ttft += [ttft[-1] if ttft else float("inf")] * lost  # a failed request counts as the worst
        gaps = sorted(m["gaps_ms"])
        return {
            "values": {
                "serve_tokens_per_s": m["tokens"] / self.window_s,
                "ttft_p90_ms": percentile(ttft, 90),
                "itl_p95_ms": percentile(gaps, 95),
            },
            "attempted": m["requests"] + len(self.inflight),
            "failed": lost,
            "facts": {
                "first_tokens": len(m["ttft_ms"]), "token_gaps": len(gaps), "replies_in_window": m["completed"],
                "ttft_p50_ms": percentile(ttft, 50), "itl_p50_ms": percentile(gaps, 50),
                "decode_bucket_widths": self.widths, **self.tick_facts,
            },
        }

    def release(self) -> None:
        self.engine = self.acc = None
        gc.collect()

    # -- the check ------------------------------------------------------------

    def sample(self) -> list:
        """A seeded sample of the window's replies, the longest among them."""
        def in_window(arrival, c):  # some token of the reply lies inside the window
            first = arrival + (c.ttft_ms or 0.0) / 1e3
            return first < self.t_close and first + sum(c.inter_token_ms) / 1e3 >= self.t_open

        ok = [c for a, c in self.finished if c.status == "ok" and in_window(a, c)]
        if not ok:
            return []
        n = min(int(self.traffic["check_requests"]), len(ok))
        longest = max(range(len(ok)), key=lambda i: len(ok[i].tokens))
        rng = np.random.default_rng([int(self.ctx["seed"]), 17])
        rest = [i for i in rng.permutation(len(ok)) if i != longest][: n - 1]
        return [ok[i] for i in [longest] + rest]

    def check(self, control: bool = False) -> dict:
        """``control`` (chipbench/tests/chip_readings.py, never a benchmark run)
        also reads, at the same positions, the gap of the token that the
        reference computed in fp8 puts first."""
        gaps = served_gaps(
            self.family, self.cfg, self.params, self.sample(), int(self.traffic["new_tokens"]["max"]),
            control="fp8" if control else None,
        )
        readings = {"served_logit_gap": max(gaps["served"], default=None), "checked_tokens": len(gaps["served"])}
        limits = self.ctx["limits"]
        checks = {}
        if "served_logit_gap" in limits:
            checks["served_logit_gap"] = {"value": readings["served_logit_gap"], "limit": limits["served_logit_gap"]}
        out = {"checks": checks, "readings": readings}
        if control:
            out["control"] = {"served_logit_gap": max(gaps["control"], default=None)}
        return out


def percentile(sorted_values: list, q: float):
    """Percentile of an ascending list by linear interpolation between the two
    nearest ranks (numpy's default); None when the list is empty.  A nearest
    rank jumps by a whole gap of the tail when the window's edge takes one
    first token more or less (60 in a window: 9% of ttft_p90, PERF.md)."""
    if not sorted_values:
        return None
    pos = q / 100.0 * (len(sorted_values) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_values) - 1)
    return float(sorted_values[lo] + (pos - lo) * (sorted_values[hi] - sorted_values[lo]))


def served_gaps(family, cfg: dict, params, sample: list, max_new: int, control: str | None = None) -> dict:
    """For each served token of each sampled request: the reference's best
    logit at that position minus its logit of the served token.  With
    ``control``, also the same gap for the token that the reference computed in
    that lower precision puts first (the control need not decode)."""
    import jax

    ref = family.Reference(cfg, "float32")
    low = family.Reference(cfg, control) if control else None
    served, controlled = [], []
    with jax.default_matmul_precision("highest"):
        for c in sample:
            tokens = np.asarray(c.tokens, np.int32)
            n_new = len(tokens) - c.prompt_len
            pad_to = -(-len(tokens) // 512) * 512
            picks = np.zeros((max_new,), np.int32)
            picks[:n_new] = tokens[c.prompt_len :]
            rows = ref.hidden_rows(params, tokens, c.prompt_len, pad_to, max_new)
            best, _, at = ref.head_stats(params, rows, picks)
            served += (np.asarray(best) - np.asarray(at))[:n_new].tolist()
            if low is not None:
                low_rows = low.hidden_rows(params, tokens, c.prompt_len, pad_to, max_new)
                _, first, _ = low.head_stats(params, low_rows, picks)
                best, _, at = ref.head_stats(params, rows, np.asarray(first))
                controlled += (np.asarray(best) - np.asarray(at))[:n_new].tolist()
    return {"served": served, "control": controlled}
