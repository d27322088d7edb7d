"""``serve_closed.py``'s closed loop for any family the program serves.

``serve_closed.Driver.setup`` names ``llama.apply_cached`` / ``llama.init_cache``
itself.  This driver takes the program's family module from the benchmark
family's ``program_module()`` instead and hands its pair to
``Accelerator.prepare_serving``; the loop, the window and the end-to-end numbers
are inherited.  ``snapshot`` adds the engine's expert counters (``moe_rows``,
``moe_experts_hit``, ``moe_max_rows``; nothing where the program has none),
which the expert layer's readers take over the traced span.  A traffic file is
what ``serve_closed.py`` says it is.

The check samples the window's replies as ``serve_closed.py`` does and puts each
through the plain float32 reference once, but what it compares is not the
widest gap.  A family that routes tokens to experts serves, in bf16, a token
whose sixth and seventh expert the float32 reference ranks the other way
round in some layer, again and again (with seeded weights in half of all
positions at 128 experts and 7 layers, PERF.md section 2), and one swapped
expert moves the logits by more than any rounding does: the widest gap of a
sound run (1.7 ... 2.5) touches the fp8 control's (3.0), at every routing
margin of the reference's own.  The gaps' bulk does not move: so the numbers
compared are their **mean** (``served_gap_mean``) and the **share** of served
tokens that lie more than ``GAP_THRESHOLD`` below the reference's best
(``served_gap_share``), each under its limit in ``limits/<workload>.json``.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np


def _serve_closed():
    """``drivers/serve_closed.py`` under the module name ``run.py:load_module`` gives it."""
    name = "chipbench_drivers_serve_closed"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_closed.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


base = _serve_closed()
SPANS = base.SPANS
MOE_COUNTERS = ("moe_rows", "moe_experts_hit", "moe_max_rows")
GAP_THRESHOLD = 0.15  # the chat cell's limit on the widest gap: what no served token of a dense bf16 model exceeds


def gap_rows(family, cfg: dict, params, sample: list, max_new: int, controls=()) -> dict:
    """As ``serve_closed.served_gaps``, for every served token of every sampled
    request: ``served`` = the reference's best logit minus its logit of the
    served token; ``margin`` = the smallest distance, over the layers, between
    the last selection score the reference chose and the first it did not; and
    for each of ``controls`` (the reference computed with that fault) the same
    gap for the token that the faulty reference puts first."""
    import jax

    ref = family.Reference(cfg, "float32")
    faulty = {name: family.Reference(cfg, name) for name in controls}
    out = {"served": [], "margin": [], **{name: [] for name in controls}}
    with jax.default_matmul_precision("highest"):
        for c in sample:
            tokens = np.asarray(c.tokens, np.int32)
            n_new = len(tokens) - c.prompt_len
            pad_to = -(-len(tokens) // 512) * 512
            picks = np.zeros((max_new,), np.int32)
            picks[:n_new] = tokens[c.prompt_len :]
            rows = ref.hidden_rows(params, tokens, c.prompt_len, pad_to, max_new)
            out["margin"] += np.asarray(ref.last_margin)[:n_new].tolist()
            best, _, at = ref.head_stats(params, rows, picks)
            out["served"] += (np.asarray(best) - np.asarray(at))[:n_new].tolist()
            for name, low in faulty.items():
                _, first, _ = low.head_stats(params, low.hidden_rows(params, tokens, c.prompt_len, pad_to, max_new), picks)
                best, _, at = ref.head_stats(params, rows, np.asarray(first))
                out[name] += (np.asarray(best) - np.asarray(at))[:n_new].tolist()
    return out


def gap_stats(gaps: list) -> dict:
    """The two numbers compared, of one list of gaps."""
    if not gaps:
        return {"served_gap_mean": None, "served_gap_share": None}
    g = np.asarray(gaps)
    return {"served_gap_mean": float(g.mean()), "served_gap_share": float((g > GAP_THRESHOLD).mean())}


class Driver(base.Driver):
    def setup(self) -> None:
        import jax

        from accelerate_tpu import Accelerator

        fam, cfg = self.family, self.cfg
        program = fam.program_module()
        self.params = jax.block_until_ready(fam.seeded_params(cfg, self.ctx["seed"]))
        self.ctx["mark"]("weights")
        self.acc = Accelerator()
        self.engine = self.acc.prepare_serving(
            program.apply_cached, program.init_cache, self.params, fam.program_config(cfg), **cfg["serve"]
        )
        self.ctx["mark"]("engine")
        sc = self.engine.serving
        self.max_slots, self.block_size = sc.max_slots, sc.block_size
        rng = np.random.default_rng([int(self.ctx["seed"]), 13])
        for n in base.warm_prompt_lengths(self.traffic, sc.block_size, sc.prefill_chunk):
            self.engine.submit(rng.integers(0, cfg["vocab_size"], n, dtype=np.int32), 4)
            self.engine.run()
        self.engine.pop_finished()
        self.ctx["mark"]("widths_warm")
        self.inflight = {}
        self.finished = []
        self.tick_s = []
        self.loop(base.DRAIN_LIMIT_S, None, ticks=int(self.traffic["preroll_ticks"]))

    def snapshot(self) -> dict:
        stats = self.engine.stats()
        return dict(super().snapshot(), **{k: stats[k] for k in MOE_COUNTERS if k in stats})

    def check(self, control: bool = False) -> dict:
        """``control`` (chipbench/tests/chip_readings.py, never a benchmark run)
        also reads the same two numbers for every control the family's
        reference knows (``CONTROLS``: the fp8 reference and its five faults)."""
        controls = tuple(self.family.CONTROLS) if control else ()
        rows = gap_rows(self.family, self.cfg, self.params, self.sample(), int(self.traffic["new_tokens"]["max"]), controls)
        stats = gap_stats(rows["served"])
        readings = dict(
            stats, checked_tokens=len(rows["served"]), served_logit_gap=max(rows["served"], default=None),
            routing_margin_q50=float(np.median(rows["margin"])) if rows["margin"] else None,
        )
        limits = self.ctx["limits"]
        out = {"checks": {k: {"value": v, "limit": limits[k]} for k, v in stats.items() if k in limits}, "readings": readings}
        if control:
            out["control"] = {name: gap_stats(rows[name]) for name in controls}
        return out
