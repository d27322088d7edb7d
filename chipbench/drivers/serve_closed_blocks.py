"""``serve_closed_family.py``'s closed loop for a family generated by diffusion
over blocks (``families/sdar_moe.py``): a decoding lane carries a block of masked
positions through denoising passes and one commit, and a reply's tokens arrive
a block at a time.

The loop, the window, the warm-up and the end-to-end numbers are inherited.
``submit`` passes the traffic file's ``denoise_steps`` (and its
``confidence_threshold``, if it has one) to ``engine.submit`` and draws the
token ids uniformly over the vocabulary **without** ``mask_token_id``;
``snapshot`` adds the engine's block counters (``denoise_slot_ticks``,
``commit_slot_ticks``, ``blocks_committed``, ``block_tokens_emitted``; nothing
where the program has none), which ``serve.tokens_per_lane_tick`` and
``serve.commit_share`` read.

The check replays what every denoising pass of the sampled replies saw.  From
``CompletedRequest.token_passes`` (of every new token, the pass of its block that
unmasked it) ``families/<family>.py:layout`` rebuilds each pass's ``B``-row state,
and the float32 reference runs the finished sequence and all those states in
one forward a reply.  Two things are compared, each by its mean and by the
share over ``GAP_THRESHOLD``, each under its limit in ``limits/<workload>.json``:

- per unmasked token, at the pass that unmasked it: the reference's best logit
  at that position less its logit of the served token (``served_gap_mean``,
  ``served_gap_share``: the two expert cells' numbers, at a masked row);
- per denoising pass that had a choice (more positions masked than it
  unmasked): the reference's best log-confidence among the masked positions the
  pass passed over, less its least among the positions the pass chose, or 0
  where every chosen position leads (``position_gap_mean``,
  ``position_gap_share``): whether the program unmasked the positions the
  reference is most confident of.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np


def _serve_closed_family():
    """``drivers/serve_closed_family.py`` under the module name ``run.py:load_module`` gives it."""
    name = "chipbench_drivers_serve_closed_family"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_closed_family.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


family_driver = _serve_closed_family()
base = family_driver.base
SPANS = base.SPANS
GAP_THRESHOLD = family_driver.GAP_THRESHOLD
BLOCK_COUNTERS = ("denoise_slot_ticks", "commit_slot_ticks", "blocks_committed", "block_tokens_emitted")


class Requests(base.Requests):
    """The deck's stream with ids uniform over the vocabulary but for one id."""

    def __init__(self, traffic: dict, vocab: int, seed: int, without: int):
        super().__init__(traffic, vocab, seed)
        self.without = without

    def next(self) -> tuple:
        prompt_len, new = self.order[self.dealt % len(self.order)]
        self.dealt += 1
        ids = self.rng.integers(0, self.vocab - 1, prompt_len, dtype=np.int32)
        return ids + (ids >= self.without), new


def position_gaps(confidence, masked, chosen, group) -> list:
    """Of every pass (``group``) that had a choice: the best log-confidence
    among the masked positions it passed over less the least among those it
    chose, 0 where the chosen lead."""
    gaps = []
    for g in np.unique(group):
        mine = group == g
        took, left = mine & chosen, mine & masked & ~chosen
        if took.any() and left.any():
            gaps.append(max(0.0, float(confidence[left].max() - confidence[took].min())))
    return gaps


def own_choice(confidence, masked, chosen, group):
    """The positions a reference would unmask itself: in every pass as many as
    were chosen, its most confident masked ones, ties to the lower position."""
    out = np.zeros_like(chosen)
    for g in np.unique(group):
        at = np.flatnonzero((group == g) & masked)
        order = at[np.argsort(-confidence[at], kind="stable")]
        out[order[: int((chosen & (group == g)).sum())]] = True
    return out


def block_gap_rows(family, cfg: dict, params, sample: list, controls=()) -> dict:
    """``{"sound": {"served": [...], "position": [...]}, control: {...}}`` over
    the sampled replies: the served tokens and the served choice of positions
    judged by the float32 reference; for each of ``controls`` (the reference
    computed with that fault) the token it puts first at the same rows and the
    positions it would choose, judged the same way."""
    import jax

    ref = family.Reference(cfg, "float32")
    faulty = {name: family.Reference(cfg, name) for name in controls}
    out = {name: {"served": [], "position": []} for name in ("sound", *controls)}
    with jax.default_matmul_precision("highest"):
        for c in sample:
            lay = family.layout(c.tokens, c.prompt_len, c.token_passes, cfg)
            if not len(lay["rows"]):
                continue
            masked, chosen, group = lay["masked"], lay["chosen"], lay["group"]
            hidden = ref.pass_rows(params, lay)
            best, _, at, lse = ref.head_stats(params, hidden, lay["served"])
            confidence = best - lse
            out["sound"]["served"] += (best - at)[chosen].tolist()
            out["sound"]["position"] += position_gaps(confidence, masked, chosen, group)
            for name, low in faulty.items():
                lay_low = family.layout(c.tokens, c.prompt_len, c.token_passes, cfg, stale_commit=name == "stale_commit")
                low_best, first, _, low_lse = low.head_stats(params, low.pass_rows(params, lay_low), lay["served"])
                _, _, at_first, _ = ref.head_stats(params, hidden, first)
                out[name]["served"] += (best - at_first)[chosen].tolist()
                out[name]["position"] += position_gaps(confidence, masked, own_choice(low_best - low_lse, masked, chosen, group), group)
    return out


def gap_stats(rows: dict) -> dict:
    """The four numbers compared, of one reading's gaps."""
    out = {}
    for kind in ("served", "position"):
        g = np.asarray(rows[kind])
        out[f"{kind}_gap_mean"] = float(g.mean()) if g.size else None
        out[f"{kind}_gap_share"] = float((g > GAP_THRESHOLD).mean()) if g.size else None
    return out


class Driver(family_driver.Driver):
    def __init__(self, ctx: dict):
        super().__init__(ctx)
        self.requests = Requests(self.traffic, self.cfg["vocab_size"], ctx["seed"], int(self.cfg["assumed"]["mask_token_id"]))

    def submit(self, now: float) -> None:
        prompt, new = self.requests.next()
        rid = self.engine.submit(
            prompt, new, arrival_t=now, denoise_steps=self.traffic.get("denoise_steps"),
            confidence_threshold=self.traffic.get("confidence_threshold"),
        )
        self.inflight[rid] = now

    def snapshot(self) -> dict:
        stats = self.engine.stats()
        return dict(super().snapshot(), **{k: stats[k] for k in BLOCK_COUNTERS if k in stats})

    def check(self, control: bool = False) -> dict:
        """``control`` (chipbench/tests/chip_readings.py, never a benchmark run)
        also reads the same four numbers for every control the family's
        reference knows (``CONTROLS``)."""
        controls = tuple(self.family.CONTROLS) if control else ()
        rows = block_gap_rows(self.family, self.cfg, self.params, self.sample(), controls)
        stats = gap_stats(rows["sound"])
        readings = dict(
            stats, checked_tokens=len(rows["sound"]["served"]), checked_passes=len(rows["sound"]["position"]),
            served_logit_gap=max(rows["sound"]["served"], default=None),
        )
        limits = self.ctx["limits"]
        out = {"checks": {k: {"value": v, "limit": limits[k]} for k, v in stats.items() if k in limits}, "readings": readings}
        if control:
            out["control"] = {name: gap_stats(rows[name]) for name in controls}
        return out
