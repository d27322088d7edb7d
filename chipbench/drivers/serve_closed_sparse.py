"""``serve_closed_window.py``'s closed loop for a family whose attention reads
the rows a learned indexer selects (``families/keye_vl2.py``).

Everything is inherited: the loop, the window, the warm-up, the end-to-end
numbers and the check's two bulk statistics of the gaps.  ``snapshot``
adds the engine's counters of what the indexer scored and what the attention
read (``index_rows_scored``, ``sparse_rows_read``, ``sparse_lane_rows``;
nothing where the program has none), which ``serve.sparse_read_share`` and
``serve.sparse_roofline`` read over the traced span beside ``context_rows``.
A traffic file is what ``serve_closed.py`` says it is.

Two things are this driver's own.  **No drain**: the replies still due when
the window closes are taken as they stand (``ServingEngine.unfinished``: the
tokens read so far and their moments), so that their tokens inside the window
count as ``serve_closed.py`` counts them, and the check reads what they served
so far; a request with no token yet is left out, as a drained one would have
had no token inside the window.  A reply of this mix is up to 6,144 new tokens
behind up to 768 prompt chunks, and a tick-by-tick run of the deck (16 lanes,
one chunk of 32 rows a tick) leaves 5,957-6,855 ticks of replies due at the
close, 152-175 s at the cell's tick of 24-26 ms (PERF.md section 5): a drain
would add as much to a run as its window and its check together.  And the
pre-roll, which ends at its ticks (4,500, about 100 s), may take five minutes
(``PREROLL_LIMIT_S``) where ``serve_closed_window.py`` stops it at two.  The
lengths are the mix's own and stay; nothing inside the measured window changes.

The check compares one number more (``gap_stats``): the share of served tokens
more than ``WIDE_GAP`` under the reference's best (``served_gap_share_wide``).
The two bulk statistics of the expert cells tell the fp8 control from a sound
run by only about twice here (on the chip, 8 replies a run: a sound run's
share and mean 0.21-0.24 and 0.089-0.106, the fp8 control's 0.46-0.50 and
0.24-0.28, the bf16 witness's 0.207 and 0.086 as the sound run's; PERF.md
section 2): a sound run's gaps go with the position, and the routers' eighth
and ninth experts lie within 2e-4.  Past 0.75 the sound runs put 1.1-1.7% of
their tokens and the fp8 control 7.8-10.2%.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys

import numpy as np


def _serve_closed_window():
    """``drivers/serve_closed_window.py`` under the module name ``run.py:load_module`` gives it."""
    name = "chipbench_drivers_serve_closed_window"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_closed_window.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


window_driver = _serve_closed_window()
base = window_driver.base
SPANS = base.SPANS
family_driver = window_driver.family_driver
SPARSE_COUNTERS = ("index_rows_scored", "sparse_rows_read", "sparse_lane_rows")
PREROLL_LIMIT_S = 300.0
WIDE_GAP = 0.75  # past it a sound run puts about 1% of its served tokens, the fp8 control 8-10% (PERF.md section 2)


def gap_stats(gaps: list) -> dict:
    """``serve_closed_family.gap_stats`` and the share of gaps over ``WIDE_GAP``."""
    wide = float((np.asarray(gaps) > WIDE_GAP).mean()) if gaps else None
    return dict(family_driver.gap_stats(gaps), served_gap_share_wide=wide)


class Driver(window_driver.Driver):
    def loop(self, seconds: float, probe, refill: bool = True, ticks=None) -> float:
        if not refill:  # the drain: no tick, the replies still due are taken as they stand at the close
            self.harvest()
            return 0.0
        if probe is None and seconds == base.DRAIN_LIMIT_S:  # the pre-roll: never the measured window
            seconds = PREROLL_LIMIT_S
        return super().loop(seconds, probe, refill, ticks)

    def harvest(self) -> None:
        """Every request still due, out of ``inflight``: the replies completed
        since the last pop (a settle of ``stats()`` reads the tick in flight) as
        they are, the others as ``unfinished`` records that served a token, with
        the status of a reply so that ``serve_closed.py`` counts their tokens."""
        for c in self.engine.pop_finished():
            self.finished.append((self.inflight.pop(c.id), c))
        self.partial = []
        for c in self.engine.unfinished():
            arrival = self.inflight.pop(c.id)
            if c.ttft_ms is not None:
                self.partial.append((arrival, dataclasses.replace(c, status="ok")))
        self.finished += self.partial

    def window(self, seconds: float, probe) -> dict:
        out = super().window(seconds, probe)
        # a reply taken as it stood has its last token inside the window, and is not one completed there
        self.marks["completed"] -= base.window_marks(self.partial, self.t_open, self.t_close)["completed"]
        return out

    def snapshot(self) -> dict:
        stats = self.engine.stats()
        return dict(super().snapshot(), **{k: stats[k] for k in SPARSE_COUNTERS if k in stats})

    def check(self, control: bool = False) -> dict:
        """``serve_closed_family.Driver.check`` with the numbers of ``gap_stats``."""
        controls = tuple(self.family.CONTROLS) if control else ()
        rows = family_driver.gap_rows(
            self.family, self.cfg, self.params, self.sample(), int(self.traffic["new_tokens"]["max"]), controls)
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
