"""``serve_closed_family.py``'s closed loop for a family whose token rows are of
two kinds (``families/afmoe.py``: full layers that keep a sequence's rows for
its whole length, sliding layers that keep a ring of blocks) and whose expert
layer holds a share of the router's experts.

Everything is inherited: the loop, the window, the warm-up, the end-to-end
numbers, and the check with its two bulk statistics of the gaps
(``served_gap_mean``, ``served_gap_share``).  ``snapshot`` adds the engine's
counters of what the two mechanisms did (``window_rows_read``,
``context_rows``, ``moe_pairs_routed``; nothing where the program has none),
which ``serve.window_read_share`` reads over the traced span.  A traffic file is what ``serve_closed.py`` says it is.

One thing is this driver's own: the time a drain may take.  ``serve_closed.py``
gives the replies still due when the window closes a minute (``DRAIN_LIMIT_S``
60), and whatever is not answered by then counts as failed.  A reply of this
mix is up to 4,096 new tokens behind up to 384 prompt chunks, and at the tick
the cell runs at (13-15 ms, PERF.md section 5) the longest card dealt just
before the close needs 58-65 s: the lengths are the issue's and stay, the limit
is ``DRAIN_LIMIT_S`` here, two minutes, for the drain and for the pre-roll
alike.  Nothing inside the measured window changes.
"""

from __future__ import annotations

import importlib.util
import os
import sys


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
WINDOW_COUNTERS = ("window_rows_read", "context_rows", "moe_pairs_routed")
DRAIN_LIMIT_S = 120.0


class Driver(family_driver.Driver):
    def loop(self, seconds: float, probe, refill: bool = True, ticks=None) -> float:
        if probe is None and seconds == base.DRAIN_LIMIT_S:  # the pre-roll and the drain: never the measured window
            seconds = DRAIN_LIMIT_S
        return super().loop(seconds, probe, refill, ticks)

    def snapshot(self) -> dict:
        stats = self.engine.stats()
        return dict(super().snapshot(), **{k: stats[k] for k in WINDOW_COUNTERS if k in stats})
